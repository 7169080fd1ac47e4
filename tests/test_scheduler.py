"""Pipelined AutoML executor tests (runtime/scheduler.py + the wiring
in automl.py / models/cv.py / models/gbm.py / models/tree/binning.py):

- HostStream ordering: tasks apply in sequence order whatever order
  they complete/arrive; skip() fills gaps; a gap with no skip is a
  named TimeoutError at drain, never a hang; task errors are captured.
- Device-token exclusivity: two threads can never hold it at once.
- Compile-ahead cache-hit accounting: AOT pre-lowering a config's
  boost executables makes the real train() hit the persistent XLA
  cache (fills cold, warm no-op on resubmission).
- Fused first-dispatch binning: bitwise parity (edges + codes) with
  the two-dispatch fit_bins -> Frame.binned path, and the kill switch.
- Pipelined vs sequential AutoML determinism: identical leaderboard
  ranking, metrics, and resume manifest for the same seed/plan; a
  mid-pipeline ``automl.step`` fault fails the job terminally with the
  finished steps' manifest entries written, and the rerun resumes.
"""

import json
import os
import tempfile
import threading
import time

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.runtime import scheduler as sched

pytestmark = pytest.mark.chaos


# ---------------------------------------------------------------------------
# HostStream / device token / CompileStream units (no device work)
# ---------------------------------------------------------------------------

class TestHostStream:
    def test_out_of_order_submission_applies_in_seq_order(self):
        hs = sched.HostStream(name="t-host-ooo", max_pending=8)
        applied = []
        done = threading.Event()

        def mk(i, sleep=0.0):
            def fn():
                if sleep:
                    time.sleep(sleep)
                applied.append(i)
                if i == 3:
                    done.set()
            return fn

        # seq 1 and 3 arrive BEFORE 0 and 2 — application order must
        # still be 0,1,2,3 (the leaderboard/manifest ordering contract)
        hs.submit(1, mk(1))
        hs.submit(3, mk(3))
        time.sleep(0.1)
        assert applied == []          # held back: seq 0 not in yet
        hs.submit(0, mk(0, sleep=0.05))
        hs.submit(2, mk(2))
        assert done.wait(timeout=10)
        assert applied == [0, 1, 2, 3]
        assert hs.stop(timeout=10)

    def test_skip_fills_gaps(self):
        hs = sched.HostStream(name="t-host-skip", max_pending=8)
        applied = []
        hs.submit(2, lambda: applied.append(2))
        hs.skip(0)
        hs.skip(1)
        assert hs.drain(timeout=10) == []
        assert applied == [2]
        assert hs.stats["skipped"] == 2
        assert hs.stop(timeout=10)

    def test_drain_names_the_wedge(self):
        hs = sched.HostStream(name="t-host-wedge", max_pending=8)
        hs.submit(1, lambda: None)    # seq 0 never submitted or skipped
        with pytest.raises(TimeoutError, match="pending=\\[1\\]"):
            hs.drain(timeout=0.5)
        hs.skip(0)                    # unwedge, then clean shutdown
        assert hs.drain(timeout=10) == []
        assert hs.stop(timeout=10)

    def test_full_queue_of_held_back_seqs_admits_the_gap_filler(self):
        """Regression: a queue full of tasks all held back by a missing
        lower seq must ADMIT that seq's submit (blocking it would
        deadlock the producer against its own backlog)."""
        hs = sched.HostStream(name="t-host-gap", max_pending=2)
        applied = []
        for s in (1, 2):              # fills the bound; worker starves
            hs.submit(s, lambda s=s: applied.append(s))
        time.sleep(0.1)
        hs.submit(0, lambda: applied.append(0))   # must not block
        assert hs.drain(timeout=10) == []
        assert applied == [0, 1, 2]
        assert hs.stop(timeout=10)

    def test_errors_captured_not_raised(self):
        hs = sched.HostStream(name="t-host-err", max_pending=8)
        applied = []

        def boom():
            raise RuntimeError("completion failed")

        hs.submit(0, boom, label="step0")
        hs.submit(1, lambda: applied.append(1))
        errs = hs.drain(timeout=10)
        # the failed task did not stall the stream, and the error is
        # attributed to its seq/label
        assert applied == [1]
        assert len(errs) == 1
        assert errs[0][0] == 0 and errs[0][1] == "step0"
        assert isinstance(errs[0][2], RuntimeError)
        assert hs.stop(timeout=10)


class TestDeviceToken:
    def test_token_exclusivity(self):
        ex = sched.PipelinedExecutor(compile_ahead=0)
        active = []
        overlap = []

        def worker(i):
            with ex.device(f"w{i}"):
                active.append(i)
                if len(active) > 1:
                    overlap.append(tuple(active))
                time.sleep(0.05)
                active.remove(i)

        ts = [threading.Thread(target=worker, args=(i,))
              for i in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert overlap == []
        st = ex.stats()
        assert st["device_steps"] == 4
        assert st["device_busy_s"] >= 4 * 0.05 * 0.9
        ex.shutdown()

    def test_queue_depth_backpressure_and_drop(self):
        # host stream blocks submit at the bound (the bound covers the
        # QUEUED backlog; an in-flight task has already left the queue)
        hs = sched.HostStream(name="t-host-bp", max_pending=2)
        release = threading.Event()
        hs.submit(0, release.wait)     # in-flight, holds the worker
        time.sleep(0.1)
        hs.submit(1, lambda: None)
        hs.submit(2, lambda: None)     # queue now at the bound
        t0 = time.monotonic()

        def unblock():
            time.sleep(0.3)
            release.set()

        threading.Thread(target=unblock).start()
        hs.submit(3, lambda: None)    # must block until a slot frees
        assert time.monotonic() - t0 >= 0.2
        assert hs.drain(timeout=10) == []
        assert hs.stop(timeout=10)


# ---------------------------------------------------------------------------
# data helpers
# ---------------------------------------------------------------------------

def _frame(n=240, seed=7):
    rng = np.random.default_rng(seed)
    x0 = rng.normal(size=n).astype(np.float32)
    x1 = rng.normal(size=n).astype(np.float32)
    y = np.where(x0 + 0.5 * x1 + rng.normal(scale=0.5, size=n) > 0,
                 "p", "n")
    return h2o.Frame.from_arrays({"x0": x0, "x1": x1, "y": y})


# ---------------------------------------------------------------------------
# fused first-dispatch binning parity
# ---------------------------------------------------------------------------

class TestFusedBinning:
    def test_bitwise_parity_with_two_dispatch_path(self, mesh8):
        from h2o_kubernetes_tpu.models.tree.binning import (
            fit_bins, fused_fit_bins)

        rng = np.random.default_rng(3)
        n = 2000
        cols = {f"f{i}": rng.normal(size=n).astype(np.float32)
                for i in range(4)}
        cols["f0"][::13] = np.nan                       # NAs
        cols["c"] = rng.choice(["a", "b", "c"], size=n)  # enum
        # high-cardinality enum: the range-grouping edge path
        cols["hc"] = np.array(
            [f"L{v:03d}" for v in rng.integers(0, 200, size=n)])
        fr = h2o.Frame.from_arrays(cols)
        names = list(cols)

        spec_c = fit_bins(fr, names, 64)
        binned_c = np.asarray(fr.binned(spec_c))
        spec_f, binned_f = fused_fit_bins(fr, names, 64)
        assert np.array_equal(np.asarray(spec_c.edges_matrix()),
                              np.asarray(spec_f.edges_matrix()))
        assert np.array_equal(binned_c, np.asarray(binned_f))
        assert spec_c.is_enum == spec_f.is_enum

        # the fit-key cache: a second fused call is a pure hit
        spec_f2, binned_f2 = fused_fit_bins(fr, names, 64)
        assert spec_f2 is spec_f and binned_f2 is binned_f
        # mutation invalidates via the frame version counter
        from h2o_kubernetes_tpu.frame import Vec

        fr["extra"] = Vec.from_numpy(np.zeros(n, dtype=np.float32),
                                     "extra")
        spec_f3, _ = fused_fit_bins(fr, names, 64)
        assert spec_f3 is not spec_f


# ---------------------------------------------------------------------------
# compile-ahead: cache-hit accounting against the real train path
# ---------------------------------------------------------------------------

class TestCompileAhead:
    def test_compile_ahead_covers_train(self, mesh8, tmp_path):
        """The drift pin: an AOT pre-lowered config's boost programs
        must be persistent-cache HITS when train() dispatches them.
        Control (no AOT) shows misses; the prepared config shows hits
        and strictly fewer misses; a warm resubmission is a no-op."""
        import jax

        from h2o_kubernetes_tpu.models import GBM
        from h2o_kubernetes_tpu.runtime.backend import (
            compile_watch_snapshot, start_compile_watch)

        from jax._src import compilation_cache as _cc

        start_compile_watch()
        prev_dir = jax.config.jax_compilation_cache_dir
        prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 0.0)
        # is_cache_used latches once per process — re-evaluate it with
        # the cache dir now set (and again on restore)
        _cc.reset_cache()
        ident = threading.get_ident()
        fr = _frame(2048, seed=3)

        def train(depth):
            return GBM(ntrees=4, max_depth=depth, seed=1, nfolds=2,
                       fold_assignment="modulo").train(
                y="y", training_frame=fr)

        try:
            train(3)                 # warm every aux program/shape
            b = compile_watch_snapshot(ident)
            train(4)                 # control: fresh depth, no AOT
            a = compile_watch_snapshot(ident)
            ctrl_miss = a["thread_pcache_misses"] \
                - b["thread_pcache_misses"]
            assert ctrl_miss >= 2    # boost @ full + fold shape

            est = GBM(ntrees=4, max_depth=5, seed=1, nfolds=2,
                      fold_assignment="modulo")
            thunks = est.compile_ahead_lowerings("y", fr)
            assert len(thunks) >= 2
            cs = sched.CompileStream(name="t-compile", max_queue=4)
            assert cs.submit("k5", lambda: thunks)
            assert cs.wait_idle(timeout=300)
            assert cs.stats["programs"] == len(thunks)
            assert cs.stats["fills"] >= 2      # cold: cache fills
            b = compile_watch_snapshot(ident)
            train(5)                 # the prepared config
            a = compile_watch_snapshot(ident)
            misses = a["thread_pcache_misses"] \
                - b["thread_pcache_misses"]
            # the two pre-lowered boost programs are NOT compiled again
            # on the device thread. On jax 0.9.0 they arrive through
            # jit's in-memory lowering cache (the AOT `lower()` and the
            # call share one computation object, whose executable is
            # memoized), so the dispatch never asks the persistent
            # cache: no hit is counted, and none is needed — what the
            # stream promises is the missing compiles.
            assert misses <= ctrl_miss - 2, \
                f"pre-lowered boost programs were compiled again " \
                f"(misses={misses}, control={ctrl_miss})"

            # warm resubmission: the promised no-op (hit accounting)
            thunks2 = GBM(ntrees=4, max_depth=5, seed=1, nfolds=2,
                          fold_assignment="modulo"
                          ).compile_ahead_lowerings("y", fr)
            assert cs.submit("k5b", lambda: thunks2)
            assert cs.wait_idle(timeout=300)
            assert cs.stats["warm"] >= len(thunks2)
            assert cs.stop(timeout=30)
        finally:
            jax.config.update("jax_compilation_cache_dir", prev_dir)
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prev_min)
            _cc.reset_cache()

    @staticmethod
    def _small_job(case, monkeypatch):
        """(estimator, frame, row of `_BOOST_PROGRAMS`) of a small job
        in one mode: bernoulli GBM, single-output DRF with `mtries`,
        three-class GBM, three-class DRF; three dispatches of two
        sizes, 2 + 2 + 1 trees (`forest_restart`: the last three of
        five trees, on a checkpoint of the first two)."""
        from h2o_kubernetes_tpu.models import DRF, GBM
        from h2o_kubernetes_tpu.models import gbm as gbm_mod

        rng = np.random.default_rng(11)
        n = 1000                        # padded to 8 shards
        cols = {f"x{i}": rng.normal(size=n).astype(np.float32)
                for i in range(5)}
        score = cols["x0"] + 0.5 * cols["x1"]
        # the row of _BOOST_PROGRAMS: a forest's is its K's
        mode = "multi" if case.startswith("multi") else "single"
        if mode == "multi":
            cols["y"] = np.array(["a", "b", "c"])[
                np.digitize(score, [-0.5, 0.5])]
        else:
            cols["y"] = np.where(score > 0, "p", "n")
        fr = h2o.Frame.from_arrays(cols)
        kw = dict(max_depth=3, nbins=16, mtries=2, seed=1)
        if case == "forest_restart":    # two trees, then three more
            kw["checkpoint"] = DRF(ntrees=2, **kw).train(
                y="y", training_frame=fr)
        est = DRF(ntrees=5, **kw) \
            if "forest" in case else GBM(ntrees=5, max_depth=3, seed=1)
        monkeypatch.setattr(gbm_mod, "_DISPATCH_BUDGET",
                            2 * n * 5 * est.params.nbins * 2 ** 3
                            * (3 if mode == "multi" else 1))
        return est, fr, mode

    @staticmethod
    def _recording(sent, fn):
        """``fn``, noting each call's (function, arguments) in ``sent``."""
        def call(*a):
            sent.append((fn, a))
            return fn(*a)
        return call

    @pytest.mark.parametrize("case", ["single", "forest", "multi",
                                      "multi_forest"])
    def test_lowered_is_what_is_dispatched(self, mesh8, monkeypatch,
                                           case):
        """The drift pin, mode by mode (`_small_job`): every
        program `compile_ahead_lowerings` lowers is one `train()`
        really dispatches and the other way round — the same jitted function,
        the same shapes, dtypes and shardings (none where the array is
        uncommitted) and the same static arguments, so the lowered
        executable is the one the dispatch looks up."""
        import jax

        from h2o_kubernetes_tpu.models import gbm as gbm_mod

        est, fr, mode = self._small_job(case, monkeypatch)
        lowered, sent = [], []
        monkeypatch.setattr(gbm_mod, "_aot",
                            lambda fn, *a: lowered.append((fn, a)))
        for thunk in est.compile_ahead_lowerings("y", fr):
            thunk()

        monkeypatch.setitem(gbm_mod._BOOST_PROGRAMS, mode, self._recording(
            sent, gbm_mod._BOOST_PROGRAMS[mode]))
        monkeypatch.setattr(gbm_mod, "_init_margin",
                            self._recording(sent, gbm_mod._init_margin))
        m = est.train(y="y", training_frame=fr)
        assert m.ntrees == (15 if mode == "multi" else 5)

        def leaf(x):
            if not hasattr(x, "dtype"):
                return x                # a static argument
            sh = x.sharding
            if isinstance(x, jax.Array) and not x.committed:
                sh = None
            return (x.shape, str(x.dtype), sh)

        def same(a, b):
            (fa, la), (fb, lb) = a, b
            la, ta = jax.tree.flatten(la)
            lb, tb = jax.tree.flatten(lb)
            if fa is not fb or ta != tb:
                return False
            for x, y in zip(map(leaf, la), map(leaf, lb)):
                if isinstance(x, tuple) and isinstance(y, tuple) \
                        and x[2] is not None and y[2] is not None:
                    if x[:2] != y[:2] or not x[2].is_equivalent_to(
                            y[2], len(x[0])):
                        return False
                elif x != y:
                    return False
            return True

        programs = {fn.__name__ for fn, _ in sent}
        assert programs == {
            "single": {"_init_margin", "_boost_jit"},
            "forest": {"_boost_jit"},
            "multi": {"_init_margin", "_boost_multi_jit"},
            "multi_forest": {"_boost_multi_jit"}}[case]
        assert len(sent) == (3 if "forest" in case else 4)
        for call in sent:
            assert any(same(call, low) for low in lowered), call
        for low in lowered:
            assert any(same(call, low) for call in sent), low

    @pytest.mark.parametrize("case", ["single", "forest", "multi",
                                      "multi_forest", "forest_restart"])
    def test_no_program_gathers_from_the_binned_matrix(
            self, mesh8, monkeypatch, case):
        """Row descent selects each row's split column while the binned
        matrix streams (`core.row_orig_bins`, PR 31): no program
        `train()` dispatches over the binned matrix — the boost program
        of each mode, and `_stack_predict`, the heap walk a job
        continued from a checkpoint starts its carry with — lowers to a
        `gather` whose operand is the `[rows, F]` `uint8` matrix. On
        the chip each such gather cost ~500 whole reads of what it
        indexed. A fresh forest sends its boost program alone: its
        train metric is read off what the scan carried (PR 33)."""
        import re

        from h2o_kubernetes_tpu.models import gbm as gbm_mod

        est, fr, mode = self._small_job(case, monkeypatch)
        sent = []
        monkeypatch.setitem(gbm_mod._BOOST_PROGRAMS, mode, self._recording(
            sent, gbm_mod._BOOST_PROGRAMS[mode]))
        monkeypatch.setattr(gbm_mod, "_stack_predict", self._recording(
            sent, gbm_mod._stack_predict))
        est.train(y="y", training_frame=fr)
        # a program's dispatches differ in their trees, not their body
        programs = {fn.__name__: (fn, a) for fn, a in sent}
        assert set(programs) == {
            "single": {"_boost_jit"},
            "forest": {"_boost_jit"},
            "multi": {"_boost_multi_jit"},
            "multi_forest": {"_boost_multi_jit"},
            "forest_restart": {"_boost_jit", "_stack_predict"}}[case]
        # `"stablehlo.gather"(%binned, %idx) ... : (tensor<125x5xui8>,`
        from_binned = re.compile(
            r"stablehlo\.gather[^\n]*: \(tensor<\d+x\d+xui8>")
        for name, (fn, a) in programs.items():
            text = fn.lower(*a).as_text()
            assert "xui8>" in text          # the matrix is in there
            assert not from_binned.search(text), name

    def test_unsupported_and_dedupe_accounting(self, mesh8):
        cs = sched.CompileStream(name="t-compile-acct", max_queue=2)
        cs.mark_unsupported()
        assert cs.submit("a", lambda: [])
        assert not cs.submit("a", lambda: [])      # deduped
        assert cs.wait_idle(timeout=30)
        assert cs.stats["unsupported"] == 1
        assert cs.stats["deduped"] == 1
        # builder errors are counted, never raised
        assert cs.submit("b", lambda: 1 / 0)
        assert cs.wait_idle(timeout=30)
        assert cs.stats["errors"] == 1
        assert cs.stop(timeout=30)


# ---------------------------------------------------------------------------
# pipelined vs sequential AutoML: determinism + fault/resume round-trip
# ---------------------------------------------------------------------------

def _strip_walltime(rows):
    return [{k: v for k, v in r.items() if k != "training_time_s"}
            for r in rows]


def _norm_manifest(man):
    return {k: {"fam": v["fam"],
                "metrics": {mk: mv for mk, mv in v["metrics"].items()
                            if mk != "training_time_s"}}
            for k, v in man.items()}


def _run_automl(pipeline: bool, fr, ckpt=None, **kw):
    from h2o_kubernetes_tpu.automl import AutoML

    os.environ["H2O_TPU_AUTOML_PIPELINE"] = "1" if pipeline else "0"
    try:
        aml = AutoML(verbosity=None, checkpoint_dir=ckpt, **kw)
        aml.train(y="y", training_frame=fr)
        return aml
    finally:
        os.environ.pop("H2O_TPU_AUTOML_PIPELINE", None)


def _scheduler_threads():
    return [t.name for t in threading.enumerate() if t.is_alive() and
            (t.name.startswith("h2o-automl-") or
             t.name.startswith("h2o-cv-"))]


class TestPipelinedAutoML:
    def test_pipelined_matches_sequential(self, mesh8):
        """The ordering contract end to end: identical leaderboard
        (ids, ranking, every metric digit) and identical manifest for
        the same seed/plan — pipelined vs H2O_TPU_AUTOML_PIPELINE=0."""
        fr = _frame(240, seed=9)
        kw = dict(max_models=2, nfolds=2, seed=5,
                  include_algos=["glm", "gbm"], project_name="detm")
        with tempfile.TemporaryDirectory() as d1, \
                tempfile.TemporaryDirectory() as d2:
            a_pipe = _run_automl(True, fr, ckpt=d1, **kw)
            a_ser = _run_automl(False, fr, ckpt=d2, **kw)
            assert _strip_walltime(a_pipe.leaderboard.as_list()) == \
                _strip_walltime(a_ser.leaderboard.as_list())
            m1 = json.load(open(os.path.join(
                d1, "automl_manifest.json")))
            m2 = json.load(open(os.path.join(
                d2, "automl_manifest.json")))
            assert list(m1) == list(m2)          # insertion order too
            assert _norm_manifest(m1) == _norm_manifest(m2)
        assert a_pipe.job.status == "DONE"
        assert a_pipe.scheduler_stats is not None
        assert a_pipe.scheduler_stats["device_steps"] == 2
        assert a_pipe.scheduler_stats["host_applied"] == 2
        assert a_ser.scheduler_stats is None     # serial path: no
        assert _scheduler_threads() == []        # executor at all

    def test_mid_pipeline_fault_resumes(self, mesh8):
        """An automl.step device error mid-pipeline: job FAILED
        terminally, the finished step's manifest entry landed BEFORE
        the failure propagated (host stream drained on the error
        path), no scheduler thread left behind — and the rerun with
        the same checkpoint_dir resumes instead of retraining."""
        from h2o_kubernetes_tpu.runtime import faults, health

        fr = _frame(200, seed=12)
        kw = dict(max_models=2, nfolds=2, seed=11,
                  include_algos=["glm", "gbm"], project_name="pfault")
        with tempfile.TemporaryDirectory() as ckpt:
            health.reset()
            with faults.inject("automl.step:device_error@1"):
                with pytest.raises(health.ClusterHealthError):
                    _run_automl(True, fr, ckpt=ckpt, **kw)
            man = json.load(open(os.path.join(
                ckpt, "automl_manifest.json")))
            assert len(man) == 1         # GLM_1 finished + persisted
            assert _scheduler_threads() == []
            health.reset()
            a2 = _run_automl(True, fr, ckpt=ckpt, **kw)
            assert any("resumed from checkpoint" in m
                       for _, m in a2.event_log)
            assert len(a2.leaderboard.rows) == 2
            assert a2.job.status == "DONE"
