"""Multi-config benchmark suite — the BASELINE.json eval configs
beyond the headline GBM number (bench.py):

- ingest: airlines-shaped CSV → Frame rows/s (the pyarrow fast path;
  SURVEY C8 — the reference's parse is chunk-parallel for this);
- config #2a GLM: binomial IRLSM on a HIGGS-shaped table (28 numeric
  features) — ≥50 IRLS iterations on ≥100k rows so the number
  measures the Gram path, not dispatch overhead;
- config #2b DRF: HIGGS-shaped forest — rides the 2-channel
  unit-hessian histogram path (h ≡ 1);
- config #3  XGBoost tree_method=hist semantics — regularized-gain
  boosting on the shared tree core;
- config #3b lambdarank on the MSLR shape (qid groups, graded rel);
- config #4  DeepLearning MLP (model-averaging allreduce) — rows/sec
  through one epoch;
- config #4b Word2Vec skip-gram, Zipf corpus;
- config #5  gbm_score_rows_per_sec — the compiled SERVING fast path
  (flattened-tree scorer + jitted-predict cache, docs/SERVING.md):
  warm ``score_numpy`` rows/s on a 100k-row batch, recorded next to
  the per-call ``predict()`` Frame path it replaces, with a
  recompile check (warm repeat must add 0 scorer-cache misses);
- config #7  ``automl_wall_100k`` — pipelined vs serial AutoML
  wall-clock on the airlines shape (docs/SCALING.md "Pipelined
  AutoML"): two cold subprocess legs with isolated persistent caches,
  leaderboard-identity check, warm-repeat compile count, and the
  scheduler's overlap accounting (device-busy / compile-wait /
  host-busy / compile-ahead fills). ``AUTOML_BENCH_ROWS`` /
  ``AUTOML_BENCH_MODELS`` size it;
- config #6  the 10M-row chunked-data-path proofs (docs/SCALING.md):
  ``ingest_airlines_csv_10m`` — streamed pyarrow record-batch CSV
  ingest of a ~1.5 GB airlines-shaped file; ``gbm_higgs_10m`` — GBM
  training where the uint8 binned matrix is the only full-width
  training-resident array. Row counts via ``BENCH_ROWS_10M``
  (default 10M), tree count via ``BENCH_GBM_10M_TREES`` (default 5).
  Both are single-shot (no warm repeat: one call IS minutes of work).

Every config row carries memory watermarks — ``peak_rss_mb`` (VmHWM:
process-lifetime peak, so a regression anywhere shows in the BENCH
trajectory), ``rss_before_mb``/``rss_after_mb`` (per-config
attribution) and ``device_peak_mb`` (sum of per-device
``memory_stats()`` peaks where the backend reports them; None on
CPU builds that don't).

``BENCH_SUITE_CONFIGS`` (comma list of config names) restricts the run
to a subset — e.g. ``BENCH_SUITE_CONFIGS=gbm_score_rows_per_sec`` for
a quick serving capture; partial runs write to a ``_partial`` file so
they never clobber a full-suite artifact.

- config #8  ``gbm_wide_sparse`` — Exclusive Feature Bundling on a
  ≥1k-column one-hot CTR-style frame (docs/SCALING.md "Wide sparse
  frames"): unbundled (H2O_TPU_EFB=0) vs bundled train wall,
  histogram width F→Fb, binned-matrix bytes both ways.
  ``BENCH_WS_ROWS`` / ``BENCH_WS_GROUPS`` / ``BENCH_WS_CARD`` size it;

- config #5c ``gbm_shap_rows_per_sec`` — compiled TreeSHAP serving
  (docs/SERVING.md "Explainable serving"): warm device
  ``contrib_numpy`` rows/s at a 100k-row serving shape vs the
  host-numpy ``ensemble_shap`` recursion (measured single-shot at the
  same shape), with the device additivity check
  (``sum phi + bias == margin`` to 1e-4), a device-vs-host parity
  check on the slice, and the warm-repeat recompile check.
  ``BENCH_SHAP_ROWS`` / ``BENCH_SHAP_HOST_ROWS`` size it;

- config #6b ``gbm_goss_10m`` — GOSS gradient-based sampling
  (docs/SCALING.md "Gradient-based sampling"): sampled (a=0.1,
  b=0.1) vs unsampled GBM at the 10M airlines shape, matched tree
  count; records histogram rows-per-level (the static compaction
  capacity), steady per-tree train time both legs, and the AUC delta
  with its ≤0.002 acceptance flag. ``BENCH_GOSS_ROWS`` /
  ``BENCH_GOSS_TREES`` size it.

Every config reports BOTH timings: ``compile_seconds`` (the first
call — what a cold user pays, XLA compile included) and ``seconds``
(steady state, compile cached; repeated until ≥1 s of measured work
or 3 calls on the CPU mesh, single repeat on TPU where trains are
long and chip windows are ~20 min). One JSON line per config + a
trailing summary; writes ``BENCH_SUITE_{TPU|CPU}_r14.json`` at the
repo root.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _timed(fn, on_tpu: bool, min_secs: float = 1.0):
    """(out, steady_seconds_per_call, calls, compile_seconds)."""
    t0 = time.perf_counter()
    out = fn()
    compile_dt = time.perf_counter() - t0
    total, calls = 0.0, 0
    max_calls = 1 if on_tpu else 3
    while calls < max_calls:
        t0 = time.perf_counter()
        out = fn()
        total += time.perf_counter() - t0
        calls += 1
        if total >= min_secs:
            break
    return out, total / calls, calls, compile_dt


def _rss_mb() -> float:
    """Current VmRSS in MiB (Linux /proc; 0.0 where unavailable)."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return round(int(ln.split()[1]) / 1024, 1)
    except OSError:
        pass
    return 0.0


def _mem_watermarks() -> dict:
    """Host + device memory watermarks recorded with EVERY config so
    memory regressions show in the BENCH trajectory, not just wall
    clock. peak_rss_mb is ru_maxrss (process-lifetime high-water)."""
    import resource

    import jax

    peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    dev, have = 0, False
    for d in jax.local_devices():
        try:
            ms = d.memory_stats()
        except Exception:
            ms = None
        if ms:
            dev += ms.get("peak_bytes_in_use",
                          ms.get("bytes_in_use", 0))
            have = True
    return {"peak_rss_mb": round(peak_kb / 1024, 1),
            "rss_after_mb": _rss_mb(),
            "device_peak_mb": round(dev / 2 ** 20, 1) if have else None}


def main() -> int:
    from h2o_kubernetes_tpu.runtime.backend import \
        enable_persistent_compile_cache

    enable_persistent_compile_cache()
    import jax
    import numpy as np

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import (DRF, GBM, GLM, DeepLearning,
                                           Word2Vec, XGBoost)
    from tools import datasets as D

    platform = jax.default_backend()
    on_tpu = platform == "tpu"
    rows = int(os.environ.get("BENCH_SUITE_ROWS",
                              1_000_000 if on_tpu else 30_000))
    results = []
    only = {c.strip() for c in os.environ.get(
        "BENCH_SUITE_CONFIGS", "").split(",") if c.strip()}

    def _want(name: str) -> bool:
        return not only or name in only

    if on_tpu and _want("automl_wall_100k"):
        # a chip belongs to ONE process: this parent has initialised
        # JAX and holds it, so the automl_scale.py children that leg
        # starts could never get the device (they would fail or hang),
        # and their per-leg temporary compile caches could never hit
        # anyway. Refused up front, before any config has run. The
        # benchmark PR (ROADMAP Queue 1 item 2) replaces the leg with
        # one that runs both legs in the process that owns the chip.
        raise SystemExit(
            "bench_suite automl_wall_100k: refuses to run on a TPU — "
            "the parent process holds the chip and the automl_scale.py "
            "children it starts cannot share it; select other configs "
            "(BENCH_SUITE_CONFIGS) or run it on CPU")

    _higgs_cache: dict = {}

    def _higgs(nr, seed=None):
        key = (nr, seed)
        if key not in _higgs_cache:
            _higgs_cache[key] = (D.higgs_frame(nr) if seed is None
                                 else D.higgs_frame(nr, seed=seed))
        return _higgs_cache[key]

    rss_mark = [_rss_mb()]

    def record(config, value, unit, seconds, calls, compile_s, **extra):
        row = {"config": config, "value": round(value, 1), "unit": unit,
               "seconds": round(seconds, 3), "calls": calls,
               "compile_seconds": round(compile_s, 3), "rows": rows,
               "platform": platform,
               "rss_before_mb": rss_mark[0], **_mem_watermarks(),
               **extra}
        rss_mark[0] = row["rss_after_mb"]
        results.append(row)
        print(json.dumps(row), flush=True)

    if _want("ingest_airlines_csv"):
        # ingest: airlines-shaped CSV through import_file (arrow fast
        # path)
        import tempfile
        ing_rows = min(max(rows, 100_000), 2_000_000)
        with tempfile.TemporaryDirectory() as td:
            csv_path = os.path.join(td, "air.csv")
            D.airlines_csv(csv_path, ing_rows, chunk=1_000_000)
            mb = os.path.getsize(csv_path) / 1e6
            fr_ing, dt, calls, cdt = _timed(
                lambda: h2o.import_file(csv_path), on_tpu)
            ncells = ing_rows * fr_ing.ncols
            record("ingest_airlines_csv", ing_rows / dt, "rows/s", dt,
                   calls, cdt, rows_ingest=ing_rows, mb=round(mb, 1),
                   cells_per_s=round(ncells / dt, 1),
                   mb_per_s=round(mb / dt, 2))

    if _want("glm_binomial_irlsm"):
        # config #2a: GLM binomial IRLSM — north-star "GLM iters/sec".
        # 50 iterations on >=100k rows: the r04 number (4 iters on 15k
        # rows, 0.024 s) measured dispatch, not the Gram path.
        fr_glm = _higgs(rows if on_tpu else max(rows, 100_000))
        # epsilons at 0 force the full 50 iterations — the benchmark
        # wants a fixed, comparable amount of Gram work, not a
        # convergence race
        m, dt, calls, cdt = _timed(lambda: GLM(
            family="binomial", solver="IRLSM", lambda_=0.0,
            max_iterations=50, objective_epsilon=0.0, beta_epsilon=0.0,
            seed=1).train(y="y", training_frame=fr_glm), on_tpu)
        record("glm_binomial_irlsm", m.n_iterations / dt, "iters/s", dt,
               calls, cdt, iterations=m.n_iterations,
               rows_glm=fr_glm.nrows,
               auc=round(float(
                   m.model_performance(fr_glm, y="y")["auc"]), 5))

    ntrees, depth = 10, 8
    if _want("drf_higgs"):
        # config #2b: DRF (unit-hessian 2-channel histograms)
        fr = _higgs(rows)
        m, dt, calls, cdt = _timed(lambda: DRF(
            ntrees=ntrees, max_depth=depth, seed=1).train(
            y="y", training_frame=fr), on_tpu)
        record("drf_higgs", fr.nrows * ntrees / dt, "rows*trees/s",
               dt, calls, cdt, ntrees=ntrees, max_depth=depth)

    if _want("xgboost_hist"):
        # config #3: XGBoost hist semantics
        fr = _higgs(rows)
        m, dt, calls, cdt = _timed(lambda: XGBoost(
            ntrees=ntrees, max_depth=6, learn_rate=0.2, seed=1).train(
            y="y", training_frame=fr), on_tpu)
        record("xgboost_hist", fr.nrows * ntrees / dt, "rows*trees/s",
               dt, calls, cdt, ntrees=ntrees, max_depth=6)

    if _want("gbm_multinomial"):
        # multinomial GBM: K class trees per round through the
        # class-flattened batching rule (custom_vmap lowers the class
        # axis into the node axis — the round-4 Mosaic fix; K x fuller
        # MXU M)
        mn_rows = min(rows, 500_000)
        rngm = np.random.default_rng(3)
        Xm = rngm.normal(size=(mn_rows, 10)).astype(np.float32)
        score = Xm[:, 0] + 0.5 * Xm[:, 1]
        ym = np.where(score > 0.6, "a",
                      np.where(score < -0.6, "b",
                               np.where(Xm[:, 2] > 0, "c", "d")))
        mcols = {f"f{i}": Xm[:, i] for i in range(10)}
        mcols["y"] = ym
        fr_mn = h2o.Frame.from_arrays(mcols)
        mn_ntrees = 5
        m, dt, calls, cdt = _timed(lambda: GBM(
            ntrees=mn_ntrees, max_depth=5, learn_rate=0.2, seed=1).train(
            y="y", training_frame=fr_mn), on_tpu)
        record("gbm_multinomial", mn_rows * mn_ntrees * m.nclasses / dt,
               "rows*classtrees/s", dt, calls, cdt, rows_mn=mn_rows,
               classes=m.nclasses,
               logloss=round(float(
                   m.scoring_history[-1].get("train_logloss",
                                             float("nan"))), 5))

    if _want("xgboost_lambdarank"):
        # config #3b: lambdarank (MSLR-WEB30K shape — graded relevance
        # over query groups, rank:ndcg LambdaMART)
        rk_rows = min(rows, 200_000)
        fr_rk = D.mslr_frame(rk_rows, seed=4, n_features=20)
        m, dt, calls, cdt = _timed(lambda: XGBoost(
            ntrees=10, max_depth=6, objective="rank:ndcg", seed=1).train(
            y="rel", training_frame=fr_rk, group_column="qid"), on_tpu)
        ndcg = m.model_performance(fr_rk, y="rel")
        record("xgboost_lambdarank", rk_rows * 10 / dt, "rows*trees/s",
               dt, calls, cdt, rows_rank=rk_rows,
               ndcg10=round(float(ndcg.get("ndcg@10", float("nan"))), 5))

    if _want("deeplearning_mlp"):
        # config #4: DeepLearning MLP, one pass (model-averaging
        # allreduce)
        dl_rows = min(rows, 200_000)
        fr_dl = _higgs(dl_rows, seed=2)
        m, dt, calls, cdt = _timed(lambda: DeepLearning(
            hidden=[64, 64], epochs=1, seed=1).train(
            y="y", training_frame=fr_dl), on_tpu)
        record("deeplearning_mlp", dl_rows / dt, "rows/s", dt, calls,
               cdt, rows_dl=dl_rows, hidden=[64, 64])

    if _want("word2vec_skipgram"):
        # config #4b: Word2Vec skip-gram over a Zipf NA-delimited corpus
        n_tok = 200_000
        toks = D.text8_like_tokens(n_tok, vocab_size=5_000, seed=5)
        fr_w2v = h2o.Frame.from_arrays({"words": np.array(toks)})
        m, dt, calls, cdt = _timed(lambda: Word2Vec(
            vec_size=32, epochs=1, min_word_freq=2, seed=1).train(
            fr_w2v), on_tpu)
        record("word2vec_skipgram", n_tok / dt, "tokens/s", dt, calls,
               cdt, tokens=n_tok, vec_size=32)

    if _want("gbm_score_rows_per_sec"):
        # config #5: the compiled serving fast path (ISSUE 2 tentpole)
        # on a HIGGS-shaped table: warm score_numpy at the full batch
        # AND the "100k×1" per-call shape, against the pre-flattening
        # per-call predict() baseline, with the warm-repeat recompile
        # check. THE harness lives in bench.py::measure_scoring (one
        # protocol for bench.py score mode and this config — no drift).
        from bench import measure_scoring

        sc_rows = int(os.environ.get("BENCH_SCORE_ROWS", 100_000))
        fr_sc = _higgs(sc_rows, seed=6)
        m_sc = GBM(ntrees=20, max_depth=5, learn_rate=0.2, seed=1).train(
            y="y", training_frame=fr_sc)
        X_sc = np.asarray(m_sc._design_matrix(fr_sc))[:sc_rows]
        fr_1 = h2o.Frame.from_arrays(
            {n_: fr_sc.vec(n_).to_numpy()[:1]
             for n_ in fr_sc.names if n_ != "y"})
        out = measure_scoring(m_sc, fr_sc, fr_1, X_sc, sc_rows,
                              reps_full=1 if on_tpu else 3)
        record("gbm_score_rows_per_sec", out.pop("value"),
               out.pop("unit"), out.pop("seconds"), out.pop("calls"),
               out.pop("compile_seconds"),
               rows_score=out.pop("rows"), ntrees=20, max_depth=5,
               **out)

    if _want("gbm_shap_rows_per_sec"):
        # config #5c (ISSUE 10): compiled TreeSHAP serving — the
        # device path-enumeration kernel (models/tree/shap.flat_shap,
        # dispatched via Model.contrib_numpy through the jitted-scorer
        # cache) against the host-numpy ensemble_shap recursion it
        # replaces on the serving path. The host leg is measured on a
        # SLICE (the recursion is linear in rows — per-node numpy ops
        # are [rows]-vectorized, so rows/s is shape-stable) and
        # reported as rows/s; the device leg runs the full serving
        # shape warm, with the recompile check and the on-device
        # additivity + host-parity assertions recorded in the row.
        import jax.numpy as jnp

        from h2o_kubernetes_tpu.models.base import scorer_cache_stats
        from h2o_kubernetes_tpu.models.tree.binning import apply_bins_jit
        from h2o_kubernetes_tpu.models.tree.shap import ensemble_shap

        sh_rows = int(os.environ.get("BENCH_SHAP_ROWS", 100_000))
        fr_sh = _higgs(sh_rows, seed=6)
        m_sh = GBM(ntrees=20, max_depth=5, learn_rate=0.2,
                   seed=1).train(y="y", training_frame=fr_sh)
        X_sh = np.asarray(m_sh._design_matrix(fr_sh))[:sh_rows]
        phi, dt, calls, cdt = _timed(
            lambda: m_sh.contrib_numpy(X_sh), on_tpu)
        # warm-repeat recompile check: one more full-shape call must
        # add zero scorer-cache misses
        s0 = scorer_cache_stats()
        m_sh.contrib_numpy(X_sh)
        warm_misses = scorer_cache_stats()["misses"] - s0["misses"]
        # device additivity: sum_f phi + bias == the flat margin
        margins = np.asarray(
            m_sh._margins(jnp.asarray(X_sh)))[:sh_rows]
        add_err = float(np.abs(phi.sum(axis=1) - margins).max())
        # host-numpy baseline + parity — at the FULL serving shape by
        # default (single-shot, like the 10M configs: the recursion is
        # ~10s at 100k rows); BENCH_SHAP_HOST_ROWS shrinks it for
        # quick captures
        host_rows = min(sh_rows,
                        int(os.environ.get("BENCH_SHAP_HOST_ROWS",
                                           sh_rows)))
        binned_h = np.asarray(apply_bins_jit(
            jnp.asarray(X_sh[:host_rows]), m_sh._edges,
            m_sh._enum_mask, m_sh.bin_spec.na_bin))
        trees_np = {f: np.asarray(getattr(m_sh.trees, f))
                    for f in ("split_feat", "split_bin", "na_left",
                              "is_split", "value", "cover")}
        t0 = time.perf_counter()
        phi_h = ensemble_shap(trees_np, binned_h,
                              len(m_sh.feature_names),
                              m_sh.bin_spec.na_bin)
        host_dt = time.perf_counter() - t0
        phi_h[:, -1] += float(m_sh.init_score)
        parity_err = float(np.abs(phi[:host_rows] - phi_h).max())
        dev_rps = sh_rows / dt
        host_rps = host_rows / host_dt
        # XLA-vs-kernel leg pair (ISSUE 17): each impl forced via
        # H2O_TPU_SHAP_KERNEL on a FRESH pickle copy — the scorer
        # cache keys on shape, not impl, so a warm executable would
        # otherwise shadow the flip. The kernel leg is recorded ONLY
        # with a chip attached: off-chip the Pallas kernel runs in
        # INTERPRET mode, which is a correctness harness, not a
        # throughput claim.
        import pickle

        def _impl_leg(env_val):
            mc = pickle.loads(pickle.dumps(m_sh))
            os.environ["H2O_TPU_SHAP_KERNEL"] = env_val
            try:
                phi_l, dt_l, _, _ = _timed(
                    lambda: mc.contrib_numpy(X_sh), on_tpu)
            finally:
                os.environ.pop("H2O_TPU_SHAP_KERNEL", None)
            return phi_l, sh_rows / dt_l

        phi_x, xla_rps = _impl_leg("0")
        legs = {"xla_rows_per_s": round(xla_rps, 1)}
        if on_tpu:
            phi_k, k_rps = _impl_leg("1")
            legs.update(
                kernel_rows_per_s=round(k_rps, 1),
                kernel_speedup_vs_xla=round(
                    k_rps / max(xla_rps, 1e-9), 2),
                kernel_vs_xla_bitwise=bool(
                    np.array_equal(phi_k, phi_x)))
        else:
            legs.update(
                kernel_rows_per_s=None,
                kernel_leg="skipped: no chip attached (interpret "
                           "mode is excluded from throughput claims)")
        record("gbm_shap_rows_per_sec", dev_rps, "rows/s", dt, calls,
               cdt, rows_shap=sh_rows, ntrees=20, max_depth=5,
               host_rows=host_rows, host_seconds=round(host_dt, 3),
               host_rows_per_s=round(host_rps, 1),
               speedup_vs_host=round(dev_rps / max(host_rps, 1e-9), 1),
               additivity_max_err=add_err,
               host_parity_max_err=parity_err,
               warm_repeat_misses=warm_misses, **legs)
        del fr_sh, m_sh, X_sh, phi

    if _want("automl_wall_100k"):
        # config #7: pipelined AutoML wall-clock (ISSUE 5 tentpole) on
        # the AUTOML_SCALE airlines shape. Two COLD legs in separate
        # subprocesses — serial (H2O_TPU_AUTOML_PIPELINE=0) then
        # pipelined — each with its own fresh persistent-cache dir so
        # neither inherits the other's compiles; the pipelined leg
        # also runs automl_scale's warm repeat (warm-repeat compile
        # count must stay 0). Recorded: the wall ratio, per-leg walls
        # and compile counts, the scheduler overlap accounting
        # (device-busy / compile-wait / host-busy / compile-ahead
        # fills), and the leaderboard identity check (model ids,
        # ranking, metrics to every printed digit — wall-clock fields
        # excluded). NOTE: on a single-core host the streams time-slice
        # one CPU, so the ratio is bounded near 1.0 by construction —
        # the overlap stats still show what LEFT the critical path
        # (the wall win materializes where the compile/host streams
        # have their own core).
        import subprocess
        import tempfile

        aml_rows = int(os.environ.get("AUTOML_BENCH_ROWS", 100_000))
        aml_models = int(os.environ.get("AUTOML_BENCH_MODELS", 2))

        def _aml_leg(pipeline: str, cache_dir: str, out_path: str,
                     recompile_check: bool) -> dict:
            env = dict(os.environ, JAX_PLATFORMS="cpu",
                       H2O_TPU_AUTOML_PIPELINE=pipeline,
                       JAX_COMPILATION_CACHE_DIR=cache_dir)
            cmd = [sys.executable,
                   os.path.join(REPO, "tools", "automl_scale.py"),
                   "--rows", str(aml_rows),
                   "--max-models", str(aml_models),
                   "--nfolds", "3",
                   "--include-algos", "glm", "gbm",
                   "--out", out_path]
            if not recompile_check:
                cmd.append("--no-recompile-check")
            r = subprocess.run(cmd, cwd=REPO, env=env,
                               capture_output=True)
            if r.returncode != 0:
                raise RuntimeError(
                    f"automl_wall leg pipeline={pipeline} rc="
                    f"{r.returncode}: "
                    f"{r.stderr.decode(errors='replace')[-400:]}")
            with open(out_path) as f:
                out = json.load(f)
            # run_shape swallows AutoML crashes into 'error' (and
            # automl_scale still exits 0) — a crashed leg must fail
            # the config, not record a 0-second "identical" row
            err = out["curve"][0].get("error")
            if err:
                raise RuntimeError(
                    f"automl_wall leg pipeline={pipeline} AutoML "
                    f"crashed: {err[-400:]}")
            return out

        def _strip_rows(rows):
            return [{k: v for k, v in r.items()
                     if k != "training_time_s"} for r in rows]

        with tempfile.TemporaryDirectory() as td:
            serial = _aml_leg("0", os.path.join(td, "cache_serial"),
                              os.path.join(td, "serial.json"), False)
            pipe = _aml_leg("1", os.path.join(td, "cache_pipe"),
                            os.path.join(td, "pipe.json"), True)
        s0, p0 = serial["curve"][0], pipe["curve"][0]
        lb_identical = _strip_rows(s0["leaderboard"]) == \
            _strip_rows(p0["leaderboard"])
        ratio = s0["wall_seconds"] / max(p0["wall_seconds"], 1e-9)
        rc = pipe.get("recompile_check") or {}
        record("automl_wall_100k", ratio, "x_speedup_vs_serial",
               p0["wall_seconds"], 1, 0.0,
               rows_automl=aml_rows, max_models=aml_models, nfolds=3,
               serial_wall_s=s0["wall_seconds"],
               pipelined_wall_s=p0["wall_seconds"],
               serial_compiles=s0["xla_compiles"],
               pipelined_compiles=p0["xla_compiles"],
               warm_repeat_compiles=rc.get("warm_compiles"),
               leaderboard_identical=lb_identical,
               leader=p0["leader"], leader_auc=p0["leader_auc"],
               scheduler_stats=p0.get("scheduler_stats"))

    if _want("multitenant_zipf_p99"):
        # config #5b (ISSUE 7): multi-tenant serving under a byte-
        # budgeted executable cache — ≥100 registry-pushed tenants,
        # Zipf(s) popularity, per-decile p99, residency vs budget,
        # evict→promote pcache proof, and the hot-model storm with
        # fairness ON vs OFF (the unfair leg must provably miss the
        # tail's SLO). Runs in THIS process (self-hosted REST server);
        # see tools/score_load.run_zipf_bench for the contract.
        from tools.score_load import run_zipf_bench

        mt_models = int(os.environ.get("BENCH_MT_MODELS", 100))
        t0 = time.perf_counter()
        mt = run_zipf_bench(
            n_models=mt_models,
            seconds=float(os.environ.get("BENCH_MT_SECONDS", 20)),
            zipf_s=float(os.environ.get("BENCH_MT_ZIPF_S", 1.1)),
            budget_mb=float(os.environ.get("BENCH_MT_BUDGET_MB", 4.0)))
        dt = time.perf_counter() - t0
        sweep = mt["sweep"]
        res = sweep["residency"]
        tail_decile = sweep["deciles"][-1] if sweep["deciles"] else {}
        record("multitenant_zipf_p99",
               sweep["p99_ms"] or 0.0, "p99_ms", dt, 1, 0.0,
               models=mt["models"], zipf_s=mt["zipf_s"],
               budget_mb=mt["budget_mb"],
               sweep_requests=sweep["requests"],
               sweep_rows_per_s=sweep["value"],
               sweep_p50_ms=sweep["p50_ms"],
               sweep_fivexx=sweep["fivexx"],
               tail_decile_p99_ms=tail_decile.get("p99_ms"),
               deciles=sweep["deciles"],
               residency=res,
               budget_held=bool(res["samples"] > 0
                                and res["budget_exceeded"] == 0),
               promotions=res["promotions_delta"],
               promotion_compiles_all_pcache_hits=bool(
                   res["pcache_misses_delta"] == 0
                   and res["compiles_delta"]
                   == res["pcache_hits_delta"]),
               evict_promote_bitwise=mt["evict_promote_bitwise"],
               storm_fair=mt["storm_fair"],
               storm_unfair=mt["storm_unfair"],
               fair_tail_slo_met=mt["storm_fair"]["tail_slo_met"],
               unfair_tail_slo_met=mt["storm_unfair"]["tail_slo_met"],
               scorer_cache_final=mt["scorer_cache_final"],
               # exposition-cost hygiene (ISSUE 14): one /metrics
               # scrape timed before + after the sweep; acceptance
               # note = the post-sweep scrape (full tenant series
               # resident) costs < 1% of the storm-shape p99, so
               # Prometheus polling cannot move the serving tail
               metrics_scrape=mt.get("metrics_scrape"),
               metrics_scrape_under_1pct_p99=bool(
                   mt.get("metrics_scrape", {}).get(
                       "after", {}).get("ok")
                   and (sweep["p99_ms"] or 0) > 0
                   and mt["metrics_scrape"]["after"]["ms"]
                   < 0.01 * sweep["p99_ms"]))

    if _want("router_zipf_p99"):
        # config #5d (ISSUE 11): the tenant-sharded fleet router vs
        # the everyone-has-everything pool at EQUAL total cache
        # budget — the same Zipf tenant storm through (a) a 3-shard
        # fleet behind the device-free front-door router (catalog
        # rendezvous-placed, head replicated) and (b) a direct
        # 3-replica pool where every replica holds the full catalog
        # under the same per-replica byte budget. Real subprocess
        # pods both ways; acceptance: router head-decile p99 within
        # 1.3x of the direct baseline (the routing hop must be
        # cheap), aggregate rows/s + tail-decile p99 recorded for
        # both. See tools/score_load.run_router_bench.
        from tools.score_load import run_router_bench

        t0 = time.perf_counter()
        rt = run_router_bench(
            tenants=int(os.environ.get("BENCH_ROUTER_TENANTS", 120)),
            shards=int(os.environ.get("BENCH_ROUTER_SHARDS", 3)),
            head=int(os.environ.get("BENCH_ROUTER_HEAD", 8)),
            budget_bytes=int(os.environ.get("BENCH_ROUTER_BUDGET",
                                            2_000_000)),
            seconds=float(os.environ.get("BENCH_ROUTER_SECONDS", 15)),
            zipf_s=float(os.environ.get("BENCH_ROUTER_ZIPF_S", 1.1)))
        dt = time.perf_counter() - t0
        record("router_zipf_p99",
               rt["router"]["head_p99_ms"] or 0.0, "p99_ms", dt, 1,
               0.0, tenants=rt["tenants"], shards=rt["shards"],
               head=rt["head"], budget_bytes=rt["budget_bytes"],
               zipf_s=rt["zipf_s"],
               router_leg=rt["router"], direct_leg=rt["direct"],
               head_p99_ratio=rt["head_p99_ratio"],
               head_p99_within_1_3x=rt["head_p99_within_1_3x"],
               router_rows_per_s=rt["router"]["rows_per_s"],
               direct_rows_per_s=rt["direct"]["rows_per_s"],
               router_tail_p99_ms=rt["router"]["tail_p99_ms"],
               direct_tail_p99_ms=rt["direct"]["tail_p99_ms"],
               router_metrics_scrape=rt["router"].get(
                   "metrics_scrape"),
               direct_metrics_scrape=rt["direct"].get(
                   "metrics_scrape"))

    if _want("gbm_wide_sparse"):
        # config #8 (ISSUE 8): Exclusive Feature Bundling on a >= 1k-
        # column one-hot-dominated CTR-style frame (docs/SCALING.md
        # "Wide sparse frames"). Two in-process legs on the SAME
        # frame: unbundled (H2O_TPU_EFB=0) then bundled
        # (H2O_TPU_EFB=1); recorded: the train-wall ratio, the
        # histogram width F -> Fb (also the per-level psum payload
        # factor), and the binned-matrix bytes both ways. The
        # env-keyed plan cache keeps the legs honest (EFB=0 never
        # builds a plan; the bundled leg's cold wall INCLUDES the
        # planning + bundled-apply passes).
        from h2o_kubernetes_tpu.models.tree import efb as E

        ws_rows = int(os.environ.get("BENCH_WS_ROWS",
                                     min(max(rows, 20_000), 100_000)))
        ws_groups = int(os.environ.get("BENCH_WS_GROUPS", 40))
        ws_card = int(os.environ.get("BENCH_WS_CARD", 25))
        fr_ws = D.wide_sparse_frame(ws_rows, n_groups=ws_groups,
                                    group_card=ws_card, seed=9)
        F_ws = fr_ws.ncols - 1
        padded_ws = fr_ws.vec("d0").padded_len
        ws_trees, ws_depth = 5, 5

        _efb_prior = os.environ.get("H2O_TPU_EFB")

        def _restore_efb():
            if _efb_prior is None:
                os.environ.pop("H2O_TPU_EFB", None)
            else:
                os.environ["H2O_TPU_EFB"] = _efb_prior

        def _ws_leg(efb_env):
            os.environ["H2O_TPU_EFB"] = efb_env
            try:
                return _timed(lambda: GBM(
                    ntrees=ws_trees, max_depth=ws_depth, learn_rate=0.2,
                    seed=1).train(y="y", training_frame=fr_ws), on_tpu)
            finally:
                _restore_efb()

        m_u, dt_u, calls_u, cdt_u = _ws_leg("0")
        m_b, dt_b, calls_b, cdt_b = _ws_leg("1")
        os.environ["H2O_TPU_EFB"] = "1"
        try:
            names_ws = [n for n in fr_ws.names if n != "y"]
            _, plan_ws = E.fit_plan_cached(fr_ws, names_ws,
                                           m_b.params.nbins)
        finally:
            _restore_efb()
        fb = plan_ws.fb if plan_ws is not None else F_ws
        # both legs must train the same model family; the structural
        # check rides the varimp ranking head (full bitwise parity is
        # tier-1 tested — tests/test_efb.py)
        top_u = sorted(m_u.varimp(), key=m_u.varimp().get)[-3:]
        top_b = sorted(m_b.varimp(), key=m_b.varimp().get)[-3:]
        record("gbm_wide_sparse", dt_u / max(dt_b, 1e-9),
               "x_speedup_vs_unbundled", dt_b, calls_b, cdt_b,
               rows_ws=ws_rows, features=F_ws, ntrees=ws_trees,
               max_depth=ws_depth,
               unbundled_wall_s=round(dt_u, 3),
               bundled_wall_s=round(dt_b, 3),
               unbundled_cold_s=round(cdt_u, 3),
               bundled_cold_s=round(cdt_b, 3),
               hist_width_unbundled=F_ws, hist_width_bundled=fb,
               hist_width_reduction=round(F_ws / max(fb, 1), 1),
               binned_mb_unbundled=round(padded_ws * F_ws / 2**20, 1),
               binned_mb_bundled=round(padded_ws * fb / 2**20, 1),
               bundles=sum(1 for c in (plan_ws.cols if plan_ws else [])
                           if c[0] == "bundle"),
               efb_conflicts=plan_ws.conflicts if plan_ws else None,
               efb_demoted=len(plan_ws.demoted) if plan_ws else None,
               varimp_top3_agree=top_u == top_b)
        del fr_ws, m_u, m_b

    # -- config #6: the 10M-row chunked-path proofs --------------------
    rows_10m = int(os.environ.get("BENCH_ROWS_10M", 10_000_000))

    if _want("gbm_goss_10m"):
        # config #6b (ISSUE 13): GOSS gradient-based one-side sampling
        # at the 10M airlines shape (docs/SCALING.md "Gradient-based
        # sampling") — sampled (a=0.1, b=0.1) vs unsampled legs at
        # matched tree count. Records the histogram rows-per-level the
        # kernel actually streams (the static compaction capacity),
        # steady per-tree train time both ways, and the AUC delta.
        # Acceptance: >=2.5x steady per-tree with GOSS on, |dAUC| <=
        # 0.002. BENCH_GOSS_ROWS/TREES shrink it for partial captures;
        # below 2M rows each leg runs cold+warm so the steady number
        # is compile-free, at the full shape legs are single-shot.
        import gc

        from h2o_kubernetes_tpu.models.tree import core as TC
        from h2o_kubernetes_tpu.runtime import mesh as meshlib

        goss_rows = int(os.environ.get("BENCH_GOSS_ROWS", rows_10m))
        nt_g = int(os.environ.get("BENCH_GOSS_TREES", 10))
        a_s = os.environ.get("BENCH_GOSS_TOP_A", "0.1")
        b_s = os.environ.get("BENCH_GOSS_RAND_B", "0.1")
        fr_g = D.airlines_frame(goss_rows, seed=10)
        padded_g = fr_g.vec("Year").padded_len
        shards = meshlib.global_mesh().shape[meshlib.ROWS]
        cap_rows = shards * TC.goss_cap_rows(
            padded_g // shards, float(a_s), float(b_s))
        legs = 1 if goss_rows > 2_000_000 else 2
        _goss_prior = {k: os.environ.get(k) for k in
                       ("H2O_TPU_GOSS", "H2O_TPU_GOSS_TOP_A",
                        "H2O_TPU_GOSS_RAND_B")}

        def _goss_leg(on: bool):
            os.environ["H2O_TPU_GOSS"] = "1" if on else "0"
            os.environ["H2O_TPU_GOSS_TOP_A"] = a_s
            os.environ["H2O_TPU_GOSS_RAND_B"] = b_s
            try:
                walls = []
                for _ in range(legs):
                    t0 = time.perf_counter()
                    mg = GBM(ntrees=nt_g, max_depth=5, learn_rate=0.2,
                             seed=1).train(y="IsDepDelayed",
                                           training_frame=fr_g)
                    walls.append(time.perf_counter() - t0)
                auc = float(mg.scoring_history[-1].get(
                    "train_auc", float("nan")))
                del mg
                gc.collect()
                return walls[0], walls[-1], auc
            finally:
                for k, v in _goss_prior.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v

        cold_off, steady_off, auc_off = _goss_leg(False)
        cold_on, steady_on, auc_on = _goss_leg(True)
        ratio = steady_off / max(steady_on, 1e-9)
        record("gbm_goss_10m", ratio,
               "x_per_tree_speedup_vs_unsampled", steady_on, legs,
               cold_on, rows_goss=goss_rows, ntrees=nt_g, max_depth=5,
               goss_top_a=float(a_s), goss_rand_b=float(b_s),
               unsampled_wall_s=round(steady_off, 3),
               sampled_wall_s=round(steady_on, 3),
               unsampled_cold_s=round(cold_off, 3),
               sampled_cold_s=round(cold_on, 3),
               per_tree_s_unsampled=round(steady_off / nt_g, 4),
               per_tree_s_sampled=round(steady_on / nt_g, 4),
               hist_rows_per_level_unsampled=padded_g,
               hist_rows_per_level_sampled=cap_rows,
               hist_rows_reduction=round(padded_g / max(cap_rows, 1),
                                         2),
               auc_unsampled=round(auc_off, 5),
               auc_sampled=round(auc_on, 5),
               auc_delta=round(abs(auc_off - auc_on), 5),
               auc_within_0_002=bool(abs(auc_off - auc_on) <= 0.002),
               per_tree_speedup_ge_2_5x=bool(ratio >= 2.5))
        del fr_g
        gc.collect()

    if _want("ingest_airlines_csv_10m"):
        import gc
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            csv_path = os.path.join(td, "air10m.csv")
            t0 = time.perf_counter()
            D.airlines_csv(csv_path, rows_10m, chunk=1_000_000)
            gen_dt = time.perf_counter() - t0
            mb = os.path.getsize(csv_path) / 1e6
            t0 = time.perf_counter()
            fr10 = h2o.import_file(csv_path)
            dt = time.perf_counter() - t0
            assert fr10.nrows == rows_10m, fr10.nrows
            record("ingest_airlines_csv_10m", rows_10m / dt, "rows/s",
                   dt, 1, 0.0, rows_ingest=rows_10m, mb=round(mb, 1),
                   mb_per_s=round(mb / dt, 2),
                   csv_gen_seconds=round(gen_dt, 1),
                   cells_per_s=round(rows_10m * fr10.ncols / dt, 1))
            del fr10
            gc.collect()

    if _want("gbm_higgs_10m"):
        import gc

        nt10 = int(os.environ.get("BENCH_GBM_10M_TREES", 5))
        t0 = time.perf_counter()
        fr10 = D.higgs_frame(rows_10m, seed=8)
        gen_dt = time.perf_counter() - t0
        F10 = fr10.ncols - 1
        padded10 = fr10.vec("f0").padded_len
        binned_mb = round(padded10 * F10 / 2 ** 20, 1)
        budget_b = float(os.environ.get("H2O_TPU_HIST_BYTES_BUDGET",
                                        2 ** 30))
        t0 = time.perf_counter()
        m10 = GBM(ntrees=nt10, max_depth=6, seed=1).train(
            y="y", training_frame=fr10)
        dt = time.perf_counter() - t0
        record("gbm_higgs_10m", rows_10m * nt10 / dt, "rows*trees/s",
               dt, 1, 0.0, rows_gbm=rows_10m, ntrees=nt10, max_depth=6,
               binned_matrix_mb=binned_mb,
               hist_budget_mb=round(budget_b / 2 ** 20, 1),
               ooc=os.environ.get("H2O_TPU_OOC", "auto"),
               frame_gen_seconds=round(gen_dt, 1),
               train_auc=round(float(
                   m10.scoring_history[-1].get("train_auc",
                                               float("nan"))), 5))
        del fr10, m10
        gc.collect()

    from h2o_kubernetes_tpu.runtime.telemetry import build_info

    out = {"suite": results, "captured_at":
           time.strftime("%Y-%m-%dT%H:%M:%S"),
           "build": build_info()}
    suffix = "" if not only else "_partial"
    path = os.path.join(
        REPO,
        f"BENCH_SUITE_{'TPU' if on_tpu else 'CPU'}_r14{suffix}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"bench_suite": "done", "configs": len(results),
                      "platform": platform}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:    # one diagnostic line, never a bare death
        import traceback

        traceback.print_exc()
        print(json.dumps({"bench_suite": "error", "error": repr(e)[:300]}))
        sys.exit(1)
