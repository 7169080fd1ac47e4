"""Chip smoke: the main path once, on the TPU, through the entry points a
user calls — `h2o.init()` -> `Frame.from_arrays` -> `GBM(...).train()` ->
`ModelRegistry.publish`/`push` -> the REST server's
`POST /3/Predictions/models/{key}` and `.../contributions`.

    python chip_smoke.py              one chip  (what the driver runs)
    python chip_smoke.py --chips 4    the 4-device mesh against a 1-device
                                      mesh, and no other phase

One process owns the chip: the REST server runs in this process and the
client is a thread. Every phase is a function of sizes and a mesh
(tests/test_chip_smoke.py rehearses them on the CPU mesh); `main()` is
the only place that looks at the platform, and it does so before any
other work: without a TPU the script exits non-zero and prints no
result. Nothing is caught and carried on from — a failed phase is a
traceback and a non-zero exit.

Everything above the last line is smoke output, not a metric. The last
stdout line is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""

from __future__ import annotations

import argparse
import json
import tempfile
import threading
import time
import urllib.request

import numpy as np

N_FEATURES = 28            # HIGGS: 21 low-level + 7 derived features
SCORE_BATCHES = (1, 128, 8192)
CONTRIB_BATCHES = (128, 1024)
AUC_FLOOR = 0.78           # at the default size; 0.5 is chance


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok, msg) -> None:
    """A failed check fails the run (an `assert` would vanish under -O)."""
    if not ok:
        raise AssertionError(msg)


def higgs_like(rows: int, seed: int) -> dict[str, np.ndarray]:
    """A HIGGS-shaped table from ``seed``: 28 float32 features (the last
    7 derived from the first 21, as the real set's invariant masses
    are) and a binary response that depends on them non-linearly."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, N_FEATURES), dtype=np.float32)
    for j in range(7):
        X[:, 21 + j] = np.sqrt(X[:, 3 * j] ** 2 + X[:, 3 * j + 1] ** 2
                               + 0.5 * X[:, 3 * j + 2] ** 2)
    logit = (X[:, 0] - 0.8 * X[:, 1] + 0.6 * X[:, 2] * X[:, 3]
             + 0.9 * (np.abs(X[:, 4]) - 0.8) + 0.7 * (X[:, 21] - 1.2)
             - 0.5 * (X[:, 24] - 1.2) * X[:, 5])
    y = logit + 0.7 * rng.logistic(size=rows).astype(np.float32) > 0
    cols = {f"f{j}": X[:, j] for j in range(N_FEATURES)}
    cols["y"] = np.where(y, "s", "b")          # signal / background
    return cols


def feature_matrix(cols: dict, n: int) -> np.ndarray:
    """The first ``n`` rows as the [n, 28] float32 matrix a client
    sends (training value space: all numeric, so the raw floats)."""
    return np.stack([cols[f"f{j}"][:n] for j in range(N_FEATURES)],
                    axis=1)


def phase_train(cols: dict, ntrees: int, max_depth: int, mesh,
                auc_floor: float = AUC_FLOOR):
    """Frame.from_arrays + GBM.train() on ``mesh``; returns
    (frame, model, train AUC)."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    with h2o.use_mesh(mesh):
        fr = h2o.Frame.from_arrays(cols)
        m = GBM(ntrees=ntrees, max_depth=max_depth, seed=1).train(
            y="y", training_frame=fr)
    auc = float(m.scoring_history[-1]["train_auc"])
    check(m.ntrees == ntrees, (m.ntrees, ntrees))
    check(np.isfinite(auc) and auc > auc_floor,
          f"train AUC {auc} does not beat the floor {auc_floor}")
    return fr, m, auc


def phase_kernel_checks(m, X: np.ndarray, seed: int, hist_rows: int,
                        n_nodes: int = 32) -> dict:
    """The kernels against their plain references, outside any timing:
    one `build_histogram` pallas vs segment at the smoke's width (28
    features, the model's bin count, a depth-5 level's 32 nodes), and
    the flat serving scorer `_margins` vs the binned heap re-descent
    `_margins_binned` on the rows of ``X`` (bitwise)."""
    import jax
    import jax.numpy as jnp

    from h2o_kubernetes_tpu.ops.histogram import build_histogram

    rng = np.random.default_rng(seed + 1)
    B = m.params.nbins
    binned = jnp.asarray(rng.integers(
        0, B, size=(hist_rows, N_FEATURES)).astype(np.uint8))
    rel = jnp.asarray(np.where(
        rng.uniform(size=hist_rows) < 0.9,
        rng.integers(0, n_nodes, size=hist_rows), -1).astype(np.int32))
    g = jnp.asarray(rng.normal(size=hist_rows).astype(np.float32))
    h = jnp.asarray(rng.uniform(0.01, 1, hist_rows).astype(np.float32))
    w = jnp.asarray((rng.uniform(size=hist_rows) < 0.95).astype(
        np.float32))
    hist = jax.jit(build_histogram, static_argnums=(5, 6, 7))
    lowered = hist.lower(binned, rel, g, h, w, n_nodes, B, "pallas")
    got = hist(binned, rel, g, h, w, n_nodes, B, "pallas")
    want = hist(binned, rel, g, h, w, n_nodes, B, "segment")
    hist_err = float(jnp.max(jnp.abs(got - want))
                     / (jnp.max(jnp.abs(want)) + 1e-30))
    check(hist_err < 1e-5, f"pallas vs segment histogram: {hist_err}")

    Xd = jnp.asarray(X)
    flat, heap = np.asarray(m._margins(Xd)), \
        np.asarray(m._margins_binned(Xd))
    check(np.isfinite(flat).all(), "non-finite margins")
    check(np.array_equal(flat, heap),
          f"_margins vs _margins_binned: max |d| "
          f"{np.abs(flat - heap).max()} on {len(X)} rows")
    return {"hist_rel_err": hist_err,
            # Mosaic compiled the kernel (interpret mode has no call)
            "hist_custom_call": "tpu_custom_call" in
            lowered.compile().as_text(),
            "margin_rows": int(len(X)), "margin_bitwise": True}


def _post(url: str, body: dict, timeout: float = 600.0) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def in_thread(fn, name: str):
    """Run ``fn`` on its own thread and return what it returns; what it
    raises is raised here."""
    box: dict = {}

    def target() -> None:
        try:
            box["out"] = fn()
        except Exception as e:      # re-raised on the calling thread
            box["err"] = e

    t = threading.Thread(target=target, name=name)
    t.start()
    t.join()
    if "err" in box:
        raise box["err"]
    return box["out"]


def phase_serve(m, X: np.ndarray, mesh,
                score_batches=SCORE_BATCHES,
                contrib_batches=CONTRIB_BATCHES) -> dict:
    """Publish ``m`` to a ModelRegistry, start the REST server in this
    process (the pod's own code path, operator/pod.py, minus the fork),
    push the artifact, and answer real HTTP requests from a client
    thread. Predictions must be bitwise the training-side
    `score_numpy`; contribution rows must sum to the served logit;
    `/3/Stats` must show no failed dispatch and a closed breaker."""
    import jax.numpy as jnp

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu import rest
    from h2o_kubernetes_tpu.operator.registry import ModelRegistry
    from h2o_kubernetes_tpu.runtime import lifecycle

    key = "higgs_gbm"
    cols = [f"f{j}" for j in range(N_FEATURES)]
    # the breaker's counters are the process's: count from here
    br0 = lifecycle.BREAKER.status()

    def client(reg, version, url) -> dict:
        loaded = reg.push(url, key, version, key,
                          warm_buckets=[max(score_batches)])
        check(loaded["contributions"] is True, loaded)
        for n in score_batches:
            r = _post(f"{url}/3/Predictions/models/{key}",
                      {"rows": X[:n].tolist(), "columns": cols})
            want = m.score_numpy(X[:n])
            got = np.stack([np.asarray(r[f"p{d}"], np.float32)
                            for d in m.response_domain], axis=1)
            check(got.shape == (n, 2) and np.isfinite(got).all(),
                  got.shape)
            check(np.array_equal(got, want),
                  f"served predictions differ from score_numpy at batch "
                  f"{n}: max |d| {np.abs(got - want).max()}")
        margins = np.asarray(m._margins(
            jnp.asarray(X[:max(contrib_batches)])))
        add_err = 0.0
        for n in contrib_batches:
            r = _post(f"{url}/3/Predictions/models/{key}/contributions",
                      {"rows": X[:n].tolist(), "columns": cols})
            phi = np.asarray(r["contributions"], np.float32)
            check(phi.shape == (n, N_FEATURES + 1)
                  and np.isfinite(phi).all(), phi.shape)
            check(r["columns"][-1] == "BiasTerm", r["columns"])
            # rows sum to the served logit: the margin itself, and
            # log(p1/p0) of what the score route served
            err = float(np.abs(phi.sum(axis=1) - margins[:n]).max())
            check(err < 1e-4, f"contributions do not add up to the "
                  f"margin at batch {n}: {err}")
            p = m.score_numpy(X[:n]).astype(np.float64)
            mid = (p[:, 1] > 1e-3) & (p[:, 1] < 1 - 1e-3)
            lerr = float(np.abs(phi.sum(axis=1)
                                - np.log(p[:, 1] / p[:, 0]))[mid].max())
            check(lerr < 1e-3, f"contributions vs served log-odds at "
                  f"batch {n}: {lerr}")
            add_err = max(add_err, err)
        with urllib.request.urlopen(f"{url}/3/Stats", timeout=60) as s:
            stats = json.loads(s.read())
        return {"warmed_buckets": loaded["warmed_buckets"],
                "additivity_err": add_err, "stats": stats}

    with h2o.use_mesh(mesh), tempfile.TemporaryDirectory() as root:
        reg = ModelRegistry(root)
        version = reg.publish(m, key)
        rest.install_pool_replica_gate()
        srv = rest.start_server(0, background=True)
        try:
            report = in_thread(
                lambda: client(
                    reg, version,
                    f"http://127.0.0.1:{srv.server_address[1]}"),
                "chip-smoke-client")
            # how each virtual-tree group of the SERVED model ran
            plans = {n: rest.MODELS[key].contrib_plan(n)
                     for n in contrib_batches}
        finally:
            # leave the process as it was found (the test runs this twice)
            rest.MODELS.pop(key, None)
            rest.REGISTRY_MODELS.pop(key, None)
            rest.MODEL_STATS.pop(key, None)
            rest.READINESS_GATES.pop("model-registry", None)
            srv.shutdown()
            srv.server_close()
    st = report.pop("stats")
    br = st["breaker"]
    check(st["ready"] is True, st["reasons"])
    check(st["healthy"] is True and br["state"] == "closed"
          and all(br[k] == br0[k] for k in
                  ("failures", "trips", "short_circuited")),
          (br0, st["breaker"]))
    ms = st["models"][key]
    check(ms["shed"] == 0 and ms["deadline_504"] == 0
          and ms["breaker_rejects"] == 0, ms)
    check(ms["requests"] == len(score_batches)
          and ms["contrib_requests"] == len(contrib_batches), ms)
    check(st["registry"][key]["warm_cache_misses"] == 0, st["registry"])
    report["breaker"] = br["state"]
    report["dispatch_failures"] = br["failures"] - br0["failures"]
    report["requests"] = ms["requests"] + ms["contrib_requests"]
    report["contrib_plan"] = {
        n: {i: p.count(i) for i in sorted(set(p))}
        for n, p in plans.items()}
    return report


def phase_mesh_compare(cols: dict, ntrees: int, max_depth: int,
                       devices, auc_floor: float = AUC_FLOOR) -> dict:
    """The same frame and GBM on the mesh over ``devices`` and on a
    1-device mesh: the frame's shards sit on distinct devices, nothing
    the step reads is replicated or parked on one device, and the two
    models agree."""
    import jax

    import h2o_kubernetes_tpu as h2o

    n = len(devices)
    out: dict = {}
    models = {}
    for tag, devs in (("mesh", list(devices)), ("one", list(devices)[:1])):
        mesh = h2o.make_mesh(devices=devs)
        t0 = time.perf_counter()
        fr, m, auc = phase_train(cols, ntrees, max_depth, mesh, auc_floor)
        out[f"{tag}_wall_s"] = time.perf_counter() - t0
        out[f"{tag}_auc"] = auc
        models[tag] = m
        if tag == "mesh":
            arrays = [fr.vec(c).data for c in fr.names] + [
                a for a in jax.tree.leaves(
                    fr.__dict__.get("_binned_cache", {}))
                if isinstance(a, jax.Array)]
            check(len(arrays) > len(fr.names), "no binned matrix cached")
            for a in arrays:
                on = {s.device for s in a.addressable_shards}
                check(on == set(devs), (a.shape, on))
                check(not a.sharding.is_fully_replicated, a.shape)
                check(a.addressable_shards[0].data.shape[0] * n
                      == a.shape[0], (a.shape, n))
            out["sharded_arrays"] = len(arrays)
        del fr
    # the two runs sum the same histograms in different orders (four
    # partial sums psum-ed vs one), so a near-tie deep in a tree may
    # pick the neighbouring bin: the SHAPE of the first trees and
    # their top three levels' (feature, bin) must be identical, and
    # below that at most 5% of the split nodes may differ
    ta, tb = models["mesh"].trees, models["one"].trees
    first = min(3, ntrees)
    isp = np.asarray(ta.is_split)[:first]
    check(np.array_equal(isp, np.asarray(tb.is_split)[:first]),
          "the first trees' shapes differ between the two meshes")
    same = np.ones_like(isp)
    for fld in ("split_feat", "split_bin"):
        same &= np.asarray(getattr(ta, fld))[:first] == \
            np.asarray(getattr(tb, fld))[:first]
    differ = isp & ~same
    check(not differ[:, :7].any(),
          "the first trees differ in their top three levels")
    check(differ.sum() <= 0.05 * isp.sum(), (differ.sum(), isp.sum()))
    check(abs(out["mesh_auc"] - out["one_auc"]) < 1e-3, out)
    out["first_trees"] = first
    out["split_nodes"] = int(isp.sum())
    out["near_tie_nodes"] = int(differ.sum())
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=2_000_000)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform "
            f"'{dev.platform}' ({len(jax.devices())} device(s)) — no "
            "result")
    if len(jax.devices()) != args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} but JAX sees "
            f"{len(jax.devices())} device(s)")
    from importlib.metadata import version

    import jaxlib

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.ops import histogram, shap_kernel
    from h2o_kubernetes_tpu.runtime.backend import (compile_watch_snapshot,
                                                    start_compile_watch)

    h2o.init()
    start_compile_watch()
    say(f"jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {version('libtpu')}")
    say(f"device_kind={dev.device_kind!r} count={len(jax.devices())} "
        f"compile_cache={jax.config.jax_compilation_cache_dir}")
    check(histogram.resolve_impl("auto") == "pallas"
          and not histogram._interpret(),
          "the histogram kernel would not run as compiled Pallas here")
    ntrees, max_depth = 50, 6
    walls: dict[str, float] = {}

    def timed(name, fn, *a, **kw):
        t0 = time.perf_counter()
        out = fn(*a, **kw)
        walls[name] = round(time.perf_counter() - t0, 2)
        return out

    cols = timed("data", higgs_like, args.rows, args.seed)
    if args.chips == 4:
        rep = timed("mesh_compare", phase_mesh_compare, cols, ntrees,
                    max_depth, jax.devices())
        say(f"4-device mesh: wall {rep['mesh_wall_s']:.1f}s auc "
            f"{rep['mesh_auc']:.5f} | 1-device mesh: wall "
            f"{rep['one_wall_s']:.1f}s auc {rep['one_auc']:.5f} "
            "(smoke output, compile included)")
        say(f"{rep['sharded_arrays']} frame/binned arrays on 4 distinct "
            f"devices, none replicated; first {rep['first_trees']} "
            f"trees same shape, {rep['near_tie_nodes']} of "
            f"{rep['split_nodes']} split nodes picked a neighbouring "
            "near-tie split")
    else:
        mesh = h2o.global_mesh()
        fr, m, auc = timed("train", phase_train, cols, ntrees, max_depth,
                           mesh)
        say(f"trained GBM {args.rows} x {N_FEATURES}, {ntrees} trees "
            f"depth {max_depth}: train AUC {auc:.5f} (floor {AUC_FLOOR})")
        del fr
        X = feature_matrix(cols, 100_000)
        kc = timed("kernel_checks", phase_kernel_checks, m, X, args.seed,
                   hist_rows=min(args.rows, 1_000_000))
        check(kc["hist_custom_call"],
              "the histogram kernel did not compile through Mosaic")
        say(f"histogram pallas vs segment rel err {kc['hist_rel_err']:.2e} "
            f"(Mosaic custom call: {kc['hist_custom_call']}); _margins "
            f"vs _margins_binned bitwise on {kc['margin_rows']} rows")
        sv = timed("serve", phase_serve, m, X, mesh)
        say(f"served {sv['requests']} HTTP requests (score batches "
            f"{SCORE_BATCHES} bitwise score_numpy, contributions "
            f"{CONTRIB_BATCHES} additive to {sv['additivity_err']:.2e}); "
            f"breaker {sv['breaker']}, dispatch failures "
            f"{sv['dispatch_failures']}, warmed {sv['warmed_buckets']}")
        say(f"TreeSHAP groups per impl {sv['contrib_plan']} (resolved "
            f"impl {shap_kernel.resolve_impl()})")
        if shap_kernel.resolve_impl() == "pallas":
            check(all(p.get("kernel", 0) > 0
                      for p in sv["contrib_plan"].values()),
                  "the SHAP kernel is the resolved impl but no group "
                  "took it")
    cw = compile_watch_snapshot()
    say(f"phase wall seconds {walls} (smoke output, not a metric)")
    say(f"compiles {cw['compiles']} ({cw['compile_s']:.1f}s), persistent "
        f"cache hits {cw['pcache_hits']} misses {cw['pcache_misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
