"""Benchmark harness — GBM training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.

The reference publishes no numbers (BASELINE.json "published": {}), so
vs_baseline is the ratio against the first number this harness ever
recorded at the SAME shape (BENCH_BASELINE.json keys entries by
"<platform>:<rows>x<trees>").  A run with no matching baseline emits
``vs_baseline: null``.

North-star metric (BASELINE.json:2): GBM rows/sec/chip. We measure
steady-state boosting throughput (binning + per-tree grow + margin
update) on a synthetic airlines-like binary-classification table.

A speed number comes from a chip run or not at all: without a TPU this
script exits non-zero and prints no result (runtime/backend.require_tpu)
— there is no CPU fallback, no retry with a knob flipped, and no echo
of an older on-chip number. ROADMAP Queue 1 item 2 replaces it with the
cell benchmark.
"""

import json
import os
import sys
import time

import numpy as np

METRIC = "gbm_boosted_rows_per_sec_per_chip"
UNIT = "rows*trees/s/chip"
SCORE_METRIC = "gbm_score_rows_per_sec"


def measure_scoring(m, fr, fr1, Xn, rows: int,
                    reps_full: int = 3) -> dict:
    """THE serving-throughput harness (shared by `bench.py score` and
    bench_suite's gbm_score_rows_per_sec config — one protocol, two
    data shapes, no drift): legacy per-call predict() baselines
    (full-batch + batch-1, via models.gbm.legacy_scoring_path), then
    warm score_numpy at both shapes with the scorer-cache recompile
    check.  `fr1` is a 1-row frame (the "100k×1" per-call serving
    unit).  Returns the flat record; `compile_seconds` is the cold
    first score_numpy call."""
    from h2o_kubernetes_tpu.models.base import scorer_cache_stats
    from h2o_kubernetes_tpu.models.gbm import legacy_scoring_path

    def timed(fn, reps):
        fn()                       # warm (compile)
        t0 = time.perf_counter()
        for _ in range(reps):
            fn()
        return (time.perf_counter() - t0) / reps

    with legacy_scoring_path(m):
        dt_legacy = timed(lambda: m.predict(fr), reps_full)
        dt1_legacy = timed(lambda: m.predict(fr1), 10 * reps_full)
    m.predict(fr)                  # warm the new frame path
    t0 = time.perf_counter()
    m.score_numpy(Xn)              # cold serving call (compile)
    compile_s = time.perf_counter() - t0
    one = Xn[:1]
    m.score_numpy(one)
    dt_frame = timed(lambda: m.predict(fr), reps_full)
    s0 = scorer_cache_stats()
    dt_fast = timed(lambda: m.score_numpy(Xn), reps_full)
    dt1_fast = timed(lambda: m.score_numpy(one), 100 * reps_full)
    s1 = scorer_cache_stats()
    return {
        "value": round(rows / dt_fast, 1),
        "unit": "rows/s",
        "seconds": round(dt_fast, 3),
        "calls": reps_full,
        "compile_seconds": round(compile_s, 3),
        "legacy_predict_rows_per_s": round(rows / dt_legacy, 1),
        "speedup_vs_legacy_predict": round(dt_legacy / dt_fast, 2),
        "frame_predict_rows_per_s": round(rows / dt_frame, 1),
        "batch1_rows_per_s": round(1.0 / dt1_fast, 1),
        "batch1_legacy_rows_per_s": round(1.0 / dt1_legacy, 1),
        "speedup_batch1": round(dt1_legacy / dt1_fast, 2),
        "warm_cache_misses": s1["misses"] - s0["misses"],
        "rows": rows,
    }


def main_score() -> None:
    """`python bench.py score` — the serving fast-path number: warm
    score_numpy rows/s (flattened-tree scorer + jitted-predict cache)
    vs the per-call predict() Frame path, one JSON line.  The warm
    repeat must add 0 scorer-cache misses (recompile check)."""
    from h2o_kubernetes_tpu.runtime.backend import require_tpu

    require_tpu("bench.py score")
    import jax

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    h2o.init()
    rows = int(os.environ.get("BENCH_SCORE_ROWS", 100_000))
    rng = np.random.default_rng(0)
    F = 10
    X = {f"x{i}": rng.normal(size=rows).astype(np.float32)
         for i in range(F - 1)}
    X["c1"] = np.array(["a", "b", "c", "d"])[rng.integers(0, 4, rows)]
    X["y"] = np.where(X["x0"] - X["x1"] > 0, "late", "ontime")
    fr = h2o.Frame.from_arrays(X)
    m = GBM(ntrees=20, max_depth=5, learn_rate=0.2, seed=1).train(
        y="y", training_frame=fr)
    Xn = np.asarray(m._design_matrix(fr))[:rows]
    fr1 = h2o.Frame.from_arrays(
        {k: v[:1] for k, v in X.items() if k != "y"})
    out = measure_scoring(m, fr, fr1, Xn, rows)
    print(json.dumps({"metric": SCORE_METRIC,
                      "platform": jax.default_backend(), **out}))


def main() -> None:
    from h2o_kubernetes_tpu.runtime.backend import require_tpu

    require_tpu("bench.py")
    import jax

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    h2o.init()
    n_chips = len(jax.devices())
    rows = int(os.environ.get("BENCH_ROWS", 1_000_000))
    ntrees = int(os.environ.get("BENCH_TREES", 10))
    rng = np.random.default_rng(0)
    F = 10
    X = {f"x{i}": rng.normal(size=rows).astype(np.float32)
         for i in range(F - 2)}
    X["c1"] = np.array(["a", "b", "c", "d", "e", "f", "g", "h"])[
        rng.integers(0, 8, size=rows)]
    X["dep_delay"] = rng.exponential(10.0, size=rows).astype(np.float32)
    logit = (1.2 * X["x0"] - 0.8 * X["x1"] + 0.05 * X["dep_delay"]
             - 1.0 + rng.normal(scale=0.5, size=rows))
    X["y"] = np.where(logit > 0, "late", "ontime")
    fr = h2o.Frame.from_arrays(X)

    def run(nt):
        return GBM(ntrees=nt, max_depth=5, learn_rate=0.2, seed=1).train(
            y="y", training_frame=fr)

    # warm-up with the SAME ntrees: the fused boosting loop compiles a
    # scan whose length is the tree count, so a shorter warm-up would
    # leave the timed run paying a fresh XLA compile
    run(ntrees)
    t0 = time.perf_counter()
    run(ntrees)
    dt = time.perf_counter() - t0
    rows_per_sec_per_chip = rows * ntrees / dt / n_chips

    platform = jax.default_backend()
    shape_key = f"{platform}:{rows}x{ntrees}"
    base_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_BASELINE.json")
    store = {"metric": METRIC, "baselines": {}}
    if os.path.exists(base_path):
        with open(base_path) as f:
            store = json.load(f)
    entry = store["baselines"].get(shape_key)
    if entry is None:
        # first run at this shape: record it, no ratio yet
        store["baselines"][shape_key] = {"value": rows_per_sec_per_chip}
        with open(base_path, "w") as f:
            json.dump(store, f, indent=1)
    base = entry["value"] if entry else None

    print(json.dumps({
        "metric": METRIC,
        "value": round(rows_per_sec_per_chip, 1),
        "unit": UNIT,
        "vs_baseline": (round(rows_per_sec_per_chip / base, 3)
                        if base else None),
        "baseline_key": shape_key if base else None,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "chips": n_chips,
        "rows": rows,
        "trees": ntrees,
        "seconds": round(dt, 3),
    }))


if __name__ == "__main__":
    main_score() if "score" in sys.argv[1:] else main()
