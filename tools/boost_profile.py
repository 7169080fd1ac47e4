"""Per-phase + per-op GBM profile on the chip.

The bench number (bench.py) times the whole ``train()``; this tool
breaks it down so kernel work is attacked where the time actually is:

1. wall-clock per phase (parse→device, fit_bins, apply_bins, init,
   fused boost dispatch, model finalize), each block_until_ready'd;
2. an XLA op-level profile of the boost dispatch alone via
   ``jax.profiler.trace``, aggregated from the perfetto trace into
   top-op self-times (no tensorboard needed — the trace JSON is parsed
   directly).

Writes ``PROFILE_TPU_r05.json`` at the repo root and prints one JSON
summary line. Exits non-zero without a TPU.
"""

import glob
import gzip
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _phase(name, fn, out):
    t0 = time.perf_counter()
    r = fn()
    import jax

    jax.block_until_ready(r) if r is not None else None
    dt = time.perf_counter() - t0
    out[name] = round(dt, 4)
    return r


def _parse_trace(log_dir: str, top: int = 30):
    """Aggregate device-track op self-times from the perfetto trace.

    The device pid carries several thread tracks — "XLA Ops" (leaf op
    executions) but also "XLA Modules" / "Steps" spans that COVER the
    ops; summing every complete event under the pid would double-count
    each op inside its module span. Only op-level tracks are summed:
    the "XLA Ops" threads when present, else the pid's threads minus
    the known enclosing-span tracks."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.trace.json.gz"),
                      recursive=True)
    if not paths:
        return {"error": "no trace file"}
    with gzip.open(sorted(paths)[-1], "rt") as f:
        trace = json.load(f)
    ev = trace.get("traceEvents", [])
    pid_names, tid_names = {}, {}
    for e in ev:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pid_names[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            tid_names[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")
    device_pids = {p for p, n in pid_names.items()
                   if "TPU" in n or "device" in n.lower()}
    op_tracks = {k for k, n in tid_names.items()
                 if (not device_pids or k[0] in device_pids)
                 and "XLA Ops" in n}
    if not op_tracks:
        span = ("XLA Modules", "Steps", "Framework")
        op_tracks = {k for k, n in tid_names.items()
                     if (not device_pids or k[0] in device_pids)
                     and not any(s in n for s in span)}
    agg: dict[str, float] = {}
    total = 0.0
    for e in ev:
        if e.get("ph") != "X":
            continue
        if op_tracks and (e.get("pid"), e.get("tid")) not in op_tracks:
            continue
        if not op_tracks and device_pids \
                and e.get("pid") not in device_pids:
            continue
        name = e.get("name", "?")
        dur = float(e.get("dur", 0.0)) / 1e6       # us -> s
        agg[name] = agg.get(name, 0.0) + dur
        total += dur
    ops = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return {"total_device_s": round(total, 4),
            "ops": [{"name": k, "s": round(v, 4)} for k, v in ops]}


def main() -> int:
    from h2o_kubernetes_tpu.runtime.backend import (
        enable_persistent_compile_cache, require_tpu)

    enable_persistent_compile_cache()
    require_tpu("boost_profile")
    import jax
    import numpy as np

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models.gbm import (GBM, _init_margin)
    from h2o_kubernetes_tpu.models.tree.binning import (apply_bins_jit,
                                                        fit_bins)
    from h2o_kubernetes_tpu.models.tree.core import (BoostParams,
                                                     TreeParams,
                                                     boost_trees)
    from h2o_kubernetes_tpu.models.base import resolve_xy

    platform = jax.default_backend()
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

    phases: dict[str, float] = {}
    import jax.numpy as jnp

    fr = _phase("frame_build", lambda: h2o.Frame.from_arrays(X), phases)
    data = resolve_xy(fr, "y", None, None, None, "auto", None)
    jax.block_until_ready(data.X)
    spec = _phase("fit_bins", lambda: fit_bins(fr, data.feature_names,
                                               n_bins=256), phases)
    edges = jnp.asarray(spec.edges_matrix())
    enum_mask = jnp.asarray(np.array(spec.is_enum))
    binned = _phase("apply_bins", lambda: apply_bins_jit(
        data.X, edges, enum_mask, spec.na_bin), phases)
    off = jnp.zeros_like(data.y)
    init, margin = _phase("init_margin", lambda: _init_margin(
        data.y, data.w, off, "bernoulli", 1), phases)
    tp = TreeParams(max_depth=5, n_bins=256)
    bp = BoostParams(distribution="bernoulli", learn_rate=0.2)
    key = jax.random.key(1)

    # compile (untimed), then timed steady-state dispatch
    _phase("boost_compile+run", lambda: boost_trees(
        binned, data.y, data.w, margin, key, ntrees, tp, bp)[0], phases)
    _phase("boost_steady", lambda: boost_trees(
        binned, data.y, data.w, margin, key, ntrees, tp, bp)[0], phases)

    # op-level profile of ONE steady-state boost dispatch
    log_dir = os.path.join(REPO, "tools", "_profile_run")
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        m2, trees = boost_trees(binned, data.y, data.w, margin, key,
                                ntrees, tp, bp)
        jax.block_until_ready(m2)
    op_profile = _parse_trace(log_dir)

    # end-to-end train() for reference (same as bench.py's timed unit)
    def full():
        return GBM(ntrees=ntrees, max_depth=5, learn_rate=0.2,
                   seed=1).train(y="y", training_frame=fr)

    full()                                  # warm
    _phase("full_train_steady", full, phases)

    out = {"platform": platform, "rows": rows, "trees": ntrees,
           "phases": phases, "op_profile": op_profile,
           "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S")}
    path = os.path.join(REPO, "PROFILE_TPU_r05.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"profile": "ok", "platform": platform,
                      "phases": phases,
                      "device_total_s":
                      op_profile.get("total_device_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
