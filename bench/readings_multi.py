"""`readings.py` for a cell whose comparison is `gbm_softmax`: the
program's readings on many seeds, in one process that owns the chip.

    python3 bench/readings_multi.py --workload xgb-covtype.train --seeds 1 2 3 --out <dir>
    python3 bench/readings_multi.py --workload xgb-covtype.train --seeds 1 2 3 --out <dir> --offline

A job a seed as the window runs it; each model is kept as
`<dir>/<cell>.<seed>.npz` (its rounds' class trees' heap arrays,
stacked [rounds, K, N]). With `--offline`, on any host, each kept model
is held against the plain reference over the table made anew from the
seed."""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np

from registry import Registry

SCALARS = ("learn_rate", "classes", "train_logloss")


def save(path: str, model: dict) -> None:
    out = {k: np.float64(model[k]) for k in SCALARS}
    out["init"] = np.asarray(model["init"], dtype=np.float64)
    for k in model["trees"][0][0]:
        out["t_" + k] = np.stack([np.stack([t[k] for t in trees])
                                  for trees in model["trees"]])
    np.savez_compressed(path, **out)


def load(path: str) -> dict:
    z = np.load(path)
    model = {k: float(z[k]) for k in SCALARS}
    model["classes"] = int(model["classes"])
    model["init"] = z["init"].tolist()
    arrays = {k[2:]: z[k] for k in z.files if k.startswith("t_")}
    rounds, K = arrays["feat"].shape[:2]
    model["trees"] = [[{k: a[r, c] for k, a in arrays.items()}
                       for c in range(K)] for r in range(rounds)]
    return model


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--offline", action="store_true")
    args = ap.parse_args(argv)
    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    config = reg.config(cell["config"])
    mod = reg.traffic(cell["kind"])
    os.makedirs(args.out, exist_ok=True)
    if not args.offline:
        import jax

        import h2o_kubernetes_tpu as h2o

        devices = jax.devices()
        if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
            raise SystemExit(f"readings: {args.workload} needs "
                             f"{cell['chips']} TPU chip(s)")
        h2o.init()
    for seed in args.seeds:
        path = os.path.join(args.out, f"{args.workload}.{seed}.npz")
        traffic = mod.Traffic(cell, config, seed,
                              lambda name: contextlib.nullcontext(),
                              reg.comparison(config["comparison"]))
        t0 = time.perf_counter()
        traffic.load()
        if args.offline:
            traffic.models = [load(path)]
            out = {"numbers": traffic.compare()}
        else:
            job = traffic.job(0)
            save(path, job["model"])
            out = {"job_s": job["job_s"], "ok": job["ok"],
                   "train_logloss": job["model"]["train_logloss"],
                   "memory": {k: v for k, v in
                              (devices[0].memory_stats() or {}).items()
                              if "bytes" in k}}
        print(json.dumps({"workload": args.workload, "seed": seed, **out,
                          "took_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
