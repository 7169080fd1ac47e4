"""The program's readings on many seeds, in one process that owns the chip.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 --out <dir>
    python3 bench/readings.py --workload <cell> --seeds 1 2 3 --out <dir> --offline

On the chip: for each seed the cell's table is made and one job run as
the window runs it (`traffic.job(0)`, the same model a run of that seed
trains), and the model is kept in the comparison's neutral form as
`<dir>/<cell>.<seed>.json` (a few hundred KB). With `--offline`, on any
host: each kept model is held against the plain reference over the
table made anew from the seed, and the numbers a run compares are
printed. Set-up is paid once for a dozen seeds, and a number that is
added later is read from the kept models without the chip. Not part
of a benchmark run; `PERF.md` holds what it read.
"""

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

import datasets
from registry import Registry


def to_json(model: dict) -> dict:
    out = dict(model)
    out["trees"] = [{k: np.asarray(v).tolist() for k, v in t.items()}
                    for t in model["trees"]]
    return out


def from_json(model: dict) -> dict:
    kinds = {"feat": np.int64, "thr": np.float32, "is_split": bool}
    out = dict(model)
    out["trees"] = [{k: np.asarray(v, dtype=kinds.get(k, np.float64))
                     for k, v in t.items()} for t in model["trees"]]
    return out


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
        path = os.path.join(args.out, f"{args.workload}.{seed}.json")
        traffic = mod.Traffic(cell, config, seed,
                              lambda name: contextlib.nullcontext(),
                              reg.comparison(config["comparison"]))
        t0 = time.perf_counter()
        if args.offline:
            with open(path) as f:
                traffic.models = [from_json(json.load(f))]
            traffic.X, traffic.y = datasets.TABLES[config["table"]](
                traffic.rows, seed)
            out = {"numbers": traffic.compare()}
        else:
            traffic.load()
            job = traffic.job(0)
            with open(path, "w") as f:
                json.dump(to_json(job["model"]), f)
            out = {"job_s": job["job_s"], "ok": job["ok"],
                   "memory": {k: v for k, v in
                              (devices[0].memory_stats() or {}).items()
                              if "bytes" in k}}
        print(json.dumps({"workload": args.workload, "seed": seed, **out,
                          "took_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
