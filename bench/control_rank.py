"""The control and the planted faults of the ranking comparison, read at
a cell's own size — `control.py` for a configuration whose comparison is
`xgb_rank`.

    python3 bench/control_rank.py --workload xgb-mslr.train --seeds 1 2

For each seed the plain reference (`reference/lambdamart_plain.train`)
is put in the program's place once as it is (float64: the comparison's
own floor), once with every row's gradient and hessian rounded to
bfloat16 (the control: the nearest precision below the float32 the
configuration states) and once with each planted fault — `pointwise`
(squared error on the labels: no queries at all), `no_delta_ndcg`
(rank:pairwise's weights under rank:ndcg's name), `cross_query`
(queries merged two by two), `truncated_query` (a query's pairs cut at
its first 256 documents), `stale_rank` (the weights from the first
round's ranks), `unstable_ties` (ties broken by reverse row order) and
the four `control.py` has — and the configuration's comparison reads
the numbers a run compares. Host numpy only; not part of a benchmark
run."""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from reference import lambdamart_plain

VARIANTS = ("float64", "bfloat16") + lambdamart_plain.FAULTS


def read_one(task) -> dict:
    root, workload, seed, variant, trees, rows = task
    import contextlib

    import numpy as np

    import run
    from registry import Registry

    reg = Registry(root)
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    cell = dict(cell, check_trees=trees)
    if rows:
        cfg = dict(cfg, rows_per_chip=rows // int(cell["chips"]))
    t0 = time.perf_counter()
    traffic = reg.traffic(cell["kind"]).Traffic(
        cell, cfg, seed, lambda name: contextlib.nullcontext(),
        reg.comparison(cfg["comparison"]))
    traffic.load()
    Xr = np.ascontiguousarray(traffic.X.T)
    traffic.X = traffic.cols = None
    fault = variant if variant in lambdamart_plain.FAULTS else None
    model = lambdamart_plain.train(
        Xr, traffic.y, traffic.qid, cfg["params"], trees,
        precision="float64" if fault else variant, fault=fault)
    numbers = traffic.comparison.compare(
        model, Xr, traffic.y, dict(cfg, qid=traffic.qid), cell, seed)
    correct, _ = run.verdict(numbers, cell["limits"])
    return {"workload": workload, "seed": seed, "variant": variant,
            "trees": trees, "rows": traffic.rows, "correct": correct,
            "numbers": numbers, "took_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=VARIANTS,
                    choices=VARIANTS)
    ap.add_argument("--trees", type=int, default=3)
    ap.add_argument("--rows", type=int, default=0,
                    help="0: the cell's own")
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    tasks = [(root, args.workload, s, v, args.trees, args.rows)
             for s in args.seeds for v in args.variants]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(args.workers, len(tasks))) as pool:
        for out in pool.imap_unordered(read_one, tasks):
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
