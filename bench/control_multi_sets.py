"""The control and the planted faults of the K-class comparison with set
splits, read at a cell's own size — `control_multi.py` for a
configuration whose comparison is `gbm_softmax_sets`.

    python3 bench/control_multi_sets.py --workload gbm-kddcup99.train \
        --seeds 1 2 --variants float64 bfloat16

For each seed the plain reference (`reference/gbm_softmax_sets_plain.
train`) is put in the program's place once as it is (float64: the
comparison's own floor), once with every row's gradient and hessian of
every class rounded to bfloat16 (the control: the nearest precision
below the float32 the configuration states) and once with each planted
fault of `gbm_softmax_sets_plain.FAULTS` — `ordinal_codes` (the
categorical columns split on their codes), `wrong_side` (one level of a
set handed out on the side its rows did not take), `sequential_softmax`
(class k's gradients taken after class k-1's tree of the same round
moved the margin), `stale_state` (no tree added to the margin),
`half_batch` (the sums over half the rows, doubled), `second_best`
(every node's best feature passed over) and `float32_split` (every
level's sums and split gains in float32, so that a node splits on the
rounding of G^2 / H) — and the configuration's
comparison reads the numbers a run compares. Host numpy only; not part
of a benchmark run. A variant at the cell's 4,898,431 rows takes about
33 minutes and 5 GB on an 8-core host."""

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

from reference import gbm_softmax_sets_plain

VARIANTS = ("float64", "bfloat16") + gbm_softmax_sets_plain.FAULTS


def read_one(task) -> dict:
    root, workload, seed, variant, rows = task
    import numpy as np

    import run
    from registry import Registry

    reg = Registry(root)
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    if rows:
        cfg = dict(cfg, rows_per_chip=rows // int(cell["chips"]))
    t0 = time.perf_counter()
    table = reg.traffic(cell["kind"]).table_module(cfg["table"])
    X, y = getattr(table, cfg["table"])(
        int(cfg["rows_per_chip"]) * int(cell["chips"]), seed)
    Xr = np.ascontiguousarray(X.T)
    del X
    fault = variant if variant in gbm_softmax_sets_plain.FAULTS else None
    model = gbm_softmax_sets_plain.train(
        Xr, y, table.LEVELS, cfg["params"], int(cfg["ntrees"]),
        int(cfg["classes"]), precision="float64" if fault else variant,
        fault=fault)
    numbers = reg.comparison(cfg["comparison"]).compare(
        model, Xr, y, dict(cfg, levels=table.LEVELS), cell, seed)
    correct, _ = run.verdict(numbers, cell["limits"])
    return {"workload": workload, "seed": seed, "variant": variant,
            "rows": len(y), "correct": correct, "numbers": numbers,
            "took_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=VARIANTS,
                    choices=VARIANTS)
    ap.add_argument("--rows", type=int, default=0,
                    help="0: the cell's own")
    ap.add_argument("--workers", type=int, default=2)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    tasks = [(root, args.workload, s, v, args.rows)
             for s in args.seeds for v in args.variants]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(args.workers, len(tasks))) as pool:
        for out in pool.imap_unordered(read_one, tasks):
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
