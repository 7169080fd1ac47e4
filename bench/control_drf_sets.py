"""The control and the planted faults of the set-split forest's
comparison, read at a cell's own size — `control_drf.py` for a
configuration whose comparison is `drf_sets`.

    python3 bench/control_drf_sets.py --workload drf-airline.train --seeds 1 2

For each seed the plain reference forest (`reference/drf_sets_plain.
train`, which hands out its own bags and candidates) is put in the
program's place once as it is (float64: the comparison's own floor),
once with every histogram sum rounded to bfloat16 (the control: the
nearest precision below the float32 sums the configuration states —
integers past 256 are lost) and once with each planted fault, and the
configuration's comparison reads the numbers a run compares. Host numpy
only: it needs no chip and touches none. Not part of a benchmark run;
`PERF.md` holds what it read and the limits set from it.

The faults: `ordinal_codes` (prefixes in code order, recorded truly:
what a forest without sets would do to an enum), `range_grouped` (300
levels folded into 254 ranges and handed out as levels), `wrong_side`
(one level of one set on the other side than the rows took),
`stale_bag` (the bag handed out is not the bag the tree was grown on),
`unbagged` (every tree sees every row), `all_features` (`mtries`
ignored), `second_best` (every node takes its second-best candidate's
best split and records it truly), `half_batch` (half the bag, sums
doubled) and `bag_metric` (the whole forest's metric over the first
tree's bag instead of the table).
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

from reference import drf_sets_plain

VARIANTS = ("float64", "bfloat16") + drf_sets_plain.FAULTS


def read_one(task) -> dict:
    root, workload, seed, variant, trees, rows = task
    import numpy as np

    import run
    from registry import Registry

    reg = Registry(root)
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    cell = dict(cell, check_trees=trees)
    table = reg.traffic(cell["kind"]).table_module(cfg["table"])
    rows = rows or int(cfg["rows_per_chip"]) * int(cell["chips"])
    X, y = getattr(table, cfg["table"])(rows, seed)
    Xr = np.ascontiguousarray(X.T)
    del X
    fault = variant if variant in drf_sets_plain.FAULTS else None
    model = drf_sets_plain.train(
        Xr, y, table.LEVELS, cfg["params"], trees, seed,
        precision="float64" if fault else variant, fault=fault)
    numbers = reg.comparison(cfg["comparison"]).compare(
        model, Xr, y, dict(cfg, levels=table.LEVELS), cell, seed)
    correct, _ = run.verdict(numbers, cell["limits"])
    return {"workload": workload, "seed": seed, "variant": variant,
            "trees": trees, "rows": rows, "correct": correct,
            "numbers": numbers}


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
