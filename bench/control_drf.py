"""The forest cell's control and planted faults, read at the cell's own
size.

    python3 bench/control_drf.py --workload <cell> --seeds 1 2 3 [--trees 3]

For each seed the plain reference forest is put in the program's place
(`reference/drf_plain.train`, which hands out its own bags and
candidates) once as it is (float64: the comparison's own floor), once
with every histogram sum rounded to bfloat16 (the control: the nearest
precision below the float32 sums the configuration states — integers
past 256 are lost) and once with each planted fault, and the
configuration's comparison reads the same numbers a run compares. Host
numpy only: it needs no chip and touches none. Not part of a benchmark
run; `PERF.md` holds what it read and the limits set from it.

The faults: `unbagged` (every tree sees every row), `shared_bag` (one
bag for every tree), `all_features` (`mtries` ignored), `second_best`
(every node takes its second-best candidate's best cut and records it
truly), `half_batch` (half the bag, sums doubled), `stale_bag` (the bag
handed out is not the bag the tree was grown on), and two of the
reported metric alone, over sound trees: `half_forest_metric` (logloss
and AUC of half the trees) and `bag_metric` (of the whole forest, over
the first tree's bag instead of the table).
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

from reference import drf_plain

VARIANTS = ("float64", "bfloat16") + drf_plain.FAULTS


def read_one(task) -> dict:
    root, workload, seed, variant, trees = task
    import numpy as np

    import datasets
    import run
    from registry import Registry

    reg = Registry(root)
    cell = reg.cell(workload)
    cfg = reg.config(cell["config"])
    cell = dict(cell, check_trees=trees)
    rows = int(cfg["rows_per_chip"]) * int(cell["chips"])
    X, y = datasets.TABLES[cfg["table"]](rows, seed)
    Xr = np.ascontiguousarray(X.T)
    del X
    fault = variant if variant in drf_plain.FAULTS else None
    model = drf_plain.train(
        Xr, y, cfg["params"], trees, seed,
        precision="float64" if fault else variant, fault=fault)
    numbers = reg.comparison(cfg["comparison"]).compare(
        model, Xr, y, cfg, cell, seed)
    correct, _ = run.verdict(numbers, cell["limits"])
    return {"workload": workload, "seed": seed, "variant": variant,
            "trees": trees, "correct": correct, "numbers": numbers}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variants", nargs="+", default=VARIANTS,
                    choices=VARIANTS)
    ap.add_argument("--trees", type=int, default=3)
    ap.add_argument("--workers", type=int, default=4)
    args = ap.parse_args(argv)
    root = os.path.dirname(HERE)
    tasks = [(root, args.workload, s, v, args.trees)
             for s in args.seeds for v in args.variants]
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(args.workers, len(tasks))) as pool:
        for out in pool.imap_unordered(read_one, tasks):
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
