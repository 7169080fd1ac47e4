"""A tiny copy of the benchmark for rehearsals off the chip: the data
files shrunk, the peaks table given the CPU's device kind, a four-chip
cell dropped in beside the one-chip cell (as a later PR would add it:
a file and an entry), nothing of the code changed. Used by the tests;
the harness has no such option."""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY = {"rows_per_chip": 20_000, "ntrees": 4}
TINY_PARAMS = {"max_depth": 3}
FOUR_CHIP = "gbm-higgs.train-4chip"


def tiny_root(dst: str, device_kind: str = "cpu") -> str:
    """Copy `BENCHMARK.json` and `bench/` (without its tests) to
    ``dst``, shrink every configuration, and add ``device_kind`` to the
    peaks with the v5e's numbers (a rehearsal reports no device
    metric)."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    cdir = os.path.join(dst, "bench", "configs")
    for name in os.listdir(cdir):
        path = os.path.join(cdir, name)
        with open(path) as f:
            cfg = json.load(f)
        cfg.update(TINY)
        cfg["params"].update(TINY_PARAMS)
        with open(path, "w") as f:
            json.dump(cfg, f)
    wdir = os.path.join(dst, "bench", "workloads")
    with open(os.path.join(wdir, "gbm-higgs.train.json")) as f:
        cell = json.load(f)
    cell["chips"] = 4
    with open(os.path.join(wdir, FOUR_CHIP + ".json"), "w") as f:
        json.dump(cell, f)
    with open(os.path.join(dst, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": FOUR_CHIP, "config": cell["config"],
         "traffic": "train-4chip", "chips": 4, "why": "rehearsal"})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    ppath = os.path.join(dst, "bench", "peaks.json")
    with open(ppath) as f:
        peaks = json.load(f)
    peaks[device_kind] = peaks["TPU v5 lite"]
    with open(ppath, "w") as f:
        json.dump(peaks, f)
    return dst
