"""Traffic kind `train_jobs_multi_enum`: `train_jobs` over a table with
categorical columns and a response of K > 2 classes — `train_jobs_enum`'s
frame (the categorical columns as integer level codes with their
``domains``, the comparison handed the columns' level counts) under
`train_jobs_multi`'s count (``ntrees`` are rounds; `shape()` gives
``trees`` = rounds x K) and check (no result where the warm-up job's
`train` root does not say the cell's classes, features and class batch).

The same job, the same window, the same span names (`bench.window` /
`bench.job` / `bench.from_arrays` / `bench.train`) and the same keys in
`shape()` and in the window's result as `train_jobs`
(`bench/tests/test_kddcup99_cell.py` holds the kinds to the same keys).
The cell measures a K-class job whose class trees split on sets, so
the warm-up job's `train` root must also say how many of the columns
split by sets."""

from __future__ import annotations

import train_jobs_enum
import train_jobs_multi
from train_jobs_enum import table_module


class Traffic(train_jobs_multi.Traffic, train_jobs_enum.Traffic):

    def load(self) -> None:
        """`train_jobs_multi`'s checks and table, then the domains and
        level counts `train_jobs_enum`'s job and comparison hand on."""
        super().load()
        table = table_module(self.config["table"])
        self.domains, self.levels = table.domains(), table.LEVELS

    def setup(self) -> None:
        super().setup()
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER

        root = TRACER.by_root("train")[-1]["spans"][0]
        want = int((self.levels > 0).sum())
        if root.get("set_features") != want:
            raise SystemExit(
                f"train_jobs_multi_enum: the warm-up job's `train` root "
                f"says set_features={root.get('set_features')}, the cell "
                f"measures {want} — no result")
