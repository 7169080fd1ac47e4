"""Traffic kind `train_jobs_enum`: `train_jobs` over a table with
categorical columns.

The same job, the same window, the same span names (`bench.window` /
`bench.job` / `bench.from_arrays` / `bench.train`) and the same keys in
`shape()` and in the window's result as `train_jobs`: every reader
depends on them (`bench/tests/test_airline_cell.py` holds the two kinds
to the same keys). What differs is what `train_jobs` cannot be told
through its files: the table is looked up under `bench/tables/` by the
configuration's ``table``, its categorical columns go to
`Frame.from_arrays` as integer level codes with their ``domains``, and
the comparison is handed the columns' level counts beside the matrix.
"""

from __future__ import annotations

import importlib.util
import os
import time

import numpy as np

import train_jobs

HERE = os.path.dirname(os.path.abspath(__file__))


def table_module(name: str):
    """`bench/tables/<name>.py`, by file as the registry finds its own."""
    path = os.path.join(os.path.dirname(HERE), "tables", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_tables_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Traffic(train_jobs.Traffic):

    def load(self) -> None:
        """The estimator, checked first so that a program that cannot
        take the configuration's parameters fails before the table is
        made; then the table from the seed."""
        import h2o_kubernetes_tpu.models as models

        self.estimator = getattr(models, self.config["estimator"])
        self.estimator(ntrees=self.trees, **self.config["params"])
        table = table_module(self.config["table"])
        self.X, self.y = getattr(table, self.config["table"])(
            self.rows, self.seed)
        self.cols = table.as_columns(self.X, self.y)
        self.domains = table.domains()
        self.levels = table.LEVELS

    def job(self, index: int) -> dict:
        import jax

        import h2o_kubernetes_tpu as h2o

        t0 = time.perf_counter()
        with self.annotate("bench.job"):
            with self.annotate("bench.from_arrays"):
                fr = h2o.Frame.from_arrays(self.cols, domains=self.domains)
                jax.block_until_ready([fr.vec(c).data for c in fr.names])
            t1 = time.perf_counter()
            with self.annotate("bench.train"):
                m = self.estimator(
                    ntrees=self.trees, **self.config["params"],
                    seed=(self.seed + index + 1) % train_jobs.MAX_SEED,
                ).train(y="y", training_frame=fr)
                model = self.comparison.neutral_model(m)
            del fr, m
        t2 = time.perf_counter()
        return {"start": t0, "end": t2, "ingest_s": t1 - t0,
                "job_s": t2 - t0, "model": model,
                "ok": bool(len(model["trees"]) == self.trees
                           and np.isfinite(model["train_logloss"]))}

    def compare(self) -> dict[str, float]:
        model = self.models[self.seed % len(self.models)]
        Xr = np.ascontiguousarray(self.X.T)
        return self.comparison.compare(
            model, Xr, self.y, dict(self.config, levels=self.levels),
            self.cell, self.seed)
