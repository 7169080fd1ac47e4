"""Traffic kind `train_jobs_rank`: `train_jobs` over a learning-to-rank
table, a query's rows contiguous under a `qid` column.

The same job, the same window, the same span names (`bench.window` /
`bench.job` / `bench.from_arrays` / `bench.train`) and the same keys in
`shape()` and in the window's result as `train_jobs`: every reader
depends on them (`bench/tests/test_mslr_cell.py` holds the two kinds to
the same keys). What differs: the table is looked up under
`bench/tables/` by the configuration's ``table`` and made from (rows,
queries, seed), the queries in the published ratio to the rows; the job
trains with ``group_column="qid"`` and is `ok` on a finite
`train_ndcg@10`; the comparison is handed the `qid` column beside the
matrix."""

from __future__ import annotations

import time

import numpy as np

import train_jobs
from train_jobs_enum import table_module


class Traffic(train_jobs.Traffic):

    def __init__(self, cell, config, seed, annotate, comparison):
        super().__init__(cell, config, seed, annotate, comparison)
        pub = config["published"]
        self.queries = max(1, self.rows * int(pub["queries_per_chip"])
                           // int(pub["rows_per_chip"]))

    def load(self) -> None:
        """The estimator, checked first so that a program that cannot
        take the configuration's parameters, or trains a ranking
        objective outside its boost plan, fails before the table is
        made; then the table from the seed."""
        import h2o_kubernetes_tpu.models as models
        from h2o_kubernetes_tpu.models import gbm

        self.estimator = getattr(models, self.config["estimator"])
        self.estimator(ntrees=self.trees, **self.config["params"])
        if not hasattr(gbm.BoostPlan, "grouped"):
            # the cell measures a ranking job ON THE BOOST PLAN; a
            # program that trains rank:* in a loop of its own beside it
            # (one padded to the longest query: tens of GB of pair
            # slots a tree at this size) is not measured under its name
            raise SystemExit(
                "train_jobs_rank: this program has no grouped objective "
                "on its boost plan (no `BoostPlan.grouped`) — no result")
        table = table_module(self.config["table"])
        self.X, self.y, self.qid = getattr(table, self.config["table"])(
            self.rows, self.queries, self.seed)
        self.cols = table.as_columns(self.X, self.y, self.qid)

    def job(self, index: int) -> dict:
        import jax

        import h2o_kubernetes_tpu as h2o

        t0 = time.perf_counter()
        with self.annotate("bench.job"):
            with self.annotate("bench.from_arrays"):
                fr = h2o.Frame.from_arrays(self.cols)
                jax.block_until_ready([fr.vec(c).data for c in fr.names])
            t1 = time.perf_counter()
            with self.annotate("bench.train"):
                m = self.estimator(
                    ntrees=self.trees, **self.config["params"],
                    seed=(self.seed + index + 1) % train_jobs.MAX_SEED,
                ).train(y="y", training_frame=fr, group_column="qid")
                model = self.comparison.neutral_model(m)
            del fr, m
        t2 = time.perf_counter()
        return {"start": t0, "end": t2, "ingest_s": t1 - t0,
                "job_s": t2 - t0, "model": model,
                "ok": bool(len(model["trees"]) == self.trees
                           and np.isfinite(model["train_ndcg@10"]))}

    def compare(self) -> dict[str, float]:
        model = self.models[self.seed % len(self.models)]
        Xr = np.ascontiguousarray(self.X.T)
        return self.comparison.compare(
            model, Xr, self.y, dict(self.config, qid=self.qid),
            self.cell, self.seed)
