"""Traffic kind `train_jobs_multi`: `train_jobs` over a table with a
response of K > 2 classes, K class trees a boosting round.

The same job, the same window, the same span names (`bench.window` /
`bench.job` / `bench.from_arrays` / `bench.train`) and the same keys in
`shape()` and in the window's result as `train_jobs`: every reader
depends on them (`bench/tests/test_covtype_cell.py` holds the two kinds
to the same keys). What differs: the table is looked up under
`bench/tables/` by the configuration's ``table``; the configuration's
``ntrees`` are ROUNDS, and `shape()` gives ``trees`` = rounds x K —
what H2O-3 calls the internal trees, and what `train_rowtrees_per_s`
counts here; and the cell measures the K class trees of a round grown
through ONE histogram call a level on the boost plan, so it gives no
result where the warm-up job's `train` root does not say so."""

from __future__ import annotations

import train_jobs
from train_jobs_enum import table_module


class Traffic(train_jobs.Traffic):

    def __init__(self, cell, config, seed, annotate, comparison):
        super().__init__(cell, config, seed, annotate, comparison)
        self.classes = int(config["classes"])

    def shape(self) -> dict:
        sh = super().shape()
        return dict(sh, trees=sh["trees"] * self.classes)

    def load(self) -> None:
        """The estimator, checked first so that a program that cannot
        take the configuration's parameters, or does not say how it
        grows a round's class trees, fails before the table is made;
        then the table from the seed."""
        import h2o_kubernetes_tpu.models as models
        from h2o_kubernetes_tpu.models import gbm

        self.estimator = getattr(models, self.config["estimator"])
        self.estimator(ntrees=self.trees, **self.config["params"])
        if "class_batch" not in getattr(gbm.BoostPlan, "_fields", ()):
            raise SystemExit(
                "train_jobs_multi: this program's boost plan does not "
                "say how a round's class trees are grown (no "
                "`BoostPlan.class_batch`) — no result")
        table = table_module(self.config["table"])
        self.X, self.y = getattr(table, self.config["table"])(
            self.rows, self.seed)
        self.cols = table.as_columns(self.X, self.y)

    def setup(self) -> None:
        super().setup()
        # a program that grows the classes one at a time (`lax.map`
        # past its histogram budget), bundles the columns (an EFB plan:
        # another histogram width) or trains K classes outside the
        # boost plan is not measured under this cell's name
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER

        roots = TRACER.by_root("train")
        root = roots[-1]["spans"][0] if roots else {}
        want = {"features": int(self.config["features"]),
                "classes": self.classes, "class_batch": "vmap"}
        got = {k: root.get(k) for k in want}
        if got != want:
            raise SystemExit(
                f"train_jobs_multi: the warm-up job's `train` root says "
                f"{got}, the cell measures {want} — no result")
