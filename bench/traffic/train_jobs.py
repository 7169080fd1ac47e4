"""Traffic kind `train_jobs`: training jobs back to back.

One job is what a user runs: `h2o.Frame.from_arrays(columns)` on a new
frame, `<estimator>(<the configuration's parameters>).train(...)` on
the mesh `h2o.init()` built over the cell's devices, ended by reading
the model's last scoring-history row (a host scalar, so the device has
finished) and the model's trees. The previous job's frame and model
are dropped before the next starts; a job that has started is
finished; the window runs from the first job's start to the last job's
end and is never shorter than asked.

The parameters of a mix are its cell's file: `config`, `chips`,
`limits`, and what the configuration's comparison reads there. The
comparison (`compare/<name>.py`, named by the configuration) takes the
program's model to a plain form and holds it against the reference.
"""

from __future__ import annotations

import time

import numpy as np

import datasets
import work

MAX_SEED = 2 ** 31 - 1


class Traffic:
    def __init__(self, cell: dict, config: dict, seed: int, annotate,
                 comparison):
        """``annotate(name)`` gives a context manager that marks a host
        span on the profiler's clock; ``comparison`` is the
        configuration's comparison module."""
        self.cell, self.config, self.seed = cell, config, int(seed)
        self.annotate, self.comparison = annotate, comparison
        self.rows = int(config["rows_per_chip"]) * int(cell["chips"])
        self.trees = int(config["ntrees"])
        self.models: list[dict] = []

    # -- what the readers and the harness ask ---------------------------

    def shape(self) -> dict:
        return {"rows": self.rows, "features": int(self.config["features"]),
                "trees": self.trees,
                "max_depth": int(self.config["params"]["max_depth"]),
                "channels": int(self.config["histogram_channels"])}

    # -- set-up ----------------------------------------------------------

    def load(self) -> None:
        """The estimator and the table from the seed."""
        import h2o_kubernetes_tpu.models as models

        self.estimator = getattr(models, self.config["estimator"])
        self.X, self.y = datasets.TABLES[self.config["table"]](
            self.rows, self.seed)
        self.cols = datasets.as_columns(self.X, self.y)

    def setup(self) -> None:
        self.load()
        self.job(-1)               # every shape of the window, once

    # -- one job ---------------------------------------------------------

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
                    seed=(self.seed + index + 1) % MAX_SEED,
                ).train(y="y", training_frame=fr)
                model = self.comparison.neutral_model(m)
            del fr, m
        t2 = time.perf_counter()
        return {"start": t0, "end": t2, "ingest_s": t1 - t0,
                "job_s": t2 - t0, "model": model,
                "ok": bool(len(model["trees"]) == self.trees
                           and np.isfinite(model["train_logloss"]))}

    # -- the window --------------------------------------------------------

    def window(self, seconds: float) -> dict:
        jobs = []
        with self.annotate("bench.window"):
            start = time.perf_counter()
            while time.perf_counter() - start < seconds:
                jobs.append(self.job(len(jobs)))
        self.models = [j.pop("model") for j in jobs]
        span = jobs[-1]["end"] - jobs[0]["start"]
        done = sum(j["ok"] for j in jobs)
        return {"attempted": len(jobs), "failed": len(jobs) - done,
                "window_s": span, "jobs": jobs,
                "end_to_end": {"train_rowtrees_per_s":
                               done * work.job_rowtrees(self.shape()) / span}}

    # -- after the window --------------------------------------------------

    def release(self) -> None:
        """Drop what the program's jobs were fed, so that the reference
        runs on a freed device and host."""
        self.cols = None

    def compare(self) -> dict[str, float]:
        """The numbers that decide `correct`, for one of the window's
        jobs drawn from the seed."""
        model = self.models[self.seed % len(self.models)]
        Xr = np.ascontiguousarray(self.X.T)
        return self.comparison.compare(model, Xr, self.y, self.config,
                                       self.cell, self.seed)
