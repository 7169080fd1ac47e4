"""AutoML wall-clock scaling evidence (Airlines-10M config shape).

The north star (`BASELINE.json` config #5) is AutoML wall-clock on an
Airlines-10M-shaped table. This harness produces the round's evidence
either way:

- on a TPU (``--rows 10000000 --max-models 12``): the on-chip
  wall-clock + leaderboard the north star is phrased in;
- on the CPU mesh (default): a rows-scaling curve with XLA
  **compile-count accounting** — the count must NOT grow with
  max_models (no per-model recompiles; dispatch-budget chunking and
  shared jitted trainers mean every same-shaped model reuses the same
  executables).

Prints one JSON line per shape + a trailing summary line, and writes
``AUTOML_SCALE_r05.json`` (CPU) / ``AUTOML_TPU_r05.json`` (TPU) at the
repo root.
"""

import argparse
import json
import logging
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


class _CompileCounter(logging.Handler):
    """Counts XLA compiles via jax's log_compiles events."""

    def __init__(self):
        super().__init__()
        self.count = 0

    def emit(self, record):
        if "Compiling" in record.getMessage():
            self.count += 1


def make_table(rows: int, seed: int = 0):
    # the full airlines shape (~27 mixed columns, NAs, enum response) —
    # the table BASELINE.json config #5 is phrased in
    from tools.datasets import airlines_frame

    return airlines_frame(rows, seed=seed)


def run_shape(rows: int, max_models: int, nfolds: int,
              max_runtime_secs: float | None = None,
              exclude_algos=None, include_algos=None) -> dict:
    import traceback

    import jax

    from h2o_kubernetes_tpu.automl import AutoML

    counter = _CompileCounter()
    # ONLY the root 'jax' logger: records from jax submodules propagate
    # up the hierarchy, so attaching to a child too would double-count
    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(counter)
    err = None
    aml = None
    lb = []
    wall = 0.0
    try:
        fr = make_table(rows)
        t0 = time.perf_counter()
        aml = AutoML(max_models=max_models, nfolds=nfolds, seed=1,
                     max_runtime_secs=max_runtime_secs,
                     exclude_algos=exclude_algos,
                     include_algos=include_algos,
                     project_name=f"scale_{rows}")
        aml.train(y="IsDepDelayed", training_frame=fr)
        wall = time.perf_counter() - t0
        lb = aml.leaderboard.as_list()
    except Exception:
        # a crashed shape must still leave a diagnosable record — the
        # first on-chip 10M run died with nothing but an exit code
        err = traceback.format_exc()[-2000:]
    finally:
        jax.config.update("jax_log_compiles", False)
        logging.getLogger("jax").removeHandler(counter)
    out = {
        "rows": rows,
        "max_models": max_models,
        "nfolds": nfolds,
        "max_runtime_secs": max_runtime_secs,
        "models_trained": len(lb),
        "wall_seconds": round(wall, 1),
        "xla_compiles": counter.count,
        "leader": lb[0]["model_id"] if lb else None,
        "leader_auc": round(lb[0].get("auc", float("nan")), 5)
        if lb else None,
        # full-precision rows: the bench's pipelined-vs-serial identity
        # check compares every printed digit (minus wall-clock fields)
        "leaderboard": lb,
        # overlap accounting when the pipelined executor ran
        # (runtime/scheduler.py; None on H2O_TPU_AUTOML_PIPELINE=0)
        "scheduler_stats": aml.scheduler_stats if aml is not None
        else None,
        "platform": jax.default_backend(),
        # the event log carries every swallowed per-model failure —
        # a 1-model leaderboard is explainable from the artifact alone
        "event_log": [f"{ts} {m}" for ts, m in
                      (aml.event_log if aml is not None else [])][-60:],
        "error": err,
    }
    print(json.dumps(out), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, nargs="+", default=None,
                    help="row counts (default: 100k/300k/1M cpu curve)")
    ap.add_argument("--max-models", type=int, default=6)
    ap.add_argument("--nfolds", type=int, default=3)
    ap.add_argument("--max-runtime-secs", type=float, default=None,
                    help="AutoML time budget per shape (the on-chip "
                    "10M capture sets this so it fits inside a chip "
                    "availability window; the metric becomes "
                    "models+leader-AUC within the budget — the same "
                    "fixed-time framing the reference's AutoML wall-"
                    "clock comparisons use)")
    ap.add_argument("--exclude-algos", nargs="+", default=None,
                    help="AutoML families to skip (the 1M-row CPU "
                    "curve drops drf/deeplearning: 100 depth-12 CPU "
                    "trees per point measure the box, not the design)")
    ap.add_argument("--include-algos", nargs="+", default=None,
                    help="restrict the plan to these families "
                    "(mutually exclusive with --exclude-algos)")
    ap.add_argument("--no-recompile-check", action="store_true",
                    help="skip the warm-repeat recompile check (the "
                    "automl_wall bench runs serial/pipelined legs in "
                    "separate processes and checks warm compiles on "
                    "one leg only)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from h2o_kubernetes_tpu.runtime.backend import \
        enable_persistent_compile_cache

    enable_persistent_compile_cache()
    import jax

    on_tpu = jax.default_backend() == "tpu"
    rows_list = args.rows or ([10_000_000] if on_tpu
                              else [100_000, 300_000, 1_000_000])
    results = [run_shape(r, args.max_models, args.nfolds,
                         args.max_runtime_secs, args.exclude_algos,
                         args.include_algos)
               for r in rows_list]
    # per-model recompile check: a WARM repeat of the smallest shape
    # (same families, same row count, same plan) must compile ~nothing
    # — every fold/final/ensemble train reuses the shape-keyed
    # executables from the first pass. (A half-max_models comparison is
    # confounded: fewer models means fewer FAMILIES, so the compile
    # delta measures family difference, not per-model recompiles.)
    # CPU-mesh only: on chip it would double the wall inside a scarce
    # availability window for a diagnostic the CPU curve already gives.
    recompile_check = None
    if not on_tpu and not args.no_recompile_check and len(results) >= 1 \
            and not results[0].get("error"):
        warm = run_shape(rows_list[0], args.max_models, args.nfolds,
                         args.max_runtime_secs, args.exclude_algos,
                         args.include_algos)
        recompile_check = {
            "cold_models": results[0]["models_trained"],
            "cold_compiles": results[0]["xla_compiles"],
            "warm_models": warm["models_trained"],
            "warm_compiles": warm["xla_compiles"],
            "warm_run_ok": warm["xla_compiles"]
            <= max(5, results[0]["xla_compiles"] // 20),
        }
    summary = {"curve": results, "recompile_check": recompile_check,
               "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S")}
    out = args.out or os.path.join(
        REPO, "AUTOML_TPU_r05.json" if on_tpu else "AUTOML_SCALE_r05.json")
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"automl_scale": "done", "file": out,
                      "shapes": len(results)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
