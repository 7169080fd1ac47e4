#!/usr/bin/env python
"""Append the 10M-row AutoML scale point to the CPU curve.

The 100k/300k/1M CPU curve (AUTOML_SCALE_r05.json) measured the full
default plan; at 10M rows on the 1-core CPU mesh the full plan is
multi-day, so the 10M point uses the harness's fixed-budget framing
(tools/automl_scale.py --max-runtime-secs docstring): ONE plan family
(GBM — the north-star algo), no CV (the leaderboard ranks on training
metrics, the documented nfolds<2 fallback), and the recorded metric is
models + leader quality + wall at 10M.

Writes AUTOML_SCALE_r06.json = the r05 curve + the 10M point.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    from h2o_kubernetes_tpu.runtime.backend import \
        enable_persistent_compile_cache

    enable_persistent_compile_cache()
    from tools.automl_scale import run_shape

    point = run_shape(
        rows=int(os.environ.get("AUTOML_10M_ROWS", 10_000_000)),
        max_models=1, nfolds=0,
        exclude_algos=["glm", "drf", "deeplearning", "xgboost",
                       "stackedensemble"])
    point["note"] = ("fixed-budget 10M point: single GBM family, "
                     "nfolds=0 (training-metric leaderboard fallback) "
                     "— the full plan is multi-day on 1 CPU core")
    prev_path = os.path.join(REPO, "AUTOML_SCALE_r05.json")
    try:
        with open(prev_path) as f:
            prev = json.load(f)
    except OSError:
        prev = {"curve": []}
    out = {"curve": prev.get("curve", []) + [point],
           "recompile_check": prev.get("recompile_check"),
           "note_10m": point["note"]}
    out_path = os.path.join(REPO, "AUTOML_SCALE_r06.json")
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"automl_scale_10m": "done", "file": out_path,
                      "wall_seconds": point["wall_seconds"],
                      "error": bool(point.get("error"))}))
    return 0 if not point.get("error") else 1


if __name__ == "__main__":
    sys.exit(main())
