"""The least work the algorithm needs, from a cell's shapes alone.

Counted from what a histogram GBM has to do, not from what any kernel
issues, so that a metric built on it reads the same work whatever
implements it: at one level of one tree every row's F bin codes
(uint8), its gradient, hessian and weight (float32) and its node id
(int32) are read once, and each of the F codes takes one add into each
histogram channel. Every row of the frame counts, sampled or not. A
tree of depth D has D levels that need a histogram.
"""

from __future__ import annotations

ROW_STATE_BYTES = 16      # g, h, w as float32 and the node id as int32


def level_bytes(rows: int, features: int) -> int:
    return rows * (features + ROW_STATE_BYTES)


def level_adds(rows: int, features: int, channels: int) -> int:
    return rows * features * channels


def level_min_seconds(rows: int, features: int, channels: int,
                      peak: dict) -> tuple[float, str]:
    """(least seconds one chip could take for one level, the bound that
    sets it: "bytes" or "adds")."""
    by = level_bytes(rows, features) / peak["hbm_bytes_per_s"]
    ad = level_adds(rows, features, channels) / peak["bf16_flops_per_s"]
    return (by, "bytes") if by >= ad else (ad, "adds")


def job_levels(shape: dict) -> int:
    """Histogram levels of one training job."""
    return int(shape["trees"]) * int(shape["max_depth"])


def job_min_seconds(shape: dict, peak: dict, chips: int = 1) -> float:
    """Least seconds ``chips`` chips could take for one job's levels,
    rows divided evenly over them."""
    per_level, _ = level_min_seconds(
        int(shape["rows"]) // chips, int(shape["features"]),
        int(shape["channels"]), peak)
    return job_levels(shape) * per_level


def job_rowtrees(shape: dict) -> int:
    return int(shape["rows"]) * int(shape["trees"])
