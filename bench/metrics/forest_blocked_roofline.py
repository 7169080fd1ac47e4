"""The bin-blocked kernel's share of its roofline in a forest over a
matrix of 16-bit bin codes, on the fullest device: the least seconds
one chip could take for the tree-levels the `hist_blocked.<n>` calls
served, over those calls' device seconds inside the traced jobs.

Counted as `bench/work.py` counts a level (every row's bin codes, its
value, weight and node id read once; one add a code a channel), but
with each bin code at the bytes it is STORED in — 2 where the matrix
has more than 256 bins, which the newest `train` root's `bins` says —
where `work.level_bytes` counts 1:

    bytes = rows x (F x code bytes + 16)      adds = rows x F x C

A forest grows one tree a scan step, so one call serves one level of
one tree. A program whose spans do not say its bins reports nothing."""

import trace_reduce as tr
import work
from _common import fullest, job_spans
from hist_blocked_share import blocked_ops


def level_min_seconds(rows: int, features: int, channels: int,
                      code_bytes: int, peak: dict) -> tuple[float, str]:
    """(least seconds one chip could take for one level, the bound
    that sets it: "bytes" or "adds")."""
    by = rows * (features * code_bytes + work.ROW_STATE_BYTES) \
        / peak["hbm_bytes_per_s"]
    ad = work.level_adds(rows, features, channels) \
        / peak["bf16_flops_per_s"]
    return (by, "bytes") if by >= ad else (ad, "adds")


def _code_bytes():
    """Bytes a bin code of the newest job's matrix; None where the
    program's spans do not say its bins."""
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER

        bins = int(TRACER.by_root("train")[-1]["spans"][0].get("bins") or 0)
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    return (1 if bins <= 256 else 2) if bins else None


def read(ctx):
    code_bytes = _code_bytes()
    ks = tr.inside(blocked_ops(ctx, fullest(ctx)), job_spans(ctx))
    if not ks or not code_bytes:
        return None
    sh = ctx["shape"]
    per_level, bound = level_min_seconds(
        sh["rows"] // ctx["chips"], sh["features"], sh["channels"],
        code_bytes, ctx["peak"])
    ctx["say"](f"forest_blocked_roofline is bound by {bound}: "
               f"{per_level * 1e3:.4f} ms a level at {code_bytes}-byte "
               f"codes, {len(ks)} calls")
    return 100.0 * len(ks) * per_level / (tr.total(ks) / 1e9)
