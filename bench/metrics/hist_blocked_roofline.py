"""The bin-blocked kernel's share of its roofline on the fullest
device: the least seconds one chip could take for the tree-levels its
calls served (`work.level_min_seconds` for this device's rows, counted
from the cell's shapes, not from what the kernel issues), over the
calls' device seconds. A forest grows one tree a scan step, so one call
serves one level of one tree."""

import trace_reduce as tr
import work
from _common import fullest, job_spans
from hist_blocked_share import blocked_ops


def read(ctx):
    dev = fullest(ctx)
    ks = tr.inside(blocked_ops(ctx, dev), job_spans(ctx))
    if not ks:
        return None
    sh = ctx["shape"]
    per_level, bound = work.level_min_seconds(
        sh["rows"] // ctx["chips"], sh["features"], sh["channels"],
        ctx["peak"])
    ctx["say"](f"hist_blocked_roofline is bound by {bound}: "
               f"{per_level * 1e3:.4f} ms a level, {len(ks)} calls")
    return 100.0 * len(ks) * per_level / (tr.total(ks) / 1e9)
