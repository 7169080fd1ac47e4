"""The histogram kernels' share of their roofline on the fullest
device: the least seconds one chip could take for the histogram levels
of the traced jobs (`work.level_min_seconds` for this device's rows,
counted from the cell's shapes, not from what the kernel issues), over
the kernels' device seconds in those jobs."""

import trace_reduce as tr
import work
from _common import fullest, job_spans
from hist_kernel_share import kernel_ops


def read(ctx):
    dev = fullest(ctx)
    jobs = job_spans(ctx)
    ks = tr.inside(kernel_ops(ctx, dev), jobs)
    if not ks or not jobs:
        return None
    sh = ctx["shape"]
    per_level, bound = work.level_min_seconds(
        sh["rows"] // ctx["chips"], sh["features"], sh["channels"],
        ctx["peak"])
    ctx["say"](f"hist_kernel_roofline is bound by {bound}: "
               f"{per_level * 1e3:.4f} ms a level")
    least = len(jobs) * work.job_levels(sh) * per_level
    return 100.0 * least / (tr.total(ks) / 1e9)
