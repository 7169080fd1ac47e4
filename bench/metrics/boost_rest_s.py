"""Device seconds a job spends inside the boost programs' module
events OUTSIDE their Mosaic kernels: split finding (the level order of
`set_order` among it), row descent (`set_descend`), the value stack,
the margin update — everything of a tree but its histograms. Median
over the window's jobs of the fullest device. (`trace_reduce.load`
keeps no `tf_op`, so the named scopes inside it cannot be told apart
here yet.)"""

import statistics

import trace_reduce as tr
from _common import boost_modules, fullest, job_spans
from hist_kernel_share import KERNEL


def read(ctx):
    dev = fullest(ctx)
    mods = boost_modules(ctx, dev)
    jobs = job_spans(ctx)
    if not mods or not jobs:
        return None
    per_job = []
    for lo, hi, _ in jobs:
        ops = tr.inside(tr.clip(dev.ops, lo, hi), mods)
        per_job.append(tr.total(
            [o for o in ops if tr.opcode(o[2]) != KERNEL]) / 1e9)
    return statistics.median(per_job)
