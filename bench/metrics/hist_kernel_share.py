"""Share of the fullest device's busy time spent in the histogram
kernels: the Mosaic custom calls inside the boost programs. Neither
`pallas_call` of `ops/histogram.py` is named, so the factorized and the
bin-blocked kernel read as one."""

import trace_reduce as tr
from _common import boost_modules, busy_ns, fullest

KERNEL = "custom-call:tpu_custom_call"      # a Mosaic kernel's call


def kernel_ops(ctx, dev) -> list:
    lo, hi = ctx["window"]
    ops = tr.inside(tr.clip(dev.ops, lo, hi), boost_modules(ctx, dev))
    return [o for o in ops if tr.opcode(o[2]) == KERNEL]


def read(ctx):
    dev = fullest(ctx)
    ks, busy = kernel_ops(ctx, dev), busy_ns(ctx, dev)
    if not ks or not busy:
        return None
    return 100.0 * tr.total(ks) / busy
