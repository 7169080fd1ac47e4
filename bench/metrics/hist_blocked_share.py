"""Share of the fullest device's busy time spent in the bin-blocked
histogram kernel: the operations named `hist_blocked.<n>`
(`ops/histogram.py` names its `pallas_call`s, so the instruction is the
kernel's name) inside the boost programs."""

import trace_reduce as tr
from _common import busy_ns, fullest
from hist_kernel_share import kernel_ops

KERNEL = "hist_blocked"


def blocked_ops(ctx, dev) -> list:
    """The boost programs' Mosaic calls whose instruction is named
    `hist_blocked.<n>`."""
    return [o for o in kernel_ops(ctx, dev)
            if o[2].split(" ")[0].split(".")[0] == KERNEL]


def read(ctx):
    dev = fullest(ctx)
    ks, busy = blocked_ops(ctx, dev), busy_ns(ctx, dev)
    if not ks or not busy:
        return None
    return 100.0 * tr.total(ks) / busy
