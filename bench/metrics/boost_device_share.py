"""Share of the fullest device's busy time spent inside the boost
programs' module events."""

import trace_reduce as tr
from _common import boost_modules, busy_ns, fullest


def read(ctx):
    dev = fullest(ctx)
    mods = boost_modules(ctx, dev)
    busy = busy_ns(ctx, dev)
    if not mods or not busy:
        return None
    lo, hi = ctx["window"]
    ops = tr.inside(tr.clip(dev.ops, lo, hi), mods)
    return 100.0 * tr.total(ops) / busy
