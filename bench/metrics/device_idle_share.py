"""Share of the traced window in which no operation ran on the fullest
device."""

from _common import busy_ns, fullest


def read(ctx):
    lo, hi = ctx["window"]
    busy = busy_ns(ctx, fullest(ctx))
    if not busy:
        return None
    return 100.0 * (1.0 - busy / (hi - lo))
