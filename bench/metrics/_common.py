"""What several readers ask of one traced window. Not a metric: the
harness looks readers up by a metric's name, and no metric is named
`_common`."""

from __future__ import annotations

import trace_reduce as tr

# the boost programs' module events carry the jitted function's name:
# core._boost_jit, core._boost_drf_jit, core._boost_multi_jit
BOOST_MODULE = "_boost"


def fullest(ctx):
    """The device with the most busy time in the window."""
    return tr.fullest(ctx["trace"], *ctx["window"])


def busy_ns(ctx, dev) -> float:
    lo, hi = ctx["window"]
    return tr.total(tr.clip(dev.ops, lo, hi))


def boost_modules(ctx, dev) -> list:
    lo, hi = ctx["window"]
    return [m for m in tr.clip(dev.modules, lo, hi) if BOOST_MODULE in m[2]]


def job_spans(ctx, name: str = "bench.job") -> list:
    """The named spans that lie whole inside the window."""
    lo, hi = ctx["window"]
    return [s for s in ctx["trace"].spans
            if s[2] == name and s[0] >= lo and s[1] <= hi]
