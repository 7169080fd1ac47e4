"""Share of the traced window that the fullest device spent in
all-reduce operations while nothing else ran on it."""

import trace_reduce as tr
from _common import fullest

ALL_REDUCE = "all-reduce"     # all-reduce, all-reduce-start, -done


def read(ctx):
    lo, hi = ctx["window"]
    dev = fullest(ctx)
    ops = tr.clip(dev.ops, lo, hi)
    rest = [o for o in ops if not tr.opcode(o[2]).startswith(ALL_REDUCE)]
    # a synchronous all-reduce is an operation of its own; an
    # asynchronous one spans from its start to its done on the async line
    ar = [o for o in ops + tr.clip(dev.async_ops, lo, hi)
          if tr.opcode(o[2]).startswith(ALL_REDUCE)]
    if not ar:
        return None
    return 100.0 * tr.exposed(ar, rest) / (hi - lo)
