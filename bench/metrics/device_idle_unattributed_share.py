"""Of the fullest device's idle seconds in the window, the share that
no leaf span of the program covers (a leaf span names no child: what
the host was doing, as finely as the program says). Idle seconds by
leaf span, and the rest by the innermost span around them, are said on
an earlier line."""

import trace_reduce as tr
from _common import fullest

import _program_spans as ps


def read(ctx):
    if not ctx["trace"].devices:
        return None
    jobs = ps.jobs(ctx)
    if not jobs:
        return None
    lo, hi = ctx["window"]
    idle = tr.gaps(fullest(ctx).ops, lo, hi)
    total = sum(e - s for s, e in idle)
    if not total:
        return None
    recs = [rec for j in jobs for rec in [j["train"]] + j["frames"]]
    leaf = [(s["t0"], s["t1"], s["name"])
            for rec in recs for s in ps.leaves(rec)]
    inner = [(s["t0"], s["t1"], s["name"]) for rec in recs for s in rec]
    by_leaf: dict[str, float] = {}
    rest: dict[str, float] = {}
    for gs, ge in idle:
        for s, e, name in tr.clip(leaf, gs, ge):
            by_leaf[name] = by_leaf.get(name, 0.0) + (e - s)
        for s, e in tr.gaps(leaf, gs, ge):
            name = tr.span_at(inner, (s + e) / 2)
            if name == "outside":
                name = tr.span_at(ctx["trace"].spans, (s + e) / 2)
            rest[name] = rest.get(name, 0.0) + (e - s)

    def listed(d):
        return ", ".join(f"{k} {v / 1e9:.4f}s" for k, v in
                         sorted(d.items(), key=lambda kv: -kv[1])) or "none"

    ctx["say"](f"device idle {total / 1e9:.4f}s in the window; by leaf "
               f"span: {listed(by_leaf)}")
    ctx["say"](f"device idle no leaf span covers, by the span around it: "
               f"{listed(rest)}")
    return 100.0 * sum(rest.values()) / total
