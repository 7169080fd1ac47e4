"""Seconds of `h2o.init()`: the program's first `init` root span. Its
children are said on an earlier line: `init.cache` (the persistent
compile cache), `init.distributed`, `init.mesh` (the mesh over the
devices; the backend's start where the caller has not asked for the
devices before)."""

import _program_spans as ps


def read(ctx):
    recs = ps.records(ctx, "init")
    if not recs:
        return None
    root = ps.root_of(recs[0])
    ctx["say"]("init: " + ", ".join(
        f"{s['name']} {ps.seconds(s):.4f}s cpu "
        f"{s.get('cpu_ms', float('nan')) / 1e3:.4f}s"
        for s in recs[0]["spans"]))
    return ps.seconds(root)
