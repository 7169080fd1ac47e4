"""Programs traced inside the window, a job: the `traces` the compile
watch credited to the root spans of the window's jobs (`train` and
`frame.from_arrays`; the field is inclusive, so a root's holds its
children's), over the jobs. A function traced in the window is a
re-trace: every shape was warmed up, and a re-trace that then finds
its executable in a cache never shows as a compile. The programs are
named on an earlier line."""

import _program_spans as ps


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs:
        return None
    roots = [rec[0] for j in jobs for rec in [j["train"]] + j["frames"]]
    if not all("cpu_ms" in r for r in roots):
        return None         # a program that credits nothing to a span
    traced = [r for r in roots if r.get("traces")]
    for r in traced:
        ctx["say"](f"traced inside the window under {r['name']}: "
                   f"{r['traces']} program(s) {r.get('programs')}, "
                   f"{r.get('trace_ms', 0):.1f}ms")
    return sum(r["traces"] for r in traced) / len(jobs)
