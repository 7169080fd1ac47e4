"""Boost-program executions per traced job, on the fullest device: a
count that repeats exactly."""

import trace_reduce as tr
from _common import boost_modules, fullest, job_spans


def read(ctx):
    jobs = job_spans(ctx)
    mods = tr.inside(boost_modules(ctx, fullest(ctx)), jobs)
    if not jobs or not mods:
        return None
    return len(mods) / len(jobs)
