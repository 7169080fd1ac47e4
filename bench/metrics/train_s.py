"""Seconds of `train()` by the program's own `train` root span: the
median over the window's jobs. `job_s` less ingest, the benchmark's
reading of the model and the frame's release."""

import statistics

import _program_spans as ps


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs:
        return None
    return statistics.median(ps.seconds(j["train"][0]) for j in jobs)
