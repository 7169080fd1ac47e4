"""Seconds of one whole job (a new frame, training, reading the model):
the median of the benchmark's `bench.job` spans in the traced window."""

import statistics

from _common import job_spans


def read(ctx):
    spans = job_spans(ctx)
    if not spans:
        return None
    return statistics.median((e - s) / 1e9 for s, e, _ in spans)
