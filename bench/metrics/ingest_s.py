"""Seconds of `Frame.from_arrays`, blocked until the columns are on the
device: the median of the `bench.from_arrays` spans. Binning happens
inside `train()` and cannot be timed from outside."""

import statistics

from _common import job_spans


def read(ctx):
    spans = job_spans(ctx, "bench.from_arrays")
    if not spans:
        return None
    return statistics.median((e - s) / 1e9 for s, e, _ in spans)
