"""Host seconds a job spends building its query layout: the
`train.group_layout` span (the group column read back, the queries
found in one pass, the size classes and their index tables, maxDCG a
query), the median over the window's jobs. A program without the span
reports nothing."""

import statistics

import _program_spans as ps


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs or not all(j["train"] for j in jobs):
        return None
    per_job = [[ps.seconds(s) for s in j["train"]
                if s["name"] == "train.group_layout"] for j in jobs]
    if not all(per_job):
        return None
    return statistics.median(sum(p) for p in per_job)
