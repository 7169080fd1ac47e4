"""Host seconds of a job's ingest: the `frame.encode` spans of its
`Frame.from_arrays` (the string response's factorize, casts, padding),
summed per job, the median over the window's jobs. `ingest_s` less
this is the transfer and the wait for it."""

import statistics

import _program_spans as ps


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs or not all(j["frames"] for j in jobs):
        return None
    return statistics.median(
        sum(ps.seconds(s) for rec in j["frames"] for s in rec
            if s["name"] == "frame.encode") for j in jobs)
