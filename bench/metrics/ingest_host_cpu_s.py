"""Seconds of CPU a job's ingest used on the host: the `frame.encode`
spans' `cpu_ms` summed per job, the median over the window's jobs.
Near `ingest_host_s` the host computes (columns in parallel would
win); the system part and the page faults, said on an earlier line,
are first touches of fresh buffers (a reused staging buffer would
win); what is left of `ingest_host_s` is a thread that was not
running. The three longest columns of a job are said by name, path and
the dtype they arrived in."""

import statistics

import _program_spans as ps


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs or not all(j["frames"] for j in jobs):
        return None
    per_job = []
    for j in jobs:
        enc = [s for rec in j["frames"] for s in rec
               if s["name"] == "frame.encode"]
        if not enc or not all("cpu_ms" in s for s in enc):
            return None
        per_job.append(sum(s["cpu_ms"] for s in enc) / 1e3)
        ctx["say"](
            f"frame.encode of a job: {len(enc)} spans "
            f"{sum(ps.seconds(s) for s in enc):.4f}s, cpu "
            f"{per_job[-1]:.4f}s of it system "
            f"{sum(s['sys_ms'] for s in enc) / 1e3:.4f}s, faults "
            f"{sum(s.get('faults', 0) for s in enc)}, switched "
            f"{sum(s.get('switched', 0) for s in enc)}, gc "
            f"{sum(s.get('gc_ms', 0) for s in enc):.1f}ms; the longest: "
            + "; ".join(
                f"{s.get('column')} {s.get('path')} {s.get('dtype')} "
                f"{ps.seconds(s):.4f}s cpu {s['cpu_ms'] / 1e3:.4f}s "
                f"system {s['sys_ms'] / 1e3:.4f}s faults "
                f"{s.get('faults', 0)}"
                for s in sorted(enc, key=ps.seconds)[:-4:-1]))
    return statistics.median(per_job)
