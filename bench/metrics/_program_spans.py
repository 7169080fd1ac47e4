"""The program's own spans (`runtime/telemetry.phase_span`: the
`frame.from_arrays` and `train` roots and their children) on the trace's
clock, for the readers that are named after what they read there. Not a
metric: no metric is named `_program_spans`.

The program stamps its spans with `time.perf_counter_ns()`, the clock
`traffic/train_jobs.py` stamps a job's `start` with; the benchmark's
`bench.job` span starts within microseconds of that stamp on the
profiler's clock. So for job i of the window
    offset = start of the i-th `bench.job` span - jobs[i]["start"]
puts that job's spans beside the device operations. Every job uses its
own offset; the offsets of one run have to agree to a millisecond, and
every `train` root has to lie inside its `bench.train` span to a tenth
of one: if not, the readers return nothing and say why.

The records come from the program's trace ring in this process, or from
`ctx["program_spans"]` ({root name: [record, ...]}) where a test supplies
them. A program without the ring's listing (the parent of the PR that
added it) gives no records and no metric.
"""

from __future__ import annotations

import trace_reduce as tr
from _common import job_spans

AGREE_NS = 1e6
INSIDE_NS = 1e5
_MEMO = "_program_spans.jobs"


def records(ctx, root: str):
    """The span records whose root span is ``root``, oldest first, or
    None where the program keeps none."""
    given = ctx.get("program_spans")
    if given is not None:
        return list(given.get(root, []))
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER
    except ImportError:
        return None
    by_root = getattr(TRACER, "by_root", None)
    return by_root(root) if by_root else None


def root_of(record: dict) -> dict:
    return next(s for s in record["spans"] if s["parent"] is None)


def seconds(span: dict) -> float:
    return (span["t1_ns"] - span["t0_ns"]) / 1e9


def leaves(spans: list) -> list:
    """The spans that no other span of the record names as parent."""
    parents = {s["parent"] for s in spans}
    return [s for s in spans if s["id"] not in parents]


def _on_trace_clock(record: dict, offset: float) -> list:
    return [dict(s, t0=s["t0_ns"] + offset, t1=s["t1_ns"] + offset)
            for s in record["spans"]]


def _jobs(ctx):
    say = ctx["say"]
    spans = job_spans(ctx)
    stamped = ctx["result"].get("jobs") or []
    if not spans or len(spans) != len(stamped):
        say(f"program spans: {len(spans)} bench.job spans in the window "
            f"for {len(stamped)} stamped jobs — not read")
        return None
    offsets = [s[0] - j["start"] * 1e9 for s, j in zip(spans, stamped)]
    if max(offsets) - min(offsets) > AGREE_NS:
        say("program spans: the jobs' clock offsets differ by "
            f"{(max(offsets) - min(offsets)) / 1e6:.3f} ms (limit 1 ms) "
            "— not read")
        return None
    trains, frames = records(ctx, "train"), records(ctx, "frame.from_arrays")
    if not trains:
        say("program spans: the program's trace ring holds no `train` "
            "record (an older program, or H2O_TPU_TRACE=0) — not read")
        return None
    out = []
    for span, job, off, outer in zip(spans, stamped, offsets,
                                     job_spans(ctx, "bench.train")):
        lo, hi = job["start"] * 1e9, job["end"] * 1e9

        def mine(recs):
            return [_on_trace_clock(r, off) for r in recs
                    if lo <= root_of(r)["t0_ns"] <= hi]

        train = mine(trains)
        if len(train) != 1:
            say(f"program spans: {len(train)} `train` roots in a job "
                "— not read")
            return None
        root = train[0][0]
        out_by = max(outer[0] - root["t0"], root["t1"] - outer[1])
        if out_by > INSIDE_NS:
            say("program spans: a `train` root lies outside its "
                f"bench.train span by {out_by / 1e6:.3f} ms (limit 0.1 "
                "ms) — not read")
            return None
        out.append({"span": span, "train": train[0],
                    "frames": mine(frames or [])})
    return out


def jobs(ctx):
    """Per job of the window: its `bench.job` span, the spans of its
    `train` record and of its `frame.from_arrays` records, each span
    with `t0`/`t1` on the trace's clock (root first). None, with the
    reason said once, where that cannot be had."""
    if _MEMO not in ctx:
        ctx[_MEMO] = _jobs(ctx)
    return ctx[_MEMO]


def covered(spans: list) -> float:
    """Nanoseconds that the spans cover, overlaps counted once."""
    return tr.total([(s["t0"], s["t1"]) for s in spans])
