"""Seconds of the warm-up job's `train()`: the first `train` root the
program recorded, which ended before the window's first job started.
Over `train_s` it is what re-tracing and loading the programs cost."""

import _program_spans as ps


def read(ctx):
    trains = ps.records(ctx, "train")
    stamped = ctx["result"].get("jobs")
    if not trains or not stamped:
        return None
    first = ps.root_of(trains[0])
    if first["t1_ns"] > stamped[0]["start"] * 1e9:
        ctx["say"]("setup_train_s: the ring's first `train` record is "
                   "not the warm-up job's — not read")
        return None
    return ps.seconds(first)
