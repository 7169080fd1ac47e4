"""Seconds of `train()` that the host would still need if the device
took no time: per job the `train` root less what its `wait` spans
cover (the spans that read a device result back), the median over the
window's jobs. An `enqueue` span should read the dispatch alone; where
the `train.dispatch` spans read near a device-second each, the runtime
is holding the host back in them, so the job's spans are said by name
on an earlier line (kind, count, seconds) and the dispatches' sum and
longest apart."""

import statistics

import _program_spans as ps


def by_name(spans: list) -> str:
    """"name kind xN seconds", in the order the names first opened."""
    rows: dict[str, list] = {}
    for s in spans:
        row = rows.setdefault(s["name"], [s["kind"], 0, 0.0])
        row[1] += 1
        row[2] += ps.seconds(s)
    return ", ".join(f"{name} {kind} x{n} {secs:.4f}s"
                     for name, (kind, n, secs) in rows.items())


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs:
        return None
    host = []
    for j in jobs:
        root = j["train"][0]
        wait = ps.covered([s for s in j["train"] if s["kind"] == "wait"])
        host.append(ps.seconds(root) - wait / 1e9)
        ctx["say"](f"train spans of a job: {by_name(j['train'])}")
        sent = [ps.seconds(s) for s in j["train"]
                if s["name"] == "train.dispatch"]
        if sent:
            ctx["say"](f"train.dispatch: {len(sent)} spans, "
                       f"{sum(sent):.4f}s in all, the longest "
                       f"{max(sent):.4f}s")
    return statistics.median(host)
