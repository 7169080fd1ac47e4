"""What the readers of the host's side of a span ask of the program's
records (`runtime/telemetry.HOST_FIELDS`: a span's thread CPU, system
time, faults, switches, collector pauses and the seconds of jax's
trace / lower / compile stages paid under it) and of the records of
set-up: the `import` and `init` roots and the warm-up job's. Not a
metric: no metric is named `_host_spans`. The records come through
`_program_spans.records`, so a test supplies them the same way, and a
program without the fields gives None everywhere."""

from __future__ import annotations

import _program_spans as ps

STAGES = ("trace_ms", "lower_ms", "compile_ms")


def off_cpu_s(span: dict) -> float:
    """Seconds of the span in which its thread was not running."""
    return (span["ms"] - span["cpu_ms"]) / 1e3


def own(spans: list, value) -> dict:
    """{span id: value(span) less value(its children)}: the fields are
    inclusive, as a span's times are."""
    out = {s["id"]: value(s) for s in spans}
    for s in spans:
        if s["parent"] is not None:
            out[s["parent"]] -= value(s)
    return out


def first_root(ctx, name: str):
    """The root span of the ring's oldest ``name`` record."""
    recs = ps.records(ctx, name)
    return ps.root_of(recs[0]) if recs else None


def window_start_ns(ctx):
    stamped = ctx["result"].get("jobs")
    return stamped[0]["start"] * 1e9 if stamped else None


def warmup_roots(ctx):
    """The warm-up job's root spans, by start: the `frame.from_arrays`
    roots and the `train` root that ended before the window's first
    job started. None without a `train` among them."""
    start = window_start_ns(ctx)
    if start is None:
        return None
    roots = [ps.root_of(r) for name in ("frame.from_arrays", "train")
             for r in ps.records(ctx, name) or []]
    roots = sorted((r for r in roots if r["t1_ns"] <= start),
                   key=lambda r: r["t0_ns"])
    if not any(r["name"] == "train" for r in roots):
        return None
    return roots


def compile_watch(ctx) -> dict:
    watch = ctx.get("compile_watch")
    if watch is None:
        from h2o_kubernetes_tpu.runtime.backend import \
            compile_watch_snapshot

        watch = compile_watch_snapshot()
    return watch
