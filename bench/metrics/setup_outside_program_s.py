"""Seconds of set-up under no span of the program: from the process's
start (`telemetry.process_start_ns()`, on the spans' clock) to the
start of the window's first job, less what the program's root spans
cover of it: `import`, `init` and the warm-up job's roots. It is the
caller's: the interpreter's start, its own imports and whatever of jax
it imported and started first, its table, the profiler's start. An
earlier line says the interval's gaps apart by where they lie."""

import _host_spans as hs
import _program_spans as ps
import trace_reduce as tr


def read(ctx):
    start = ctx.get("process_start_ns")
    if start is None:
        try:
            from h2o_kubernetes_tpu.runtime.telemetry import \
                process_start_ns
        except ImportError:
            return None
        start = process_start_ns()
    end, warm = hs.window_start_ns(ctx), hs.warmup_roots(ctx)
    imported, init = hs.first_root(ctx, "import"), hs.first_root(ctx, "init")
    if None in (start, end, imported, init) or not warm:
        return None
    roots = [imported, init] + warm
    covered = tr.total(tr.clip(
        [(r["t0_ns"], r["t1_ns"]) for r in roots], start, end))
    frame = next((r for r in warm if r["name"] == "frame.from_arrays"),
                 warm[0])
    train = next(r for r in warm if r["name"] == "train")
    inside_warm = tr.total(tr.gaps(
        [(r["t0_ns"], r["t1_ns"]) for r in warm],
        frame["t0_ns"], train["t1_ns"]))
    ctx["say"](
        "set-up under no span of the program: before `import` "
        f"{(imported['t0_ns'] - start) / 1e9:.3f}s, between `import` "
        f"and `init` {(init['t0_ns'] - imported['t1_ns']) / 1e9:.3f}s, "
        "between `init` and the warm-up job's first root "
        f"{(frame['t0_ns'] - init['t1_ns']) / 1e9:.3f}s, between the "
        f"warm-up job's roots {inside_warm / 1e9:.3f}s, from its end to "
        "the window's first job "
        f"{(end - train['t1_ns']) / 1e9:.3f}s; the warm-up job's roots "
        f"cover {sum(ps.seconds(r) for r in warm):.3f}s")
    return (end - start - covered) / 1e9
