"""The benchmark's entry: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process owns the cell's chips. It fails, printing no result, unless
JAX finds a TPU with exactly the cell's number of chips and the chip is
in `peaks.json`. Set-up (everything from process start to the window:
imports, `h2o.init()`, the table from the seed, one whole warm-up job
at the cell's shapes) is `setup_s`; then the traffic kind's window;
then peak memory, the comparison that decides `correct`, and one last
line of JSON on standard output. Everything else worth reading goes on
earlier lines. With `--trace 1` the window runs under the profiler and
the line carries the per-layer metrics, read by one file each under
`metrics/`, in place of the end-to-end ones.

`main()` is the only place that looks at the platform; `run_cell` is
what the tests rehearse on the CPU.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, as near as Python gives

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import trace_reduce
from registry import Registry


def verdict(numbers: dict[str, float], limits: dict[str, float]
            ) -> tuple[bool, dict]:
    """(correct, {name: [number, limit]}): every limit has its number,
    every number is finite and within its limit."""
    compared = {k: [float(numbers.get(k, float("nan"))), float(lim)]
                for k, lim in limits.items()}
    ok = all(v == v and abs(v) != float("inf") and v <= lim
             for v, lim in compared.values())
    return bool(ok), compared


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


@contextlib.contextmanager
def profiled(on: bool):
    """Trace what runs inside into a directory of its own under TMPDIR;
    yields a dict that holds the `.xplane.pb` path once the block has
    ended. Host spans only (no Python call tracing: it slows the host)."""
    out: dict = {}
    if not on:
        yield out
        return
    import jax

    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError("the profiler wrote no trace")
        out["trace"] = trace_reduce.load(found[0])
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def breakdown(trace, lo: float, hi: float) -> dict:
    """The fullest device's longest operations, and its idle time by the
    benchmark span the host was in."""
    dev = trace_reduce.fullest(trace, lo, hi)
    ops = trace_reduce.sum_by_name(trace_reduce.clip(dev.ops, lo, hi))
    idle: dict[str, float] = {}
    for s, e in trace_reduce.gaps(dev.ops, lo, hi):
        # the window span holds everything: what is left for it is the
        # time between jobs
        name = trace_reduce.span_at(trace.spans, (s + e) / 2)
        idle[name] = idle.get(name, 0.0) + (e - s)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def run_cell(reg: Registry, workload: str, seed: int, seconds: float,
             trace: bool, devices, t0: float = T0) -> dict:
    """Everything of a run after the look for a chip; returns the
    result line as a dict."""
    import jax

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.runtime.backend import (compile_watch_snapshot,
                                                    start_compile_watch)

    cell = reg.cell(workload)
    config = reg.config(cell["config"])
    peak = reg.peaks().get(devices[0].device_kind)
    if peak is None:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{devices[0].device_kind!r} in peaks.json")
    # sub-second programs are kept in the persistent cache too, so that
    # only a checkout's first run compiles them. The program's own knob:
    # `h2o.init()` applies it wherever the cache lives (jax is imported
    # by now and no longer reads its own variable); an operator's value
    # stands
    os.environ.setdefault("H2O_TPU_PCACHE_MIN_SECS", "0")
    h2o.init()
    start_compile_watch()
    say(f"jax {jax.__version__} device_kind={devices[0].device_kind!r} "
        f"count={len(devices)} compile_cache="
        f"{jax.config.jax_compilation_cache_dir}")
    traffic = reg.traffic(cell["kind"]).Traffic(
        cell, config, seed, jax.profiler.TraceAnnotation,
        reg.comparison(config["comparison"]))
    traffic.setup()
    before = compile_watch_snapshot()
    say(f"set-up compiles {before['compiles']} ({before['compile_s']:.1f}s),"
        f" persistent cache hits {before['pcache_hits']} misses "
        f"{before['pcache_misses']}")
    with profiled(trace) as prof:
        setup_s = time.perf_counter() - t0
        res = traffic.window(seconds)
    after = compile_watch_snapshot()
    in_window = after["compiles"] - before["compiles"]
    # the fullest device. A loaded program's temporaries are reserved
    # apart from the buffers in use, and the reservation stands from the
    # program's first run on (`bytes_reserved` still reads its peak
    # after the window), so the buffers' peak comes on top of it
    full = max((d.memory_stats() or {} for d in devices),
               key=lambda s: int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)))
    memory = {"in_use": int(full.get("peak_bytes_in_use", 0)),
              "reserved": int(full.get("peak_bytes_reserved", 0))}
    peak_bytes = memory["in_use"] + memory["reserved"]
    say(f"memory stats of the fullest device: {full}")
    say(f"memory_peak_bytes {peak_bytes} = peak_bytes_in_use "
        f"{memory['in_use']} + peak_bytes_reserved {memory['reserved']}; "
        "the reservation stands at its peak after the window: "
        f"{full.get('bytes_reserved') == full.get('peak_bytes_reserved')}")
    for j in res["jobs"]:
        say(f"job {j['job_s']:.3f}s (ingest {j['ingest_s']:.3f}s) "
            f"ok={j['ok']}")
    say(f"window {res['window_s']:.3f}s, {res['attempted']} attempted, "
        f"compiles inside it {in_window}")
    if in_window:
        # a run that compiled in its window measured the compiler
        res["failed"] = res["attempted"]
    traffic.release()
    tc = time.perf_counter()
    numbers = traffic.compare()
    say(f"comparison took {time.perf_counter() - tc:.1f}s: {numbers}")
    correct, compared = verdict(numbers, cell["limits"])
    correct = correct and res["failed"] == 0 and res["attempted"] > 0

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    metrics: dict = {}
    line: dict = {"correct": correct, "attempted": res["attempted"],
                  "failed": res["failed"], "metrics": metrics,
                  "device": device}
    if not trace:
        values = dict(res["end_to_end"], setup_s=setup_s)
        for m in reg.metrics("end_to_end", workload):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        tr = prof["trace"]
        lo, hi = trace_reduce.window(tr)
        busy = [trace_reduce.total(trace_reduce.clip(d.ops, lo, hi))
                for d in tr.devices]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ctx = {"trace": tr, "window": (lo, hi), "peak": peak,
               "chips": len(devices), "shape": traffic.shape(),
               "result": res, "memory": memory, "say": say}
        for m in reg.metrics("per_layer", workload):
            value = reg.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["breakdown"] = breakdown(tr, lo, hi)
    line["compared"] = compared
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    reg = Registry(ROOT)
    chips = reg.entry(args.workload)["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != chips:
        raise SystemExit(
            f"bench: {args.workload} needs {chips} TPU chip(s), but JAX "
            f"found platform '{devices[0].platform}' with {len(devices)} "
            "device(s) — no result")
    line = run_cell(reg, args.workload, args.seed, args.seconds,
                    bool(args.trace), devices)
    for name, (value, limit) in line["compared"].items():
        print(f"[bench] compared {name} {value!r} limit {limit!r}",
              file=sys.stderr, flush=True)
    print(f"[bench] correct {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
