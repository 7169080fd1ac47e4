"""Seconds of CPU that the thread calling `train()` used: per job the
`train` root's `cpu_ms` (the thread's CPU clock at the span's two
ends), the median over the window's jobs. `train_host_s` less this is
the time the runtime held the host in a dispatch without its
computing: a queue, not work. On earlier lines: per span name the
seconds its thread was off the CPU (`ms - cpu_ms`, each span's own
part), and every span that stands out — a `host` span off the CPU for
over a quarter of a second of its own, collector pauses over 50 ms,
over 50 involuntary switches — with its faults."""

import statistics

import _host_spans as hs
import _program_spans as ps

OFF_CPU_S, GC_MS, SWITCHED = 0.25, 50.0, 50


def stands_out(span: dict, own_off: float) -> bool:
    return (span["kind"] == "host" and own_off > OFF_CPU_S) \
        or span.get("gc_ms", 0) > GC_MS \
        or span.get("switched", 0) > SWITCHED


def read(ctx):
    jobs = ps.jobs(ctx)
    if not jobs or not all("cpu_ms" in s for j in jobs
                           for s in j["train"]):
        return None
    for j in jobs:
        off = hs.own(j["train"], hs.off_cpu_s)
        rows: dict[str, list] = {}
        for s in j["train"]:
            row = rows.setdefault(s["name"], [s["kind"], 0, 0.0])
            row[1] += 1
            row[2] += off[s["id"]]
        ctx["say"]("off the CPU in a job's train spans (own part): "
                   + ", ".join(f"{name} {kind} x{n} {secs:.4f}s"
                               for name, (kind, n, secs) in rows.items()))
        for s in j["train"]:
            if stands_out(s, off[s["id"]]):
                ctx["say"](
                    f"stands out: {s['name']} {s['kind']} "
                    f"{s['ms'] / 1e3:.4f}s, cpu {s['cpu_ms'] / 1e3:.4f}s "
                    f"(system {s['sys_ms'] / 1e3:.4f}s), own off-CPU "
                    f"{off[s['id']]:.4f}s, gc {s.get('gc_ms', 0):.1f}ms, "
                    f"switched {s.get('switched', 0)}, faults "
                    f"{s.get('faults', 0)}")
    return statistics.median(j["train"][0]["cpu_ms"] for j in jobs) / 1e3
