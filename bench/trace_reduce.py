"""From a profiler trace (`.xplane.pb`) to what the per-layer readers
read: per device the operation and module intervals, their union (busy
time), the gaps between them, and the benchmark's own host spans on the
same clock. `python bench/trace_reduce.py <file>` describes a trace for
a look by hand.

Device planes are those named `/device:TPU:<n>`; of their lines "XLA
Ops" holds one event per executed operation (Mosaic kernels among them)
and "XLA Modules" one per executed program. Host spans are the
`jax.profiler.TraceAnnotation`s whose names start with `bench.`.
"""

from __future__ import annotations

import gzip
import re
import sys
from dataclasses import dataclass, field

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
# operations that only enclose other operations of the ops line
ENCLOSING = ("while", "conditional", "call")
# an event of the ops line is named by its whole HLO instruction:
# "%fusion.37 = f32[67108864]{0:T(1024)} fusion(f32[16,254]{...} %a, ...)"
_HLO = re.compile(r"^%?(?P<instr>\S+) = (?P<shape>\(?[a-z0-9]+\[[^\]]*\])?"
                  r".*?\s(?P<opcode>[a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)        # (start, end, name)
    async_ops: list = field(default_factory=list)  # (start, end, name)
    modules: list = field(default_factory=list)    # (start, end, name)


@dataclass
class Trace:
    devices: list          # [Device], sorted by name
    spans: list            # [(start, end, name)] host spans, by start


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def short_name(name: str) -> str:
    """"<instruction> <opcode> <result shape>" of an HLO instruction's
    text, a custom call's opcode with its target
    ("custom-call:tpu_custom_call" is a Mosaic kernel); other names as
    they are."""
    m = _HLO.match(name)
    if not m:
        return name
    op = m["opcode"]
    target = _TARGET.search(name) if op == "custom-call" else None
    if target:
        op = f"{op}:{target[1]}"
    return f"{m['instr']} {op} {m['shape'] or ''}".strip()


def opcode(short: str) -> str:
    parts = short.split(" ")
    return parts[1] if len(parts) > 1 else ""


def _read(path: str):
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def _events(line) -> list:
    return sorted((e.start_ns, e.start_ns + e.duration_ns,
                   short_name(e.name)) for e in line.events)


def load(path: str) -> Trace:
    devices, spans = [], []
    for plane in _read(path).planes:
        if is_device_plane(plane.name):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.ops = [e for e in _events(line)
                               if opcode(e[2]) not in ENCLOSING]
                elif line.name == ASYNC_LINE:
                    dev.async_ops = _events(line)
                elif line.name == MODULES_LINE:
                    dev.modules = _events(line)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    devices.sort(key=lambda d: d.name)
    spans.sort()
    return Trace(devices, spans)


def clip(intervals, lo: float, hi: float) -> list:
    """The parts of (start, end, ...) intervals inside [lo, hi]."""
    out = []
    for iv in intervals:
        s, e = max(iv[0], lo), min(iv[1], hi)
        if e > s:
            out.append((s, e) + tuple(iv[2:]))
    return out


def union(intervals) -> list:
    """Merged (start, end) of possibly overlapping intervals."""
    out: list = []
    for s, e in sorted((iv[0], iv[1]) for iv in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals) -> float:
    """Nanoseconds covered by the union of ``intervals``."""
    return float(sum(e - s for s, e in union(intervals)))


def gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) stretches of [lo, hi] that no interval covers."""
    out, at = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def sum_by_name(intervals) -> dict[str, float]:
    out: dict[str, float] = {}
    for s, e, name in intervals:
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def inside(intervals, outer) -> list:
    """Intervals that start within one of the ``outer`` intervals."""
    outer = union(outer)
    out, k = [], 0
    for iv in sorted(intervals):
        while k < len(outer) and outer[k][1] <= iv[0]:
            k += 1
        if k < len(outer) and outer[k][0] <= iv[0]:
            out.append(iv)
    return out


def exposed(intervals, others) -> float:
    """Nanoseconds of ``intervals`` during which none of ``others``
    runs."""
    cover = union(others)
    left = 0.0
    for s, e in union(intervals):
        left += e - s
        left -= sum(min(e, ce) - max(s, cs) for cs, ce in cover
                    if ce > s and cs < e)
    return left


def span_at(spans, t: float) -> str:
    """Name of the innermost benchmark span that holds time ``t``, or
    "outside"."""
    best, width = "outside", None
    for s, e, name in spans:
        if s <= t < e and (width is None or e - s < width):
            best, width = name, e - s
    return best


def fullest(trace: Trace, lo: float, hi: float) -> Device:
    """The device with the most busy time in [lo, hi]."""
    return max(trace.devices, key=lambda d: total(clip(d.ops, lo, hi)))


def window(trace: Trace, name: str = "bench.window"):
    """(start, end) of the span that marks the traced window."""
    for s, e, n in trace.spans:
        if n == name:
            return s, e
    raise ValueError(f"the trace holds no {name!r} span")


def describe(path: str, top: int = 25) -> str:
    """Planes, lines, event counts and the longest events' names."""
    rows = []
    for plane in _read(path).planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            rows.append(f"  LINE {line.name!r}: {len(evs)} events")
            if not (is_device_plane(plane.name)
                    or any(e.name.startswith(SPAN_PREFIX) for e in evs)):
                continue
            agg: dict[str, list] = {}
            for e in evs:
                a = agg.setdefault(e.name, [0, 0.0, e])
                a[0] += 1
                a[1] += e.duration_ns
            for name, (n, ns, e) in sorted(
                    agg.items(), key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"    {ns / 1e9:10.4f}s x{n:<6} {name[:100]}")
                rows.append(f"        first: start {e.start_ns} dur "
                            f"{e.duration_ns} stats "
                            f"{ {k: str(v)[:60] for k, v in e.stats} }")
    return "\n".join(rows)


if __name__ == "__main__":
    print(describe(sys.argv[1]))
