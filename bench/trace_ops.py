"""Device seconds of one traced job by the NAMED SCOPE of its operations.

    python3 bench/trace_ops.py --workload xgb-mslr.train --seed 7 [--out <file>]

`trace_reduce.load` keeps an operation's instruction and drops its
`tf_op` (the JAX name stack: `.../grad_hess/rank_pairs/...`), so the
per-layer readers cannot tell the named scopes inside a boost program
apart (PERF.md section 7). This script keeps it: set-up as `run.py`
makes it (the table from the seed, one warm-up job), then ONE job of
the cell's traffic under the profiler, and for the fullest device the
seconds of its operations by class — a Mosaic kernel by its name, any
other operation by the innermost scope of `SCOPES` in its name stack,
the rest by `other` — inside the boost programs' module events and
outside them. Not part of a benchmark run; needs the cell's chips."""

from __future__ import annotations

import argparse
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

import trace_reduce as tr
from registry import Registry

# innermost first: an operation under grad_hess/rank_pairs is rank_pairs
SCOPES = ("rank_sort", "rank_pairs", "set_order", "set_descend",
          "hist_psum", "level_hist", "sibling", "split_find", "descend",
          "leaves", "grad_hess", "sample", "margin")
NAME_STATS = ("tf_op", "op_name")


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    """(value, next index) of the varint at ``buf[i]``."""
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return val, i


def _fields(buf: bytes):
    """(field number, wire type, value) of one protobuf message: a
    varint as int, a length-delimited field as bytes. The profiler's
    Python reader hands out an event's own stats and not its
    metadata's, where the name stack lives, so the file is read as the
    wire format it is (tsl/profiler/protobuf/xplane.proto)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wt in (1, 5):
            ln = 8 if wt == 1 else 4
            val = buf[i:i + ln]
            i += ln
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, val


def _map_entry(buf: bytes) -> tuple:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def name_stacks(raw: bytes) -> dict:
    """{plane name: {event (instruction) name: its JAX name stack}} of
    a serialized XSpace: per plane, the string stat of every event
    metadata whose stat metadata is named `tf_op` (or another of
    `NAME_STATS`), given as a string or as a reference to one."""
    out = {}
    for num, _, plane in _fields(raw):
        if num != 1:
            continue
        pname, stat_names, metas = "", {}, []
        for f, _, v in _fields(plane):
            if f == 2:
                pname = v.decode()
            elif f == 5:                       # stat_metadata map
                k, m = _map_entry(v)
                stat_names[k] = next(
                    (x.decode(errors="replace")
                     for n_, _, x in _fields(m) if n_ == 2), "")
            elif f == 4:                       # event_metadata map
                metas.append(_map_entry(v)[1])
        wanted = {k for k, nm in stat_names.items() if nm in NAME_STATS}
        stacks = {}
        for m in metas:
            name, stack = "", ""
            for f, _, v in _fields(m):
                if f == 2:
                    name = v.decode(errors="replace")
                elif f == 5:
                    st = dict((n_, x) for n_, _, x in _fields(v))
                    if st.get(1) in wanted:
                        if 5 in st:
                            stack = st[5].decode(errors="replace")
                        elif 7 in st:
                            stack = stat_names.get(st[7], "")
            if name and stack:
                stacks[name] = stack
        out[pname] = stacks
    return out


def op_class(name: str, stack: str) -> str:
    short = tr.short_name(name)
    if tr.opcode(short) == "custom-call:tpu_custom_call":
        return "kernel:" + short.split(" ")[0].lstrip("%").split(".")[0]
    parts = stack.split("/")
    for scope in SCOPES:
        if scope in parts:
            return scope
    return "other"


def by_class(path: str) -> dict:
    """{device: {"boost": {class: seconds}, "outside": {...}}} of the
    trace at ``path`` (`.xplane.pb`, or the same gzipped)."""
    import gzip

    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    stacks = name_stacks(raw)
    out = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not tr.is_device_plane(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines[tr.MODULES_LINE].events) \
            if tr.MODULES_LINE in lines else []
        boost = [m for m in mods if "_boost" in m[2]]
        sums = {"boost": {}, "outside": {}}
        named = 0
        mine = stacks.get(plane.name, {})
        for e in lines[tr.OPS_LINE].events if tr.OPS_LINE in lines else ():
            if tr.opcode(tr.short_name(e.name)) in tr.ENCLOSING:
                continue
            mid = e.start_ns + e.duration_ns / 2
            where = "boost" if any(a <= mid <= b for a, b, _ in boost) \
                else "outside"
            stack = mine.get(e.name, "")
            named += bool(stack)
            cls = op_class(e.name, stack)
            sums[where][cls] = sums[where].get(cls, 0.0) \
                + e.duration_ns / 1e9
        out[plane.name] = {**sums, "events_with_a_name_stack": named,
                           "boost_modules": len(boost)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    reg = Registry(ROOT)
    cell = reg.cell(args.workload)
    config = reg.config(cell["config"])

    import jax

    import h2o_kubernetes_tpu as h2o

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != cell["chips"]:
        raise SystemExit(f"trace_ops: {args.workload} needs "
                         f"{cell['chips']} TPU chip(s)")
    os.environ.setdefault("H2O_TPU_PCACHE_MIN_SECS", "0")
    h2o.init()
    traffic = reg.traffic(cell["kind"]).Traffic(
        cell, config, args.seed, jax.profiler.TraceAnnotation,
        reg.comparison(config["comparison"]))
    traffic.setup()
    log_dir = tempfile.mkdtemp(prefix="trace_ops_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            job = traffic.job(0)
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb"))
        result = {"workload": args.workload, "seed": args.seed,
                  "job_s": job["job_s"], "ok": job["ok"],
                  "devices": by_class(found[0])}
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
