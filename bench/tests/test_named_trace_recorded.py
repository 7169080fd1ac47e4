"""The readers on a second recorded trace: the tiny one-chip cell again
(20,000 rows, 4 trees of depth 3 a job, three jobs), recorded on a TPU
v5e by `record_trace.py` with the program as PR 26 left it — kernels
named, scopes set, and the program's own spans in the profile as
`h2o.*` host events. The loader drops those (it keeps `bench.`), so the
test reads them from the file itself and hands them to the readers in
the ring's form: the same spans, stamped by the profiler a few
microseconds outside the program's own stamps. What the recording run
printed is what the readers have to give again."""

import gzip
import os

import pytest

import trace_reduce as tr
from registry import Registry
from test_trace_recorded import HERE, REPO, SHAPE

TRACE = os.path.join(HERE, "data", "tiny_train_named.xplane.pb.gz")
KINDS = {"frame.put": "enqueue", "train.bin": "enqueue",
         "train.init_margin": "enqueue", "train.boost": "enqueue",
         "train.dispatch": "enqueue", "train.read_model": "wait",
         "train.metric": "wait"}                     # the rest: host
# what the recording run printed (chip, PR 26)
PRINTED = {"job_s": 0.10317266, "ingest_s": 0.017338528,
           "boost_device_share": 12.67972315637802,
           "boost_dispatches_per_job": 1.0,
           "hist_kernel_share": 6.6137412734617955,
           "hist_kernel_roofline": 0.31889086712455805,
           "device_idle_share": 40.735681734681314,
           "bin_device_s": 0.04461448}
# from the program's spans; the profiler's copies lie ~2 us outside
# each, which 29 `frame.encode` spans of 80 us feel most
PRINTED_SPANS = {"train_s": (0.084636492, 1e-3),
                 "train_host_s": (0.018572288, 1e-2),
                 "ingest_host_s": (0.002387408, 0.1),
                 "device_idle_unattributed_share": (9.459087910812185,
                                                    0.05)}


def program_records(path):
    """The `h2o.*` host events as span records, a record per root."""
    from jax.profiler import ProfileData

    with gzip.open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    events = sorted(
        (e.start_ns, -e.duration_ns, e.name[len("h2o."):])
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for e in line.events
        if e.name.startswith("h2o."))
    records, open_ = {}, []             # open_: (end, id) innermost last
    for start, neg, name in events:
        end = start - neg
        while open_ and open_[-1][0] <= start:
            open_.pop()
        if not open_:
            spans = []
            records.setdefault(name, []).append(
                {"root": name, "trace_id": str(start), "spans": spans})
        spans.append({"name": name, "id": len(spans),
                      "parent": open_[-1][1] if open_ else None,
                      "kind": KINDS.get(name, "host"), "t0_ns": start,
                      "t1_ns": end, "ms": (end - start) / 1e6})
        open_.append((end, spans[-1]["id"]))
    return records


@pytest.fixture(scope="module")
def ctx():
    trace = tr.load(TRACE)
    jobs = [{"start": s / 1e9, "end": e / 1e9}
            for s, e, n in trace.spans if n == "bench.job"]
    said = []
    return {"trace": trace, "window": tr.window(trace), "chips": 1,
            "peak": Registry(REPO).peaks()["TPU v5 lite"], "shape": SHAPE,
            "result": {"attempted": 3, "failed": 0, "jobs": jobs,
                       "window_s": 0.3094701},
            "program_spans": program_records(TRACE),
            "say": said.append, "said": said, "reg": Registry(REPO)}


def test_the_kernel_has_its_name(ctx):
    """`short_name` cuts the named instruction as it cut the unnamed
    one (`kernel_metadata` now holds a line break): the name is the
    kernel's, the opcode still the Mosaic call `hist_kernel_share`
    matches on."""
    ops = ctx["trace"].devices[0].ops
    kernels = [o for o in ops
               if tr.opcode(o[2]) == "custom-call:tpu_custom_call"]
    assert kernels and not [o for o in ops if "closed_call" in o[2]]
    names = {o[2].split(".")[0] for o in kernels}
    assert names == {"hist_fact"}
    lo, hi = ctx["window"]
    boost = [m for m in tr.clip(ctx["trace"].devices[0].modules, lo, hi)
             if "_boost" in m[2]]
    inside = tr.inside(tr.clip(kernels, lo, hi), boost)
    assert len(boost) == 3 and len(inside) == 36    # 3 x 4 trees x 3


def test_the_programs_spans_are_in_the_profile(ctx):
    recs = ctx["program_spans"]
    assert len(recs["train"]) == 3 == len(recs["frame.from_arrays"])
    names = [s["name"] for s in recs["train"][0]["spans"]]
    assert names == ["train", "train.prepare", "train.bin",
                     "train.init_margin", "train.boost", "train.dispatch",
                     "train.read_model", "train.metric", "train.finalize"]
    assert [s["parent"] for s in recs["train"][0]["spans"]] == \
        [None, 0, 0, 0, 0, 4, 0, 0, 0]
    frame = recs["frame.from_arrays"][0]["spans"]
    assert [s["name"] for s in frame[1:]] == \
        ["frame.encode", "frame.put"] * 29


@pytest.mark.parametrize("name", sorted(PRINTED))
def test_reader_gives_what_the_run_printed(ctx, name):
    assert ctx["reg"].reader(name).read(ctx) == pytest.approx(
        PRINTED[name], rel=1e-3)


@pytest.mark.parametrize("name", sorted(PRINTED_SPANS))
def test_span_reader_gives_what_the_run_printed(ctx, name):
    want, rel = PRINTED_SPANS[name]
    assert ctx["reg"].reader(name).read(ctx) == pytest.approx(want,
                                                              rel=rel)


def test_the_jobs_spans_are_said_by_name(ctx):
    ctx["reg"].reader("train_host_s").read(ctx)
    line = next(s for s in ctx["said"] if s.startswith("train spans"))
    assert "train.dispatch enqueue x1" in line
    assert "train.read_model wait x1" in line
