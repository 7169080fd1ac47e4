"""The readers of the program's own spans (`metrics/_program_spans.py`
and the seven metrics that came with it), on the recorded trace with
span records a test supplies, and end to end on the CPU, where the
program's ring and the profiler's host spans are real and only the
device is missing."""

import statistics

import jax
import pytest

import rehearse
import run
import trace_reduce as tr
from registry import Registry
from test_trace_recorded import REPO, SHAPE, TRACE

NEW = ["train_s", "train_host_s", "ingest_host_s", "bin_device_s",
       "device_idle_unattributed_share", "setup_train_s",
       "setup_compile_s"]
# the trace's clock less perf_counter's, as the recording run might
# have had it
OFFSET = 7_000_000_000.0


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


def named(trace, name):
    return [s for s in trace.spans if s[2] == name]


def span(ident, parent, name, kind, t0, t1, **attrs):
    return dict(attrs, id=ident, parent=parent, name=name, kind=kind,
                t0_ns=int(t0), t1_ns=int(t1), ms=(t1 - t0) / 1e6)


def train_record(lo, hi):
    """A `train` tree inside [lo, hi] of the program's clock: 1 ms of
    prepare, bin and two dispatches enqueued in 2 ms, then two waits
    (one nested in the other's interval would be a fault: they lie
    apart) that take all but the last millisecond."""
    ms = 1e6
    w0, w1 = lo + 3 * ms, hi - 1 * ms
    mid = (w0 + w1) / 2
    return {"trace_id": f"t{int(lo)}", "root": "train", "spans": [
        span(0, None, "train", "host", lo, hi, estimator="GBM"),
        span(1, 0, "train.prepare", "host", lo, lo + ms),
        span(2, 0, "train.bin", "enqueue", lo + ms, lo + 2 * ms),
        span(3, 0, "train.boost", "enqueue", lo + 2 * ms, lo + 3 * ms),
        span(4, 3, "train.dispatch", "enqueue", lo + 2 * ms,
             lo + 2.4 * ms, first_tree=0, trees=2),
        span(5, 3, "train.dispatch", "enqueue", lo + 2.5 * ms,
             lo + 2.9 * ms, first_tree=2, trees=2),
        span(6, 0, "train.read_model", "wait", w0, mid),
        span(7, 0, "train.metric", "wait", mid, w1),
        span(8, 0, "train.finalize", "host", w1, hi)]}


def frame_record(lo, hi):
    """`frame.from_arrays` in [lo, hi]: two columns, each encoded for
    0.3 ms and put in the rest of its half."""
    half = (hi - lo) / 2
    spans = [span(0, None, "frame.from_arrays", "host", lo, hi)]
    for c in range(2):
        a = lo + c * half
        spans.append(span(1 + 2 * c, 0, "frame.encode", "host", a,
                          a + 3e5, bytes=80000))
        spans.append(span(2 + 2 * c, 0, "frame.put", "enqueue", a + 3e5,
                          a + half, bytes=80000))
    return {"trace_id": f"f{int(lo)}", "root": "frame.from_arrays",
            "spans": spans}


@pytest.fixture()
def ctx(trace):
    """The recorded run as the program might have seen it: every job
    stamped on the program's clock, a `train` record 20 µs inside each
    `bench.train` span and a frame record inside each
    `bench.from_arrays`, and the warm-up job's records before them."""
    jobs = [{"start": (s - OFFSET) / 1e9, "end": (e - OFFSET) / 1e9}
            for s, e, _ in named(trace, "bench.job")]
    first = jobs[0]["start"] * 1e9
    trains = [train_record(first - 9e8, first - 1e8)] + [
        train_record(s + 2e4 - OFFSET, e - 2e5 - OFFSET)
        for s, e, _ in named(trace, "bench.train")]
    frames = [frame_record(first - 9.5e8, first - 9.1e8)] + [
        frame_record(s + 5e3 - OFFSET, e - 1e5 - OFFSET)
        for s, e, _ in named(trace, "bench.from_arrays")]
    said = []
    return {"trace": trace, "window": tr.window(trace), "chips": 1,
            "shape": SHAPE, "result": {"attempted": 4, "failed": 0,
                                       "jobs": jobs},
            "program_spans": {"train": trains,
                              "frame.from_arrays": frames},
            "compile_watch": {"compile_s": 1.25, "compiles": 39},
            "say": said.append, "said": said, "reg": Registry(REPO)}


def read(ctx, name):
    return ctx["reg"].reader(name).read(ctx)


def test_benchmark_lists_the_seven_after_the_ten(ctx):
    names = [m["name"] for m in ctx["reg"].metrics("per_layer",
                                                   "gbm-higgs.train")]
    assert names[-7:] == NEW and len(names) == 17
    assert all("workloads" not in m
               for m in ctx["reg"].benchmark["per_layer"])


def test_span_seconds_on_the_recorded_jobs(ctx, trace):
    outer = [(e - 2e5) - (s + 2e4) for s, e, _ in named(trace,
                                                        "bench.train")]
    assert read(ctx, "train_s") == pytest.approx(
        statistics.median(outer) / 1e9, rel=1e-9)
    # the two waits cover all but 3 ms at the start and 1 ms at the end
    assert read(ctx, "train_host_s") == pytest.approx(0.004, rel=1e-6)
    assert any("train.dispatch: 2 spans, 0.0008s" in s
               for s in ctx["said"])
    assert read(ctx, "ingest_host_s") == pytest.approx(0.0006, rel=1e-6)
    assert read(ctx, "setup_train_s") == pytest.approx(0.8, rel=1e-9)
    assert read(ctx, "setup_compile_s") == 1.25
    assert read(ctx, "train_s") < read(ctx, "job_s")


def test_offsets_that_disagree_are_not_read(ctx):
    ctx["result"]["jobs"][2]["start"] += 0.002      # 2 ms off the others
    for name in ("train_s", "train_host_s", "ingest_host_s",
                 "device_idle_unattributed_share"):
        assert read(ctx, name) is None
    assert len([s for s in ctx["said"] if "offsets differ" in s]) == 1
    # what needs no common clock is still read
    assert read(ctx, "setup_train_s") == pytest.approx(0.8)
    assert read(ctx, "bin_device_s") > 0


def test_a_train_root_outside_its_span_is_not_read(ctx):
    ctx["program_spans"]["train"][2]["spans"][0]["t1_ns"] += int(1e6)
    assert read(ctx, "train_s") is None
    assert any("outside its bench.train span" in s for s in ctx["said"])


def test_a_program_without_records_gives_no_metric(ctx):
    ctx["program_spans"] = {}
    for name in ("train_s", "train_host_s", "ingest_host_s",
                 "device_idle_unattributed_share", "setup_train_s"):
        assert read(ctx, name) is None
    assert any("no `train` record" in s for s in ctx["said"])


def test_bin_device_seconds_against_the_module_sums(ctx, trace):
    """The recorded jobs bin in one `_fused_fit_bin_jit` dispatch each
    (0.178465 s of module events over the four jobs, as
    `test_module_and_kernel_sums` holds the boost program's); the
    reader finds it through the program's own table."""
    dev = trace.devices[0]
    lo, hi = tr.window(trace)
    mods = [m for m in tr.clip(dev.modules, lo, hi)
            if m[2].startswith("jit__fused_fit_bin_jit(")]
    assert len(mods) == 4
    assert tr.total(mods) / 1e9 == pytest.approx(0.178465, rel=1e-3)
    per_job = [tr.total(tr.inside(tr.clip(dev.ops, lo, hi), [m])) / 1e9
               for m in mods]
    got = read(ctx, "bin_device_s")
    assert got == pytest.approx(statistics.median(per_job), rel=1e-9)
    assert 0.95 * tr.total(mods) / 4e9 < got <= max(
        e - s for s, e, _ in mods) / 1e9
    assert read(dict(ctx, train_programs={"bin": ("_no_such_jit",)}),
                "bin_device_s") is None


def test_idle_no_leaf_span_covers(ctx, trace):
    dev = trace.devices[0]
    lo, hi = tr.window(trace)
    idle = tr.gaps(dev.ops, lo, hi)
    leaves = []
    for rec in ctx["program_spans"]["train"][1:] + \
            ctx["program_spans"]["frame.from_arrays"][1:]:
        parents = {s["parent"] for s in rec["spans"]}
        leaves += [(s["t0_ns"] + OFFSET, s["t1_ns"] + OFFSET)
                   for s in rec["spans"] if s["id"] not in parents]
    want = 100.0 * tr.exposed(idle, leaves) / sum(e - s for s, e in idle)
    got = read(ctx, "device_idle_unattributed_share")
    assert got == pytest.approx(want, rel=1e-9) and 0 < got < 100
    by_leaf, rest = ctx["said"][-2:]
    assert "by leaf span: " in by_leaf and "frame.put" in by_leaf
    # what the leaves leave: the benchmark's own time around the
    # program's roots, and the boost span between its dispatches
    assert "bench.job" in rest or "bench.window" in rest


def test_cpu_rehearsal_finds_the_readers(tmp_path):
    """The traced window as far as the CPU goes: the program's real
    ring, the profiler's real host spans (`bench.*`, and the program's
    own `h2o.*` beside them), no device plane. The clocks agree, so
    the span readers read; the two device readers return nothing."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.runtime.backend import start_compile_watch
    from h2o_kubernetes_tpu.runtime.telemetry import TRACER

    reg = Registry(rehearse.tiny_root(str(tmp_path)))
    cell = reg.cell("gbm-higgs.train")
    config = reg.config(cell["config"])
    devs = jax.devices()[:1]
    TRACER.clear()
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        h2o.init()
        start_compile_watch()
        traffic = reg.traffic(cell["kind"]).Traffic(
            cell, config, 2 ** 31 + 11, jax.profiler.TraceAnnotation,
            reg.comparison(config["comparison"]))
        traffic.setup()
        with run.profiled(True) as prof:
            res = traffic.window(0.3)
    trace = prof["trace"]
    said = []
    ctx = {"trace": trace, "window": tr.window(trace), "result": res,
           "chips": 1, "shape": traffic.shape(), "say": said.append}
    got = {name: reg.reader(name).read(ctx) for name in NEW}
    assert got["bin_device_s"] is None
    assert got["device_idle_unattributed_share"] is None
    assert not [s for s in said if "not read" in s], said
    job_s = statistics.median(j["job_s"] for j in res["jobs"])
    assert 0 < got["train_host_s"] < got["train_s"] < job_s
    assert 0 < got["ingest_host_s"] < statistics.median(
        j["ingest_s"] for j in res["jobs"])
    assert got["setup_train_s"] >= got["train_s"]
    assert got["setup_compile_s"] > 0
