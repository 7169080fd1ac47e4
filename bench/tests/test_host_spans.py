"""The seven readers of the host's side of a span (PR 36:
`metrics/_host_spans.py` and the metrics named in `SEVEN`), on the
recorded trace with span records a test supplies, on records that lack
the fields (an older program: nothing read, nothing raised), and end
to end in a fresh process on the CPU, where the program's ring, its
compile watch and the profiler's host spans are real and only the
device is missing."""

import json
import os
import subprocess
import sys

import pytest

from registry import Registry
from test_program_spans import ctx, read, trace  # noqa: F401 — fixtures
from test_trace_recorded import REPO

SEVEN = ["train_host_cpu_s", "ingest_host_cpu_s", "window_traces_per_job",
         "setup_import_s", "setup_init_s", "setup_pipeline_s",
         "setup_outside_program_s"]
HERE = os.path.dirname(os.path.abspath(__file__))

# a `train` record's spans in the order `train_record` lists them:
# (cpu_ms, sys_ms) of root, prepare, bin, boost, two dispatches, two
# waits, finalize
TRAIN_CPU = [(5.0, 0.5), (1.0, 0.1), (0.2, 0.0), (0.8, 0.0), (0.3, 0.0),
             (0.3, 0.0), (0.1, 0.0), (0.1, 0.0), (1.0, 0.2)]


def root_span(name, t0, t1, **attrs):
    return {"root": name, "spans": [dict(
        attrs, id=0, parent=None, name=name, kind="host", t0_ns=int(t0),
        t1_ns=int(t1), ms=(t1 - t0) / 1e6)]}


@pytest.fixture()
def host(ctx):
    """The recorded run's records with the host's fields as a program
    of PR 36 leaves them, and the records of its set-up: the process
    started 22 s before the window, imported the package from 20 to 15
    s before it, ran `init` from 14 to 13, and its warm-up job (0.95 to
    0.1 s before) paid 0.66 s in the pipeline."""
    spans = ctx["program_spans"]
    for rec in spans["train"]:
        for s, (cpu, sys_) in zip(rec["spans"], TRAIN_CPU):
            s.update(cpu_ms=cpu, sys_ms=sys_)
    for rec in spans["frame.from_arrays"]:
        rec["spans"][0].update(cpu_ms=0.6, sys_ms=0.1)
        for s in rec["spans"][1:]:
            s.update(cpu_ms=0.0, sys_ms=0.0)
        for c, s in enumerate(x for x in rec["spans"]
                              if x["name"] == "frame.encode"):
            s.update(cpu_ms=0.25, sys_ms=0.05, faults=20, column=f"x{c}",
                     path="as_is", dtype="float32")
    warm_train, warm_frame = spans["train"][0], spans["frame.from_arrays"][0]
    warm_train["spans"][0].update(trace_ms=100.0, lower_ms=200.0,
                                  compile_ms=300.0, cache_load_ms=250.0,
                                  traces=3, programs=["_boost_jit"])
    warm_frame["spans"][0].update(trace_ms=10.0, lower_ms=20.0,
                                  compile_ms=30.0, traces=1)
    first = ctx["result"]["jobs"][0]["start"] * 1e9
    spans["import"] = [root_span("import", first - 20e9, first - 15e9,
                                 modules=1000, cpu_ms=4000.0)]
    spans["init"] = [root_span("init", first - 14e9, first - 13e9,
                               cpu_ms=40.0, sys_ms=8.0)]
    ctx["process_start_ns"] = first - 22e9
    ctx["compile_watch"] = {
        "compiles": 39, "compile_s": 0.4, "traces": 4, "trace_s": 0.12,
        "lower_s": 0.23, "cache_load_s": 0.25, "by_program": {
            "_boost_jit": {"traces": 1, "trace_s": 0.1, "lower_s": 0.2,
                           "compiles": 1, "compile_s": 0.3,
                           "cache_load_s": 0.25}}}
    return ctx


def test_the_hand_made_fields_give_the_hand_made_answers(host):
    assert read(host, "train_host_cpu_s") == pytest.approx(0.005)
    assert read(host, "ingest_host_cpu_s") == pytest.approx(0.0005)
    assert read(host, "window_traces_per_job") == 0
    assert read(host, "setup_import_s") == pytest.approx(5.0)
    assert read(host, "setup_init_s") == pytest.approx(1.0)
    assert read(host, "setup_pipeline_s") == pytest.approx(0.66)
    # 22 s less import 5, init 1, the warm-up's frame 0.04 and train 0.8
    assert read(host, "setup_outside_program_s") == pytest.approx(15.16)
    said = "\n".join(host["said"])
    assert "before `import` 2.000s, between `import` and `init` 1.000s" \
        in said
    assert "first root 12.050s" in said and "first job 0.100s" in said
    assert "trace 0.110s, lower 0.220s, backend compile 0.330s (of it " \
        "cache loads 0.250s), 4 programs traced" in said
    assert "most of it: _boost_jit 0.600s" in said
    assert "x1 as_is float32" in said and "faults 40" in said
    assert "import: 5.000s, 1000 modules, cpu 4.000s" in said


def test_off_cpu_by_name_and_what_stands_out(host):
    """A dispatch that blocks is time off the CPU under its own name,
    not under its parents'; a `host` span a quarter of a second off
    the CPU stands out, with its faults."""
    job = host["program_spans"]["train"][2]["spans"]
    job[8].update(ms=401.0, cpu_ms=1.0, faults=7, switched=3)  # finalize
    job[0]["ms"] += 400.0       # (times on the clock stay as recorded)
    read(host, "train_host_cpu_s")
    by_name = [s for s in host["said"] if s.startswith("off the CPU")]
    assert len(by_name) == 4
    assert "train.finalize host x1 0.4000s" in by_name[1]
    stood = [s for s in host["said"] if s.startswith("stands out")]
    assert len(stood) == 1 and "train.finalize host" in stood[0]
    assert "switched 3, faults 7" in stood[0]


def test_a_trace_inside_the_window_is_named(host):
    job = host["program_spans"]["train"][3]["spans"][0]
    job.update(traces=2, trace_ms=12.5, programs=["_boost_jit", "_pad_jit"])
    assert read(host, "window_traces_per_job") == pytest.approx(2 / 4)
    assert any("traced inside the window under train" in s
               and "_pad_jit" in s for s in host["said"])


def test_records_without_the_fields_give_no_metric(ctx):
    """The parent's program: the spans and their times, none of the
    fields, no `import` or `init` record."""
    for name in SEVEN:
        assert read(ctx, name) is None, name


def test_the_new_entries_name_files_and_metrics_that_exist():
    reg = Registry(REPO)
    entries = reg.benchmark["per_layer"][-len(SEVEN):]
    assert [m["name"] for m in entries] == SEVEN
    end_to_end = {m["name"] for m in reg.benchmark["end_to_end"]}
    for m in entries:
        assert "workloads" not in m and m["moves"] in end_to_end
        assert callable(reg.reader(m["name"]).read)
    # every training cell reports them
    for w in reg.benchmark["workloads"]:
        assert set(SEVEN) <= {m["name"]
                              for m in reg.metrics("per_layer", w["name"])}


REHEARSAL = """
import json, sys
sys.path[:0] = {paths!r}
import jax
import rehearse, run
import trace_reduce as tr
from registry import Registry

reg = Registry(rehearse.tiny_root({tmp!r}))
devs = jax.devices()[:1]           # as bench/run.py::main: before import
import h2o_kubernetes_tpu as h2o
cell = reg.cell("gbm-higgs.train")
config = reg.config(cell["config"])
with h2o.use_mesh(h2o.make_mesh(devices=devs)):
    h2o.init()
    traffic = reg.traffic(cell["kind"]).Traffic(
        cell, config, 2 ** 31 + 36, jax.profiler.TraceAnnotation,
        reg.comparison(config["comparison"]))
    traffic.setup()
    with run.profiled(True) as prof:
        res = traffic.window(0.3)
said = []
ctx = {{"trace": prof["trace"], "window": tr.window(prof["trace"]),
       "result": res, "chips": 1, "shape": traffic.shape(),
       "say": said.append}}
got = {{name: reg.reader(name).read(ctx) for name in {names!r}}}
print(json.dumps({{"got": got, "said": said,
                  "job_s": [j["job_s"] for j in res["jobs"]]}}))
"""


def test_cpu_rehearsal_finds_the_seven(tmp_path):
    """A fresh process, as `bench/run.py` is one: the devices asked
    for first, then the import, `h2o.init()`, the table, the warm-up
    job and a traced window; every reader reads."""
    code = REHEARSAL.format(
        paths=[REPO, os.path.dirname(HERE), HERE], tmp=str(tmp_path),
        names=SEVEN + ["train_s", "ingest_host_s", "setup_train_s"])
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=dict(os.environ))
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    got, said = out["got"], "\n".join(out["said"])
    assert all(got[name] is not None for name in SEVEN), (got, said)
    assert "not read" not in said, said
    # (on the CPU backend the thread computes while it waits, so only
    # the root's wall time bounds its CPU)
    assert 0 < got["train_host_cpu_s"] <= got["train_s"] * 1.05
    assert 0 < got["ingest_host_cpu_s"] <= got["ingest_host_s"] * 1.05
    assert got["window_traces_per_job"] == 0, said
    assert got["setup_import_s"] > 0.1 and got["setup_init_s"] > 0
    assert 0 < got["setup_pipeline_s"] < got["setup_train_s"] + 1.0
    assert got["setup_outside_program_s"] > 0
    assert "before `import`" in said and "y factorize" in said
