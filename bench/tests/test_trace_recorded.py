"""The reduction and the readers on a small recorded trace: one traced
run of the tiny one-chip cell (20,000 rows, 4 trees of depth 3 a job)
on a TPU v5e, recorded by `record_trace.py` (PR 25). What the run
itself printed then is what the reduction has to give again."""

import json
import os

import pytest

import run
import trace_reduce as tr
from registry import Registry

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
TRACE = os.path.join(HERE, "data", "tiny_train.xplane.pb.gz")
SHAPE = {"rows": 20_000, "features": 28, "trees": 4, "max_depth": 3,
         "channels": 3}


@pytest.fixture(scope="module")
def trace():
    return tr.load(TRACE)


@pytest.fixture(scope="module")
def ctx(trace):
    reg = Registry(REPO)
    said = []
    return {"trace": trace, "window": tr.window(trace),
            "peak": reg.peaks()["TPU v5 lite"], "chips": 1, "shape": SHAPE,
            "result": {"attempted": 4, "failed": 0,
                       "window_s": 0.395693015},
            "memory": {"in_use": 3 * 2 ** 29, "reserved": 2 ** 32},
            "say": said.append,
            "said": said, "reg": reg}


def test_planes_lines_and_spans(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert len(dev.ops) > 1000 and len(dev.modules) > 100
    names = [n for _, _, n in trace.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.job") == 4 == names.count("bench.train")
    lo, hi = tr.window(trace)
    assert (hi - lo) / 1e9 == pytest.approx(0.395693015)


def test_names_are_cut_to_instruction_opcode_shape(trace):
    ops = {tr.opcode(o[2]) for o in trace.devices[0].ops}
    assert "custom-call:tpu_custom_call" in ops and "fusion" in ops
    assert not ops & set(tr.ENCLOSING)
    assert tr.short_name(
        "%fusion.37 = f32[67108864]{0:T(1024)} fusion(f32[16,254]{1,0} "
        "%copy-done, s32[67108864]{0} %b), kind=kCustom"
    ) == "fusion.37 fusion f32[67108864]"
    assert tr.short_name("jit__boost_jit(123)") == "jit__boost_jit(123)"


def test_busy_and_idle_make_the_window(trace):
    lo, hi = tr.window(trace)
    dev = trace.devices[0]
    busy = tr.total(tr.clip(dev.ops, lo, hi))
    idle = sum(e - s for s, e in tr.gaps(dev.ops, lo, hi))
    assert busy + idle == pytest.approx(hi - lo)
    assert busy / 1e9 == pytest.approx(0.2445, rel=1e-3)


def test_module_and_kernel_sums(trace):
    lo, hi = tr.window(trace)
    dev = trace.devices[0]
    mods = tr.clip(dev.modules, lo, hi)
    boost = [m for m in mods if "_boost" in m[2]]
    assert len(boost) == 4                    # one dispatch a job
    assert tr.total(boost) / 1e9 == pytest.approx(0.031135, rel=1e-3)
    kernels = [o for o in tr.inside(tr.clip(dev.ops, lo, hi), boost)
               if tr.opcode(o[2]) == "custom-call:tpu_custom_call"]
    # one kernel call a level: 4 jobs x 4 trees x 3 levels
    assert len(kernels) == 48
    assert tr.total(kernels) / 1e9 == pytest.approx(0.016173, rel=1e-3)


def test_breakdown_names_the_gaps(trace):
    lo, hi = tr.window(trace)
    b = run.breakdown(trace, lo, hi)
    assert len(b["device_ops"]) == 10 and b["device_ops"][0][1] > 0.1
    assert {n for n, _ in b["idle_gaps"]} <= {
        "bench.window", "bench.job", "bench.from_arrays", "bench.train"}
    idle = sum(s for _, s in b["idle_gaps"])
    busy = tr.total(tr.clip(trace.devices[0].ops, lo, hi)) / 1e9
    assert idle + busy == pytest.approx((hi - lo) / 1e9)
    json.dumps(b)


# what the recording run printed for these metrics (chip, PR 25)
PRINTED = {"job_s": 0.09875226649999999, "ingest_s": 0.013310044,
           "boost_device_share": 12.676629206219845,
           "boost_dispatches_per_job": 1.0,
           "hist_kernel_share": 6.613723295838864,
           "hist_kernel_roofline": 0.31889016388106833,
           "train_step_mfu": 0.013034696834274374,
           "device_idle_share": 38.19914561797357}


@pytest.mark.parametrize("name", sorted(PRINTED))
def test_reader_gives_what_the_run_printed(ctx, name):
    assert ctx["reg"].reader(name).read(ctx) == pytest.approx(
        PRINTED[name], rel=1e-3)


def test_memory_readers_give_the_two_peaks_apart(ctx):
    assert ctx["reg"].reader("device_peak_gib").read(ctx) == 1.5
    assert ctx["reg"].reader("device_reserved_gib").read(ctx) == 4.0
    none = dict(ctx, memory={"in_use": 0, "reserved": 0})
    assert ctx["reg"].reader("device_reserved_gib").read(none) is None


def test_one_chip_has_no_all_reduce_to_read(ctx):
    """A reader that finds nothing to read returns nothing."""
    assert ctx["reg"].reader("psum_exposed_share").read(ctx) is None


def test_roofline_says_which_bound(ctx):
    ctx["reg"].reader("hist_kernel_roofline").read(ctx)
    assert any("bound by bytes" in s for s in ctx["said"])
