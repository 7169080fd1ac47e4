"""The two readers that came with the forest cell, on a synthetic
traced window: a device whose boost modules (one tree each, or several)
hold calls of both histogram kernels."""

import pytest

import trace_reduce as tr
from registry import Registry
from test_trace_recorded import REPO

MS = 1e6
OFFSET = 5e9                  # the trace's clock less the program's
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
SHAPE = {"rows": 4_194_304, "features": 28, "trees": 6, "max_depth": 12,
         "channels": 2}
BLOCKED_MS, FACT_MS = 400.0, 20.0


def kernel(name, n, start, ms):
    return (start, start + ms * MS,
            f"{name}.{n} custom-call:tpu_custom_call f32[28,2,65536]")


def span(ident, parent, name, kind, t0, t1, **attrs):
    return dict(attrs, id=ident, parent=parent, name=name, kind=kind,
                t0_ns=int(t0), t1_ns=int(t1), ms=(t1 - t0) / 1e6)


def make_ctx(per_dispatch=1, trees=6, jobs=2):
    """``jobs`` jobs of 1 s: per tree one bin-blocked call and eleven
    factorized ones, ``per_dispatch`` trees to a boost module."""
    dispatches = trees // per_dispatch
    dev = tr.Device("/device:TPU:0")
    spans, stamped, trains = [], [], []
    at = 1e9
    lo = at
    for j in range(jobs):
        j0, j1 = at, at + 1000 * MS
        spans += [(j0, j1, "bench.job"),
                  (j0, j0 + 10 * MS, "bench.from_arrays"),
                  (j0 + 10 * MS, j1 - MS, "bench.train")]
        stamped.append({"start": (j0 - OFFSET) / 1e9,
                        "end": (j1 - OFFSET) / 1e9})
        r0, r1 = j0 + 10 * MS + 2e4 - OFFSET, j1 - MS - 2e4 - OFFSET
        rec = [span(0, None, "train", "host", r0, r1),
               span(1, 0, "train.boost", "enqueue", r0 + MS, r0 + 3 * MS)]
        t = j0 + 20 * MS
        for d in range(dispatches):
            rec.append(span(2 + d, 1, "train.dispatch", "enqueue",
                            r0 + MS + d * 1e5, r0 + MS + d * 1e5 + 5e4,
                            first_tree=d * per_dispatch,
                            trees=per_dispatch))
            m0 = t
            for _ in range(per_dispatch):
                for k in range(11):
                    dev.ops.append(kernel("hist_fact", k, t, FACT_MS / 11))
                    t += FACT_MS / 11 * MS
                dev.ops.append(kernel("hist_blocked", 12, t,
                                      BLOCKED_MS / 4))
                t += BLOCKED_MS / 4 * MS
                dev.ops.append((t, t + 5 * MS,
                                "fusion.9 fusion u8[4194304]"))
                t += 5 * MS
            dev.modules.append((m0, t, "jit__boost_drf_jit(123)"))
        rec.append(span(2 + dispatches, 0, "train.read_model", "wait",
                        r0 + 3 * MS, r1 - MS))
        trains.append({"trace_id": f"t{j}", "root": "train", "spans": rec})
        # a kernel call outside any boost module is no reader's
        dev.ops.append(kernel("hist_blocked", 99, j1 - 50 * MS, 1.0))
        at = j1
    spans.append((lo, at, "bench.window"))
    trace = tr.Trace([dev], sorted(spans))
    said = []
    return {"trace": trace, "window": (lo, at), "chips": 1, "peak": PEAK,
            "shape": SHAPE, "result": {"jobs": stamped},
            "program_spans": {"train": trains, "frame.from_arrays": []},
            "say": said.append, "said": said, "reg": Registry(REPO)}


def read(ctx, name):
    return ctx["reg"].reader(name).read(ctx)


def test_share_counts_the_named_kernel_inside_the_boost_modules():
    ctx = make_ctx()
    dev = ctx["trace"].devices[0]
    busy = tr.total(dev.ops)
    blocked = 2 * 6 * BLOCKED_MS / 4 * MS
    assert read(ctx, "hist_blocked_share") == pytest.approx(
        100 * blocked / busy)
    # the two kernels together are what the older reader counts as one
    both = read(ctx, "hist_kernel_share")
    assert both == pytest.approx(
        100 * (blocked + 2 * 6 * FACT_MS * MS) / busy)


@pytest.mark.parametrize("per_dispatch", [1, 2, 6])
def test_roofline_counts_a_level_for_every_call(per_dispatch):
    """One call, one level of one tree, however many trees a dispatch
    holds."""
    import work

    ctx = make_ctx(per_dispatch)
    per_level, bound = work.level_min_seconds(
        SHAPE["rows"], SHAPE["features"], SHAPE["channels"], PEAK)
    assert bound == "bytes"
    want = 100 * per_level / (BLOCKED_MS / 4 / 1e3)
    assert read(ctx, "hist_blocked_roofline") == pytest.approx(want)
    assert 0 < want < 100
    assert any("12 calls" in said for said in ctx["said"])


def test_the_readers_need_no_span_of_the_program():
    """A program whose trace ring is empty (`H2O_TPU_TRACE=0`) or whose
    spans are another PR's: both readers read the device trace alone."""
    ctx = make_ctx()
    ctx["program_spans"] = {"train": [], "frame.from_arrays": []}
    assert read(ctx, "hist_blocked_share") > 0
    assert read(ctx, "hist_blocked_roofline") > 0


def test_no_blocked_call_no_number():
    """What the parent's traced runs of the other cells give: nothing,
    and no exception."""
    ctx = make_ctx()
    dev = ctx["trace"].devices[0]
    dev.ops = [o for o in dev.ops if not o[2].startswith("hist_blocked")]
    assert read(ctx, "hist_blocked_share") is None
    assert read(ctx, "hist_blocked_roofline") is None
