"""The cell of ISSUE 38, `xgb-covtype.train`, rehearsed off the chip as
`bench/run.py` runs it (`run_cell`, at a tiny size, one virtual device),
its files held to `BENCHMARK.json` BY NAME, its traffic kind held to
the keys `train_jobs` gives every reader, and its three readers on a
synthetic traced window."""

import contextlib
import json

import jax
import pytest

import rehearse
import run
import trace_reduce as tr
from registry import Registry

CELL = "xgb-covtype.train"
MS = 1e6
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
SHAPE = {"rows": 581_012, "features": 54, "trees": 70, "max_depth": 6,
         "channels": 3}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy, its limits widened tenfold: the nodes of a
    20,000-row table are a thirtieth as large as the cell's and their
    float32 sums read that much rougher against the reference's
    float64."""
    root = rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny_cov")))
    path = f"{root}/bench/workloads/{CELL}.json"
    with open(path) as f:
        cell = json.load(f)
    cell["limits"] = {k: v * 10 for k, v in cell["limits"].items()}
    with open(path, "w") as f:
        json.dump(cell, f)
    return root


def test_cell_file_entry_and_configuration_agree():
    reg = Registry(rehearse.REPO)
    cell, entry = reg.cell(CELL), reg.entry(CELL)
    assert cell["kind"] == "train_jobs_multi" and entry["chips"] == 1
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    cfg = reg.config(cell["config"])
    assert cfg["comparison"] == "gbm_softmax"
    assert cfg["estimator"] == "XGBoost"
    p = cfg["params"]
    assert p["objective"] == "multi:softprob" and p["max_depth"] == 6
    assert p["nbins"] == 256 and p["eta"] == 0.3
    assert p["reg_lambda"] == 1.0 and p["min_child_weight"] == 1.0
    assert cell["check_rounds"] == cfg["ntrees"]
    assert 10 <= cfg["ntrees"] <= 40 and cell["regret_rounds"] == 2
    assert cell["limits"]["cover_gap"] == 0
    assert set(cell["limits"]) == {"cover_gap", "value_gap", "gain_gap",
                                   "regret_gap", "logloss_gap"}
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {"ntrees"}
    # the published table, uncut
    pub = cfg["published"]
    assert cfg["rows_per_chip"] == pub["rows_per_chip"] == 581_012
    assert cfg["classes"] == pub["classes"] == 7
    assert pub["ntrees"] == 3000
    table = reg.traffic(cell["kind"]).table_module(cfg["table"])
    assert cfg["features"] == pub["features"] == table.N_FEATURES == 54
    assert table.CLASSES == 7
    b = reg.benchmark
    # (found by name, not by place: a later PR adds after them)
    listed_cfg = next(c for c in b["configs"] if c["name"] == "xgb-covtype")
    assert listed_cfg["source"] == cfg["source"]
    assert listed_cfg["reduced"] == cfg["reduced"]
    assert listed_cfg["file"] == "bench/configs/xgb-covtype.json"
    assert len(cfg["source"]) <= 200
    listed = {m["name"]: m for m in b["per_layer"]
              if CELL in m.get("workloads", [])}
    assert set(listed) == {"class_batch_share", "multi_hist_roofline",
                           "multi_rest_s"}
    for name, m in listed.items():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_rowtrees_per_s"
        assert callable(reg.reader(name).read)
    assert len(json.dumps(b)) < 64 * 1024


def test_both_traffic_kinds_give_the_readers_the_same_keys(root):
    """`shape()`, a job and the window's result: every reader and the
    harness depend on their keys. The K-class kind counts a round's K
    class trees in `trees`."""
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    got = {}
    devs = jax.devices()[:1]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        h2o.init()
        for workload in ("gbm-higgs.train", CELL):
            cell = reg.cell(workload)
            config = reg.config(cell["config"])
            spans = []

            @contextlib.contextmanager
            def note(name):
                spans.append(name)
                yield

            traffic = reg.traffic(cell["kind"]).Traffic(
                cell, config, 5, note, reg.comparison(config["comparison"]))
            traffic.load()
            res = traffic.window(0.01)
            got[workload] = (set(traffic.shape()), set(res),
                             set(res["jobs"][0]), set(res["end_to_end"]),
                             sorted(set(spans)))
            if workload == CELL:
                assert traffic.shape()["trees"] == 4 * 7
                assert res["failed"] == 0
                assert res["end_to_end"]["train_rowtrees_per_s"] == \
                    pytest.approx(res["attempted"] * 20_000 * 28
                                  / res["window_s"])
    assert got[CELL] == got["gbm-higgs.train"]
    assert got[CELL][4] == ["bench.from_arrays", "bench.job",
                            "bench.train", "bench.window"]


def test_run_cell_end_to_end(root):
    """Jobs until the window is over, a seed past 2**31, nothing
    compiled inside the window, the comparison within the cell's own
    limits, every limit with its number."""
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    devs = jax.devices()[:1]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        line = run.run_cell(reg, CELL, 2 ** 31 + 38, 0.5, False, devs)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    assert set(line["compared"]) == set(reg.cell(CELL)["limits"])
    json.dumps(line)


def test_class_batch_share_reads_the_programs_counter():
    reg = Registry(rehearse.REPO)
    # the rehearsals above trained K-class jobs in this process, every
    # one through the class batch
    assert reg.reader("class_batch_share").read({}) == 100.0


@pytest.mark.parametrize("what", ["mapped", "bundled", "no_root"])
def test_no_result_where_the_job_is_not_what_the_cell_measures(
        root, monkeypatch, what):
    """A round's classes grown one at a time, another histogram width,
    or a program whose spans do not say: no result under this name."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.runtime import telemetry

    reg = Registry(root)
    cell = reg.cell(CELL)
    config = reg.config(cell["config"])
    if what == "mapped":
        monkeypatch.setattr(core, "_MULTI_HIST_BUDGET", 1)
    elif what == "bundled":
        config = dict(config, features=53)
    else:
        monkeypatch.setattr(telemetry.TRACER, "by_root", lambda name: [])
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1])):
        h2o.init()
        traffic = reg.traffic(cell["kind"]).Traffic(
            cell, config, 7, lambda name: contextlib.nullcontext(),
            reg.comparison(config["comparison"]))
        with pytest.raises(SystemExit, match="no result"):
            traffic.setup()


def _ctx(module="jit__boost_multi_jit(123)", jobs=2, rounds=10):
    """``jobs`` jobs of 12 s: a boost module a round, in it 6 kernel
    calls of 40 ms and 6 other operations of 30 ms."""
    dev = tr.Device("/device:TPU:0")
    spans = []
    at = 1e9
    lo = at
    for _ in range(jobs):
        j0, j1 = at, at + 12_000 * MS
        spans += [(j0, j1, "bench.job"),
                  (j0, j0 + 200 * MS, "bench.from_arrays"),
                  (j0 + 200 * MS, j1 - MS, "bench.train")]
        t = j0 + 300 * MS
        for r in range(rounds):
            m0 = t
            for d in range(6):
                dev.ops.append((t, t + 40 * MS, f"hist_fact.{d} "
                                "custom-call:tpu_custom_call f32[7,1,8]"))
                t += 40 * MS
                dev.ops.append((t, t + 30 * MS,
                                f"fusion.{d} fusion f32[581632,7]"))
                t += 30 * MS
            dev.modules.append((m0, t, module))
            t += 5 * MS
        at = j1
    spans.append((lo, at, "bench.window"))
    return {"trace": tr.Trace(devices=[dev], spans=sorted(spans)),
            "window": (lo, at), "peak": PEAK, "chips": 1, "shape": SHAPE,
            "say": lambda msg: None}


def test_multi_readers_on_a_synthetic_window(monkeypatch):
    reg = Registry(rehearse.REPO)
    roof = reg.reader("multi_hist_roofline")
    rest = reg.reader("multi_rest_s")
    monkeypatch.setattr(roof, "_classes", lambda: 7)
    ctx = _ctx()
    assert rest.read(ctx) == pytest.approx(10 * 6 * 0.030)
    # a level of a round: the codes once, the row state a class
    by = 581_012 * (54 + 16 * 7) / 819e9
    ad = 581_012 * 54 * 3 * 7 / 197e12
    assert roof.round_level_min_seconds(581_012, 54, 3, 7, PEAK) == \
        (by, "bytes") and ad < by
    assert roof.read(ctx) == pytest.approx(100 * by / 0.040)
    # the shared reader counts a level a tree, K times the codes
    shared = reg.reader("hist_kernel_roofline").read(ctx)
    assert shared / roof.read(ctx) == pytest.approx(
        7 * (54 + 16) / (54 + 16 * 7))
    # nothing where the traced jobs ran another boost program, or the
    # program's spans do not say how many classes
    other = _ctx(module="jit__boost_jit(5)")
    assert roof.read(other) is None and rest.read(other) is None
    monkeypatch.setattr(roof, "_classes", lambda: None)
    assert roof.read(ctx) is None


def test_kept_model_survives_readings_npz(tmp_path):
    import numpy as np

    import readings_multi
    from reference import gbm_softmax_plain

    reg = Registry(rehearse.REPO)
    table = reg.traffic("train_jobs_multi").table_module("covtype_like")
    X, y = table.covtype_like(3000, 3)
    params = dict(reg.config("xgb-covtype")["params"], max_depth=3)
    model = gbm_softmax_plain.train(np.ascontiguousarray(X.T), y, params,
                                    2, 7)
    path = str(tmp_path / "m.npz")
    readings_multi.save(path, model)
    back = readings_multi.load(path)
    assert back["train_logloss"] == model["train_logloss"]
    assert back["classes"] == 7 and back["init"] == model["init"].tolist()
    assert [len(r) for r in back["trees"]] == [7, 7]
    for ra, rb in zip(model["trees"], back["trees"]):
        for a, b in zip(ra, rb):
            assert set(a) == set(b)
            for k in a:
                assert a[k].dtype == b[k].dtype and (a[k] == b[k]).all()
