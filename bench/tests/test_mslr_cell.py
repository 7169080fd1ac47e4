"""The cell of ISSUE 34, `xgb-mslr.train`, rehearsed off the chip as
`bench/run.py` runs it (`run_cell`, at a tiny size, one virtual device),
its files held to `BENCHMARK.json`, and its traffic kind held to the
keys `train_jobs` gives every reader."""

import contextlib
import json

import jax
import pytest

import rehearse
import run
from registry import Registry

CELL = "xgb-mslr.train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy, with the cell's `min_child_weight` brought down
    with its rows (the hessians of 20,000 rows sum to a hundredth of
    the cell's) and its limits widened tenfold."""
    root = rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny_mslr")))
    path = f"{root}/bench/configs/xgb-mslr.json"
    with open(path) as f:
        cfg = json.load(f)
    cfg["params"]["min_child_weight"] = 1.0
    with open(path, "w") as f:
        json.dump(cfg, f)
    # the limits are the cell's at its own size: the nodes of a
    # 20,000-row table are a hundredth as large and their float32 sums
    # read that much rougher against the reference's float64
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
    assert cell["kind"] == "train_jobs_rank" and entry["chips"] == 1
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    cfg = reg.config(cell["config"])
    assert cfg["comparison"] == "xgb_rank" and cfg["estimator"] == "XGBoost"
    p = cfg["params"]
    assert p["objective"] == "rank:ndcg" and p["max_depth"] == 8
    assert p["nbins"] == 256 and p["eta"] == 0.1
    assert p["reg_lambda"] == 1.0 and p["min_child_weight"] == 100.0
    assert cell["check_trees"] == cfg["ntrees"]
    assert cell["limits"]["cover_gap"] == 0
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {"ntrees"}
    # the published split, uncut
    assert cfg["rows_per_chip"] == cfg["published"]["rows_per_chip"] \
        == 2_270_296
    assert cfg["published"]["queries_per_chip"] == 18_919
    table = reg.traffic(cell["kind"]).table_module(cfg["table"])
    assert cfg["features"] == table.N_FEATURES == 136
    b = reg.benchmark
    # (found by name, not by place: a later PR adds after them)
    listed_cfg = next(c for c in b["configs"] if c["name"] == "xgb-mslr")
    assert listed_cfg["source"] == cfg["source"]
    assert listed_cfg["reduced"] == cfg["reduced"]
    assert listed_cfg["file"] == "bench/configs/xgb-mslr.json"
    assert len(cfg["source"]) <= 200
    listed = [m["name"] for m in b["per_layer"]
              if CELL in m.get("workloads", [])]
    assert listed == ["boost_rest_s", "pair_fill_share", "rank_layout_s"]
    for name in listed:
        assert callable(reg.reader(name).read)
    assert len(json.dumps(b)) < 64 * 1024


def test_both_traffic_kinds_give_the_readers_the_same_keys(root):
    """`shape()`, a job and the window's result: every reader and the
    harness depend on their keys."""
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
                # the queries in the published ratio to the rows
                assert traffic.queries == 20_000 * 18_919 // 2_270_296
                assert res["failed"] == 0
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
        line = run.run_cell(reg, CELL, 2 ** 31 + 34, 0.5, False, devs)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    assert set(line["compared"]) == set(reg.cell(CELL)["limits"])
    json.dumps(line)


def test_pair_fill_share_reads_the_programs_counter():
    reg = Registry(rehearse.REPO)
    share = reg.reader("pair_fill_share").read({})
    # the rehearsal above trained in this process: size classes keep
    # the slots within a few times the pairs that exist
    assert share is not None and 25.0 < share <= 100.0


def test_rank_layout_s_reads_the_programs_span():
    """The `train.group_layout` span of each job's `train` record,
    from records a test supplies (`_program_spans`); nothing from a
    program without the span."""
    reg = Registry(rehearse.REPO)
    reader = reg.reader("rank_layout_s")

    def span(name, t0, t1, parent=0, sid=1):
        return {"name": name, "id": sid, "parent": parent, "kind": "host",
                "t0_ns": t0, "t1_ns": t1}

    def ctx(with_layout):
        jobs = [{"start": 1.0, "end": 2.0}]
        rec = {"spans": [span("train", 1.1e9, 1.9e9, None, 0)] + (
            [span("train.group_layout", 1.2e9, 1.25e9)]
            if with_layout else [])}
        import trace_reduce as tr

        trace = tr.Trace(devices=[], spans=[
            (0.0, 3e9, "bench.window"), (1e9, 2e9, "bench.job"),
            (1.05e9, 1.95e9, "bench.train")])
        return {"trace": trace, "window": (0.0, 3e9), "say": print,
                "result": {"jobs": jobs},
                "program_spans": {"train": [rec], "frame.from_arrays": []}}

    assert reader.read(ctx(True)) == pytest.approx(0.05)
    assert reader.read(ctx(False)) is None


def test_kept_model_survives_readings_npz(tmp_path):
    import numpy as np

    import readings_rank
    from reference import lambdamart_plain

    reg = Registry(rehearse.REPO)
    table = reg.traffic("train_jobs_rank").table_module("mslr_like")
    X, y, qid = table.mslr_like(3000, 25, 3)
    params = dict(reg.config("xgb-mslr")["params"], max_depth=3,
                  min_child_weight=0.5)
    model = lambdamart_plain.train(np.ascontiguousarray(X.T), y, qid,
                                   params, 2)
    path = str(tmp_path / "m.npz")
    readings_rank.save(path, model)
    back = readings_rank.load(path)
    assert back["train_ndcg@10"] == model["train_ndcg@10"]
    assert len(back["trees"]) == 2
    for a, b in zip(model["trees"], back["trees"]):
        assert set(a) == set(b)
        for k in a:
            assert a[k].dtype == b[k].dtype and (a[k] == b[k]).all()
