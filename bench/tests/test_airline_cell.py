"""The cell of ISSUE 32, `gbm-airline.train`, rehearsed off the chip as
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

CELL = "gbm-airline.train"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny_airline")))


def test_cell_file_entry_and_configuration_agree():
    reg = Registry(rehearse.REPO)
    cell, entry = reg.cell(CELL), reg.entry(CELL)
    assert cell["kind"] == "train_jobs_enum" and entry["chips"] == 1
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    cfg = reg.config(cell["config"])
    assert cfg["comparison"] == "gbm_sets" and cfg["estimator"] == "GBM"
    assert cfg["params"]["categorical_encoding"] == "enum"
    assert cfg["params"]["nbins"] == 100 and cfg["params"]["max_depth"] == 10
    assert cfg["params"]["nbins_cats"] == 1024
    assert cell["check_trees"] == cfg["ntrees"]
    assert cell["limits"]["cover_gap"] == 0
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {
        "rows_per_chip", "ntrees"}
    table = reg.traffic(cell["kind"]).table_module(cfg["table"])
    assert cfg["features"] == len(table.LEVELS) == 8
    assert cfg["enum_levels"] == {n: lv for n, lv in table.COLUMNS if lv}
    b = reg.benchmark
    assert b["configs"][-1]["name"] == "gbm-airline"
    assert b["configs"][-1]["source"] == cfg["source"]
    assert len(cfg["source"]) <= 200
    assert b["workloads"][-1]["name"] == CELL
    listed = [m["name"] for m in b["per_layer"]
              if CELL in m.get("workloads", [])]
    assert listed == ["hist_blocked_share", "hist_blocked_roofline",
                      "set_split_share", "boost_rest_s"]
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
        line = run.run_cell(reg, CELL, 2 ** 31 + 32, 0.5, False, devs)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    assert set(line["compared"]) == set(reg.cell(CELL)["limits"])
    json.dumps(line)


def test_set_split_share_reads_the_programs_counter():
    reg = Registry(rehearse.REPO)
    share = reg.reader("set_split_share").read({})
    # the rehearsal above trained in this process: six of the eight
    # columns are categorical and carry the response
    assert share is not None and 50.0 < share <= 100.0


def test_kept_model_survives_readings_npz(tmp_path):
    """`readings_sets.py` keeps a model with its sets bit-packed and
    compares it later, off the chip."""
    import numpy as np

    import readings_sets
    from reference import gbm_sets_plain

    reg = Registry(rehearse.REPO)
    table = reg.traffic("train_jobs_enum").table_module("airline_like")
    X, y = table.airline_like(3000, 3)
    params = dict(reg.config("gbm-airline")["params"], max_depth=3)
    model = gbm_sets_plain.train(np.ascontiguousarray(X.T), y,
                                 table.LEVELS, params, 2)
    model["splits"] = {"set": 5, "numeric": 2}
    path = str(tmp_path / "m.npz")
    readings_sets.save(path, model)
    back = readings_sets.load(path)
    assert back["splits"] == model["splits"]
    assert back["train_logloss"] == model["train_logloss"]
    for a, b in zip(model["trees"], back["trees"]):
        assert set(a) == set(b)
        for k in a:
            assert a[k].dtype == b[k].dtype and (a[k] == b[k]).all() or \
                (np.isnan(a[k]) == np.isnan(b[k])).all()
