"""The two cells of ISSUE 28 rehearsed off the chip as `bench/run.py`
runs them (`run_cell`, at a tiny size): `gbm-higgs.train-4chip` from its
own file on four virtual devices, `drf-higgs.train` on one. The test
steers: shrunk configurations, the CPU's device kind in the peaks, the
mesh; the cells' files, limits and entries are the real ones."""

import json
import os
import shutil

import jax
import pytest

import rehearse
import run
from registry import Registry

NEW_CELLS = ["gbm-higgs.train-4chip", "drf-higgs.train"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """`rehearse.tiny_root`, with the real four-chip cell and the real
    `BENCHMARK.json` put back over its stand-ins."""
    dst = rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny")))
    shutil.copy(os.path.join(rehearse.BENCH, "workloads",
                             rehearse.FOUR_CHIP + ".json"),
                os.path.join(dst, "bench", "workloads"))
    shutil.copy(os.path.join(rehearse.REPO, "BENCHMARK.json"), dst)
    # a bag of the rehearsal's 20,000 rows keeps its share to a standard
    # deviation of 0.34%, where the cell's 4,194,304 keep it to 0.024%
    path = os.path.join(dst, "bench", "workloads", "drf-higgs.train.json")
    with open(path) as f:
        cell = json.load(f)
    cell["limits"]["bag_rate_gap"] = 0.02
    with open(path, "w") as f:
        json.dump(cell, f)
    return dst


@pytest.mark.parametrize("workload", NEW_CELLS)
def test_cell_file_and_entry_agree(workload):
    reg = Registry(rehearse.REPO)
    cell, entry = reg.cell(workload), reg.entry(workload)
    assert cell["kind"] == "train_jobs" and cell["why"] == entry["why"]
    assert len(entry["why"]) <= 200
    config = reg.config(cell["config"])
    assert set(cell["limits"]) >= {"cover_gap", "value_gap", "gain_gap",
                                   "regret_gap", "logloss_gap", "auc_gap"}
    assert cell["limits"]["cover_gap"] == 0
    assert cell["check_trees"] == config["ntrees"]
    listed = [m["name"] for m in reg.benchmark["per_layer"]
              if workload in m.get("workloads", [])]
    assert listed == {"gbm-higgs.train-4chip": ["psum_exposed_share"],
                      "drf-higgs.train": ["hist_blocked_share",
                                          "hist_blocked_roofline"]}[workload]
    for name in listed:
        assert callable(reg.reader(name).read)


def test_benchmark_keeps_what_it_had():
    """New entries are at the end of their lists; the accepted ones are
    as the parent commit had them."""
    b = Registry(rehearse.REPO).benchmark
    assert [c["name"] for c in b["configs"]] == ["gbm-higgs", "drf-higgs"]
    assert [w["name"] for w in b["workloads"]] == [
        "gbm-higgs.train"] + NEW_CELLS
    assert [w["chips"] for w in b["workloads"]] == [1, 4, 1]
    assert [m["name"] for m in b["per_layer"]][17:] == [
        "psum_exposed_share", "hist_blocked_share",
        "hist_blocked_roofline"]
    assert all("workloads" not in m for m in b["per_layer"][:17])
    assert b["run_seconds"] == 10 and len(json.dumps(b)) < 64 * 1024


def test_drf_configuration_states_its_cuts():
    cfg = Registry(rehearse.REPO).config("drf-higgs")
    assert cfg["estimator"] == "DRF" and cfg["comparison"] == "drf_bagged"
    assert cfg["params"] == {"max_depth": 12, "nbins": 64,
                             "sample_rate": 0.632, "mtries": -1,
                             "min_rows": 1.0}
    assert cfg["features"] == 28 and cfg["histogram_channels"] == 2
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == {
        "rows_per_chip", "ntrees", "max_depth"}
    assert cfg["published"]["max_depth"] == 20 and cfg["assumed"]


@pytest.mark.parametrize("workload", NEW_CELLS)
def test_run_cell_end_to_end(root, workload):
    """Jobs until the window is over on the cell's number of devices, a
    seed past 2**31, nothing compiled inside the window, the comparison
    within the cell's own limits, every limit with its number."""
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    chips = reg.entry(workload)["chips"]
    devs = jax.devices()[:chips]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        line = run.run_cell(reg, workload, 2 ** 31 + 28, 0.5, False, devs)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert line["device"]["count"] == chips
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    assert set(line["compared"]) == set(reg.cell(workload)["limits"])
    if workload == "drf-higgs.train":
        assert line["compared"]["mtries_gap"] == [0.0, 0.0]
    json.dumps(line)


def test_forest_model_goes_through_readings_json(root):
    """`bench/readings.py` keeps a model as JSON and compares it later,
    off the chip: the forest's neutral form, its record of the trees'
    keys included, survives that, and the bags drawn again from it are
    the ones the trees were grown on (cover exact)."""
    import h2o_kubernetes_tpu as h2o
    import readings

    reg = Registry(root)
    cell = reg.cell("drf-higgs.train")
    config = reg.config(cell["config"])
    import contextlib

    traffic = reg.traffic(cell["kind"]).Traffic(
        cell, config, 77, lambda name: contextlib.nullcontext(),
        reg.comparison(config["comparison"]))
    devs = jax.devices()[:1]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        h2o.init()
        traffic.load()
        model = traffic.job(0)["model"]
    kept = json.loads(json.dumps(readings.to_json(model)))
    traffic.models = [readings.from_json(kept)]
    numbers = traffic.compare()
    assert numbers["cover_gap"] == 0 and numbers["mtries_gap"] == 0
    assert run.verdict(numbers, cell["limits"])[0], numbers
