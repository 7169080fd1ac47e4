"""The set-split forest's cell, `drf-airline.train`, rehearsed off the
chip as `bench/run.py` runs it (`run_cell`, at a tiny size, one virtual
device), its files held to `BENCHMARK.json` — its
entries found by name, wherever later PRs put theirs — and its two
per-layer readers."""

import json
import os

import jax
import pytest

import rehearse
import run
from registry import Registry

CELL, CONFIG = "drf-airline.train", "drf-airline"
METRICS = ["forest_set_split_share", "forest_blocked_roofline"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """`rehearse.tiny_root`, its bag-rate limit widened for the
    rehearsal's rows: a bag of 20,000 rows keeps its share to a standard
    deviation of 0.34%, where the cell's 8,388,608 keep it to 0.017%."""
    dst = rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny_forest")))
    path = os.path.join(dst, "bench", "workloads", CELL + ".json")
    with open(path) as f:
        cell = json.load(f)
    cell["limits"]["bag_rate_gap"] = 0.02
    with open(path, "w") as f:
        json.dump(cell, f)
    return dst


def _by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def test_cell_file_entry_and_configuration_agree():
    reg = Registry(rehearse.REPO)
    b = reg.benchmark
    cell, entry = reg.cell(CELL), _by_name(b["workloads"], CELL)
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert cell["kind"] == "train_jobs_enum"
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    cfg = reg.config(CONFIG)
    assert cfg["source"] == _by_name(b["configs"], CONFIG)["source"]
    assert len(cfg["source"]) <= 200
    assert cfg["estimator"] == "DRF" and cfg["comparison"] == "drf_sets"
    assert cfg["params"] == {
        "max_depth": 12, "nbins": 64, "nbins_cats": 1024,
        "categorical_encoding": "enum", "mtries": -1, "sample_rate": 0.632,
        "min_rows": 1.0, "min_split_improvement": 1e-5}
    assert cfg["histogram_channels"] == 2 and cfg["matrix_bins"] == 512
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == set(
        _by_name(b["configs"], CONFIG)["reduced"]) == {
        "max_depth", "rows_per_chip", "ntrees"}
    table = reg.traffic(cell["kind"]).table_module(cfg["table"])
    assert cfg["features"] == len(table.LEVELS) == 8
    assert cfg["enum_levels"] == {n: lv for n, lv in table.COLUMNS if lv}
    assert cell["check_trees"] == cfg["ntrees"]
    assert cell["limits"]["cover_gap"] == cell["limits"]["mtries_gap"] == 0
    assert set(cell["limits"]) == {
        "cover_gap", "value_gap", "gain_gap", "regret_gap", "bag_rate_gap",
        "mtries_gap", "logloss_gap", "auc_gap"}
    listed = [m["name"] for m in b["per_layer"]
              if CELL in m.get("workloads", [])]
    assert listed == METRICS
    for name in listed:
        assert callable(reg.reader(name).read)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(json.dumps(b)) < 64 * 1024


def test_run_cell_end_to_end(root):
    """Jobs until the window is over, a seed past 2**31, nothing
    compiled inside the window, the comparison within the cell's own
    limits, every limit with its number."""
    import h2o_kubernetes_tpu as h2o

    from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

    ctr = REGISTRY.counter("h2o_train_splits_total", label="kind")
    before = ctr.value("set"), ctr.value("numeric")
    reg = Registry(root)
    devs = jax.devices()[:1]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        line = run.run_cell(reg, CELL, 2 ** 31 + 40, 0.5, False, devs)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    assert line["compared"]["mtries_gap"] == [0.0, 0.0]
    assert set(line["compared"]) == set(reg.cell(CELL)["limits"])
    json.dumps(line)
    # what a traced run's line carries (the CPU's trace has no device to
    # reduce, so the reader is asked here; the counter is the process's,
    # and other tests' jobs may have added to it): six of the eight
    # columns are categorical and carry the response
    n_set = ctr.value("set") - before[0]
    assert n_set > ctr.value("numeric") - before[1]
    share = reg.reader("forest_set_split_share").read({})
    assert share is not None and 0.0 < share <= 100.0


def test_forest_set_split_share_reads_nothing_after_a_boosted_job(root):
    import numpy as np

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    reg = Registry(rehearse.REPO)
    x = np.arange(400, dtype=np.float32)
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1])):
        h2o.init()
        fr = h2o.Frame.from_arrays({"a": x,
                                    "y": (x % 7).astype(np.float32)})
        GBM(ntrees=1, max_depth=2).train(y="y", training_frame=fr)
    assert reg.reader("forest_set_split_share").read({}) is None
    assert reg.reader("set_split_share").read({}) is not None


def test_forest_blocked_roofline_counts_codes_at_their_stored_width():
    """Two bytes a bin code where `work.level_bytes` counts one: at the
    cell's 8 columns a level reads 32 bytes a row, not 24."""
    import work

    reg = Registry(rehearse.REPO)
    reader = reg.reader("forest_blocked_roofline")
    peak = reg.peaks()["TPU v5 lite"]
    rows = 8_388_608
    two, bound = reader.level_min_seconds(rows, 8, 2, 2, peak)
    one, _ = reader.level_min_seconds(rows, 8, 2, 1, peak)
    assert bound == "bytes"
    assert two == pytest.approx(rows * 32 / peak["hbm_bytes_per_s"])
    assert one == pytest.approx(work.level_min_seconds(rows, 8, 2, peak)[0])


def test_forest_blocked_roofline_on_a_traced_window(monkeypatch):
    """On `test_drf_readers`' synthetic window (one blocked call a tree)
    the reader is `hist_blocked_roofline` with the codes counted at the
    width the newest job's `train` root says; a program whose spans do
    not say it gives nothing."""
    from test_drf_readers import SHAPE, make_ctx, read

    ctx = make_ctx()
    reader = Registry(rehearse.REPO).reader("forest_blocked_roofline")
    monkeypatch.setattr(reader, "_code_bytes", lambda: 2)
    F = SHAPE["features"]
    assert reader.read(ctx) == pytest.approx(
        read(ctx, "hist_blocked_roofline") * (2 * F + 16) / (F + 16))
    monkeypatch.setattr(reader, "_code_bytes", lambda: None)
    assert reader.read(ctx) is None
