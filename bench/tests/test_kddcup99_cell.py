"""The cell `gbm-kddcup99.train` (H2O-3 multinomial GBM with set splits
on the KDD Cup 1999 table), rehearsed off the chip as `bench/run.py`
runs it (`run_cell`, at a tiny size, one virtual device): its files
held to `BENCHMARK.json` BY NAME, its table generator to the columns,
levels and class shares the configuration states, its traffic kind to
the keys every reader is given, and its three readers on a synthetic
traced window."""

import contextlib
import json

import jax
import numpy as np
import pytest

import rehearse
import run
from registry import Registry
from test_covtype_cell import PEAK, _ctx

CELL, CONFIG = "gbm-kddcup99.train", "gbm-kddcup99"
METRICS = ["multi_set_split_share", "multi_set_hist_roofline",
           "multi_set_rest_s"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny copy, its limits widened tenfold as `xgb-covtype`'s
    rehearsal widens them: the nodes of a 20,000-row table are a
    245th as large as the cell's, and their float32 sums read that much
    rougher against the reference's float64."""
    root = rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny_kdd")))
    path = f"{root}/bench/workloads/{CELL}.json"
    with open(path) as f:
        cell = json.load(f)
    cell["limits"] = {k: v * 10 for k, v in cell["limits"].items()}
    with open(path, "w") as f:
        json.dump(cell, f)
    return root


def _by_name(entries, name):
    (found,) = [e for e in entries if e["name"] == name]
    return found


def _table():
    return Registry(rehearse.REPO).traffic(
        "train_jobs_multi_enum").table_module("kddcup99_like")


def test_cell_file_entry_and_configuration_agree():
    reg = Registry(rehearse.REPO)
    b = reg.benchmark
    cell, entry = reg.cell(CELL), _by_name(b["workloads"], CELL)
    assert entry["config"] == CONFIG and entry["chips"] == 1
    assert entry["traffic"] == "train"
    assert cell["kind"] == "train_jobs_multi_enum"
    assert cell["why"] == entry["why"] and len(entry["why"]) <= 200
    cfg = reg.config(CONFIG)
    listed = _by_name(b["configs"], CONFIG)
    assert listed["file"] == f"bench/configs/{CONFIG}.json"
    assert cfg["source"] == listed["source"] and len(cfg["source"]) <= 200
    assert cfg["estimator"] == "GBM"
    assert cfg["comparison"] == "gbm_softmax_sets"
    assert cfg["params"] == {
        "distribution": "multinomial", "max_depth": 5, "learn_rate": 0.1,
        "min_rows": 10.0, "sample_rate": 1.0,
        "min_split_improvement": 1e-5, "nbins": 256, "nbins_cats": 1024,
        "categorical_encoding": "enum"}
    assert cfg["published"] == {"rows_per_chip": 4_898_431,
                                "features": 41, "classes": 23,
                                "ntrees": 50, "nbins": 20}
    assert cfg["rows_per_chip"] == 4_898_431 and cfg["ntrees"] == 3
    assert cfg["matrix_bins"] == 256 and cfg["histogram_channels"] == 3
    assert set(cfg["reduced"]) == set(cfg["reduced_why"]) == set(
        listed["reduced"]) == {"ntrees"}
    table = _table()
    assert cfg["features"] == table.N_FEATURES == 41
    assert cfg["classes"] == table.K == 23
    assert cfg["enum_levels"] == {n: len(d)
                                  for n, d in table.DOMAINS.items()}
    assert cell["check_rounds"] == cfg["ntrees"]
    assert cell["limits"]["cover_gap"] == 0
    assert set(cell["limits"]) == {"cover_gap", "value_gap", "gain_gap",
                                   "regret_gap", "logloss_gap",
                                   "split_gap"}
    per_layer = [m["name"] for m in b["per_layer"]
                 if CELL in m.get("workloads", [])]
    assert per_layer == METRICS
    for name in per_layer:
        assert callable(reg.reader(name).read)
    assert sum(w["chips"] == 4 for w in b["workloads"]) == 1
    assert len(json.dumps(b)) < 64 * 1024


def test_the_table_has_the_stated_columns_levels_and_shares():
    t = _table()
    assert t.class_counts(t.ROWS).tolist() == [t.COUNTS[c]
                                               for c in t.CLASSES]
    assert sum(t.COUNTS.values()) == t.ROWS == 4_898_431
    assert [len(t.DOMAINS[n]) for n in ("protocol_type", "service",
                                        "flag")] == [3, 70, 11]
    assert [n for n, lv in zip(t.NAMES, t.LEVELS) if lv] == [
        "protocol_type", "service", "flag"]
    rows = 200_000
    X, y = t.kddcup99_like(rows, 2 ** 31 + 17)
    assert X.shape == (41, rows) and X.dtype == np.float32
    np.testing.assert_array_equal(np.bincount(y, minlength=t.K),
                                  t.class_counts(rows))
    assert (t.class_counts(rows) >= 1).all()
    for j, (name, kind) in enumerate(t.COLUMNS):
        x = X[j]
        if kind == "enum":
            assert set(np.unique(x)) <= set(range(t.LEVELS[j])), name
        elif kind == "binary":
            assert set(np.unique(x)) <= {0.0, 1.0}, name
        elif kind == "rate":
            assert 0 <= x.min() and x.max() <= 1, name
            np.testing.assert_allclose(np.rint(x * 100) / 100, x,
                                       atol=1e-6)
        elif kind == "conn":
            assert 0 <= x.min() and x.max() <= 511, name
        elif kind == "host":
            assert 0 <= x.min() and x.max() <= 255, name
        elif kind == "zero":
            assert not x.any(), name
        else:
            assert x.min() >= 0 and (x == np.rint(x)).all(), name
    # the services' popularity is skewed, and a class hangs on them
    service = X[t.NAMES.index("service")].astype(np.int64)
    share = np.sort(np.bincount(service, minlength=70))[::-1] / rows
    assert share[:3].sum() > 0.6 and (share > 0).sum() > 40
    smurf = y == t.CLASSES.index("smurf")
    assert (service[smurf] == t.SERVICES.index("ecr_i")).mean() > 0.95
    # the same seed gives the same table
    X2, y2 = t.kddcup99_like(rows, 2 ** 31 + 17)
    assert (X2 == X).all() and (y2 == y).all()


def test_the_traffic_kind_gives_the_readers_the_same_keys(root):
    """`shape()`, a job and the window's result: every reader and the
    harness depend on their keys; a round's 23 class trees count in
    `trees`."""
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    got = {}
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1])):
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
                assert traffic.shape()["trees"] == 4 * 23
                assert res["failed"] == 0
    assert got[CELL] == got["gbm-higgs.train"]


def test_run_cell_end_to_end(root):
    """Jobs until the window is over, a seed past 2**31, nothing
    compiled inside the window, the comparison within the cell's own
    limits, every limit with its number; the splits counter then reads
    a K-class job's set splits."""
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    devs = jax.devices()[:1]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        line = run.run_cell(reg, CELL, 2 ** 31 + 44, 0.5, False, devs)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_rowtrees_per_s", "setup_s"}
    assert line["compared"]["cover_gap"] == [0.0, 0.0]
    assert set(line["compared"]) == set(reg.cell(CELL)["limits"])
    json.dumps(line)
    share = reg.reader("multi_set_split_share").read({})
    assert share is not None and 0.0 < share <= 100.0


def test_no_result_where_the_columns_do_not_split_by_sets(root):
    """Enums trained on their codes (`label_encoder`) are not what the
    cell measures: the warm-up job's root says no set features."""
    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    cell = reg.cell(CELL)
    config = reg.config(cell["config"])
    config = dict(config, params=dict(
        config["params"], categorical_encoding="label_encoder"))
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1])):
        h2o.init()
        traffic = reg.traffic(cell["kind"]).Traffic(
            cell, config, 7, lambda name: contextlib.nullcontext(),
            reg.comparison(config["comparison"]))
        with pytest.raises(SystemExit, match="no result"):
            traffic.setup()


def test_multi_set_readers_on_a_synthetic_window(monkeypatch):
    """`xgb-covtype`'s synthetic window read as a job with sets: the
    rest is `multi_rest_s`'s, the roofline `multi_hist_roofline`'s with
    the codes at their stored width; nothing where the newest job is
    not a K-class job with sets."""
    reg = Registry(rehearse.REPO)
    roof = reg.reader("multi_set_hist_roofline")
    rest = reg.reader("multi_set_rest_s")
    share = reg.reader("multi_set_split_share")
    ctx = _ctx()
    root = {"classes": 7, "set_features": 3, "bins": 256}
    for mod in (roof, rest):
        monkeypatch.setattr(mod, "k_class_sets", lambda: root)
    assert rest.read(ctx) == pytest.approx(10 * 6 * 0.030)
    base = reg.reader("multi_hist_roofline")
    monkeypatch.setattr(base, "_classes", lambda: 7)
    assert roof.read(ctx) == pytest.approx(base.read(ctx))
    # two bytes a code past 256 bins
    root["bins"] = 512
    by = 581_012 * (54 * 2 + 16 * 7) / PEAK["hbm_bytes_per_s"]
    assert roof.round_level_min_seconds(581_012, 54, 3, 7, 512, PEAK) \
        == (pytest.approx(by), "bytes")
    assert roof.read(ctx) == pytest.approx(100 * by / 0.040)
    other = _ctx(module="jit__boost_jit(5)")
    assert roof.read(other) is None and rest.read(other) is None
    for mod in (roof, rest):
        monkeypatch.setattr(mod, "k_class_sets", lambda: None)
    assert roof.read(ctx) is None and rest.read(ctx) is None
    monkeypatch.setattr(share, "k_class_sets", lambda: None)
    assert share.read({}) is None
