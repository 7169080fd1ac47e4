"""A random forest whose categorical columns split on SETS of levels
(H2O-3 DRF at its default encoding, Enum: ``categorical_encoding=
"enum"``) through the normal entry points — `DRF.train`, the boost
plan, `_boost_jit` — held against the benchmark's plain reference
(`bench/reference/drf_sets_plain.py`) tree by tree, given the bags,
candidates and cuts the model hands out."""

import os
import sys

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models import DRF
from h2o_kubernetes_tpu.models.gbm import GBMModel
from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY, TRACER
from test_set_splits import LEVELS, _frame, _table

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
for p in (BENCH, os.path.join(BENCH, "compare")):
    if p not in sys.path:
        sys.path.insert(0, p)

import drf_sets  # noqa: E402  (bench/compare)
from reference import drf_sets_plain as ref  # noqa: E402

PARAMS = dict(max_depth=6, nbins=64, nbins_cats=1024,
              categorical_encoding="enum", sample_rate=0.632, mtries=-1,
              min_rows=1.0, min_split_improvement=1e-5)
NTREES = 3
MTRIES = 2                 # ⌊√5⌋: H2O-3's classification default


@pytest.fixture(scope="module")
def forest(mesh8):
    X, y = _table()
    fr = _frame(X, y)
    m = DRF(ntrees=NTREES, seed=1, **PARAMS).train(y="y", training_frame=fr)
    return X, y, fr, m


def _handed_out(m):
    return (np.stack([m.tree_bag(t) for t in range(NTREES)]),
            np.stack([m.tree_candidates(t) for t in range(NTREES)]))


def test_agrees_with_the_reference_split_for_split(forest):
    """Given the model's own bags, candidates and cuts the reference
    grows the same forest: every split on the same feature, the same
    set of levels or the same threshold, the same NA side; every cover
    exactly; leaves and gains to float32's rounding of integer sums."""
    X, y, _, m = forest
    mine = drf_sets.neutral_model(m)
    bags, cands = _handed_out(m)
    edges = np.asarray(m.bin_spec.edges_matrix())[:, :PARAMS["nbins"] - 3]
    theirs = ref.train(X, y, LEVELS, PARAMS, NTREES, seed=0, edges=edges,
                       bags=bags, candidates=cands)
    assert m.params.mtries == MTRIES and theirs["mtries"] == MTRIES
    assert mine["splits"]["set"] > mine["splits"]["numeric"] > 0
    assert mine["splits"] == theirs["splits"]
    for a, b in zip(mine["trees"], theirs["trees"]):
        for f in ("is_split", "feat", "is_set", "na_left", "cover"):
            np.testing.assert_array_equal(a[f], b[f], err_msg=f)
        np.testing.assert_array_equal(a["left"][:, :300], b["left"])
        assert not a["left"][:, 300:].any()
        num = a["is_split"] & ~a["is_set"]
        np.testing.assert_array_equal(a["thr"][num], b["thr"][num])
        np.testing.assert_allclose(a["value"], b["value"], atol=1e-6)
        np.testing.assert_allclose(a["gain"], b["gain"], rtol=1e-5,
                                   atol=1e-4)
    # the comparison the cell runs, over the reference's own bins
    gaps = drf_sets.compare(
        mine, X, y, {"params": PARAMS, "levels": LEVELS},
        {"check_trees": NTREES, "regret_trees": 2}, seed=3, workers=2)
    assert gaps["cover_gap"] == 0 and gaps["mtries_gap"] == 0
    assert gaps["value_gap"] < 1e-6 and gaps["gain_gap"] < 1e-5
    assert abs(gaps["regret_gap"]) < 1e-3
    assert gaps["bag_rate_gap"] < 0.03
    assert gaps["logloss_gap"] < 1e-5 and gaps["auc_gap"] < 1e-5


def test_the_carried_train_metric_is_a_walk_of_the_trees(forest):
    """The metric read off the sum the scan carried is BITWISE the
    one every tree walked again over the binned matrix gives."""
    _, _, fr, m = forest
    binned = fr.binned(m.bin_spec)
    raw = np.asarray(m._response(m._margins_of_binned(binned)))
    walked = {f"train_{k}": v
              for k, v in m.performance_of(fr, "y", raw).items()}
    assert m.scoring_history[-1] == {"ntrees": NTREES, **walked}


def test_predict_goes_through_the_heap_descent(forest, monkeypatch):
    """The flat scorer cannot carry a set: `predict` descends the heap
    over bin codes (`_margins_binned`), and gives the reference's
    forest probability."""
    X, _, fr, m = forest
    calls = []
    real = GBMModel._margins_binned

    def spy(self, *a, **kw):
        calls.append(1)
        return real(self, *a, **kw)

    monkeypatch.setattr(GBMModel, "_margins_binned", spy)
    got = np.asarray(m.predict_raw(fr))[:, 1]
    assert calls and "_flat_trees" not in m.__dict__
    want = ref.forest_prob(drf_sets.neutral_model(m)["trees"], X)
    np.testing.assert_allclose(got[: len(X)], want, atol=2e-7)


def test_spans_and_counters_say_what_the_forest_did(forest):
    """`h2o_train_splits_total{kind}` counts a forest's set splits, and
    its `train` root says how many columns split by sets and how many
    candidates a node had."""
    X, y, _, _ = forest
    ctr = REGISTRY.counter("h2o_train_splits_total", label="kind")
    before = ctr.value("set"), ctr.value("numeric")
    m = DRF(ntrees=2, seed=4, **PARAMS).train(y="y",
                                              training_frame=_frame(X, y))
    sp = np.asarray(m.trees.split_feat)
    n_set = int(np.isin(sp, [0, 1, 2]).sum())
    assert ctr.value("set") - before[0] == n_set > 0
    assert ctr.value("numeric") - before[1] == int((sp >= 3).sum())
    root = TRACER.by_root("train")[-1]["spans"][0]
    assert root["encoding"] == "enum" and root["set_features"] == 3
    assert root["mtries"] == MTRIES and root["bins"] == 512


def test_sets_beat_label_encoder_on_level_effects(forest):
    _, _, fr, m = forest
    ordinal = DRF(ntrees=NTREES, seed=1,
                  **dict(PARAMS, categorical_encoding="label_encoder")
                  ).train(y="y", training_frame=fr)
    assert ordinal.trees.left_bins is None
    got = m.scoring_history[-1]["train_logloss"]
    want = ordinal.scoring_history[-1]["train_logloss"]
    assert got < want - 0.02, (got, want)


def test_save_load_round_trip(forest, tmp_path):
    _, _, fr, m = forest
    want = np.asarray(m.predict_raw(fr))
    m2 = h2o.load_model(h2o.save_model(m, str(tmp_path / "forest.model")))
    assert m2._set_splits and m2.params._drf_mode
    assert (np.asarray(m2.predict_raw(fr)) == want).all()
