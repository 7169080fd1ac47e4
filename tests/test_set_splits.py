"""Set splits on categorical columns (H2O-3's
``categorical_encoding="enum"``; ISSUE 32): one bin a level, a split
sends a SET of levels left, through the normal entry points, held
against the benchmark's plain reference
(`bench/reference/gbm_sets_plain.py`) tree by tree; and everything that
cannot carry a set split yet refuses it by name."""

import io
import os
import sys

import jax
import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models import DRF, GBM, XGBoost
from h2o_kubernetes_tpu.models.tree import binning
from h2o_kubernetes_tpu.models.tree.core import Tree, flatten_trees

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
for p in (BENCH, os.path.join(BENCH, "compare")):
    if p not in sys.path:
        sys.path.insert(0, p)

import gbm_sets  # noqa: E402  (bench/compare)
from reference import gbm_sets_plain as ref  # noqa: E402

LEVELS = [7, 40, 300, 0, 0]
NAMES = ["c7", "c40", "c300", "x1", "x2"]
PARAMS = dict(max_depth=5, nbins=100, nbins_cats=1024,
              categorical_encoding="enum", learn_rate=0.1, min_rows=10.0,
              min_split_improvement=1e-5, distribution="bernoulli")
NTREES = 5
NOISE_GAIN = 1e-3      # float32 gains below this are rounding


def _table(n=6000, seed=0, na=False):
    """Enums of 7, 40 and 300 levels and two numeric columns; the
    response hangs on a random effect of every level, unrelated to its
    code."""
    rng = np.random.default_rng(seed)
    codes = [rng.integers(0, lv, n) for lv in LEVELS[:3]]
    eff = [rng.normal(size=lv) for lv in LEVELS[:3]]
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    z = sum(e[c] for e, c in zip(eff, codes)) + 0.5 * x1
    y = rng.random(n) < 1 / (1 + np.exp(-z))
    X = np.stack([c.astype(np.float32) for c in codes]
                 + [x1.astype(np.float32), x2.astype(np.float32)], axis=1)
    if na:
        X[rng.random(n) < 0.08, 1] = -1        # NA level codes
        X[rng.random(n) < 0.05, 3] = np.nan
    return X, y


def _frame(X, y):
    cols = {nm: X[:, j].astype(np.int32) if LEVELS[j] else X[:, j]
            for j, nm in enumerate(NAMES)}
    cols["y"] = np.where(y, "p", "n")
    doms = {nm: [f"{nm}_{i:03d}" for i in range(LEVELS[j])]
            for j, nm in enumerate(NAMES) if LEVELS[j]}
    return h2o.Frame.from_arrays(cols, domains=doms)


@pytest.fixture(scope="module")
def trained(mesh8):
    X, y = _table()
    fr = _frame(X, y)
    m = GBM(ntrees=NTREES, seed=1, **PARAMS).train(y="y",
                                                   training_frame=fr)
    return X, y, fr, m


def _same_ancestors(a, b):
    """Nodes whose path from the root took the same splits in both
    trees (a subtree under a differing split is another tree)."""
    N = len(a["feat"])
    ok = np.zeros(N, dtype=bool)
    ok[0] = True
    for i in range((N - 1) // 2):
        same = ok[i] and a["is_split"][i] and b["is_split"][i] and \
            a["feat"][i] == b["feat"][i] and \
            (a["left"][i, :300] == b["left"][i, :300]).all() and \
            (a["is_set"][i] or a["thr"][i] == b["thr"][i])
        ok[2 * i + 1] = ok[2 * i + 2] = same
    return ok


# (a) the program against the plain reference, tree by tree
def test_agrees_with_the_reference_tree_by_tree(trained):
    X, y, _, m = trained
    mine = gbm_sets.neutral_model(m)
    edges = np.asarray(m.bin_spec.edges_matrix())[:, :PARAMS["nbins"] - 3]
    theirs = ref.train(X, y, LEVELS, PARAMS, NTREES, edges=edges)
    assert mine["splits"]["set"] > mine["splits"]["numeric"] > 0
    compared = 0
    for a, b in zip(mine["trees"], theirs["trees"]):
        ok = _same_ancestors(a, b)
        clear = ok & (np.maximum(a["gain"], b["gain"]) > NOISE_GAIN)
        assert (a["is_split"] == b["is_split"])[clear].all()
        sp = clear & a["is_split"]
        compared += int(sp.sum())
        assert (a["feat"] == b["feat"])[sp].all()
        assert (a["is_set"] == b["is_set"])[sp].all()
        assert (a["na_left"] == b["na_left"])[sp].all()
        assert (a["left"][sp][:, :300] == b["left"][sp]).all()
        assert not a["left"][sp][:, 300:].any()
        num = sp & ~a["is_set"]
        assert (a["thr"][num] == b["thr"][num]).all()
        assert (a["cover"] == b["cover"])[ok].all()     # exactly
        np.testing.assert_allclose(a["gain"][sp], b["gain"][sp],
                                   rtol=2e-3, atol=1e-3)
    assert compared >= 100
    gaps = gbm_sets.compare(
        mine, X, y, {"params": PARAMS, "levels": LEVELS},
        {"check_trees": NTREES, "regret_trees": 2}, seed=3, blocks=2)
    assert gaps["cover_gap"] == 0.0
    assert gaps["regret_gap"] < 1e-6 and gaps["gain_gap"] < 3e-3
    assert gaps["value_gap"] < 5e-4 and gaps["logloss_gap"] < 1e-4


# (b) sets reach what an ordinal split on the code does not
def test_enum_beats_label_encoder_on_level_effects(trained):
    _, _, fr, m = trained
    ordinal = GBM(ntrees=NTREES, seed=1,
                  **dict(PARAMS, categorical_encoding="label_encoder")
                  ).train(y="y", training_frame=fr)
    assert ordinal.trees.left_bins is None
    assert ordinal.bin_spec.n_bins == PARAMS["nbins"]
    got = m.scoring_history[-1]["train_logloss"]
    want = ordinal.scoring_history[-1]["train_logloss"]
    assert got < want - 0.05, (got, want)


# (c) the level order is computed after the psum: shards do not matter
def test_one_shard_and_eight_give_the_same_splits(trained):
    X, y, _, m8 = trained
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1])):
        m1 = GBM(ntrees=NTREES, seed=1, **PARAMS).train(
            y="y", training_frame=_frame(X, y))
    # the shards' float32 histograms add up in another order, so a
    # gain that is rounding alone may fall on the other side of
    # min_split_improvement; every other split is the same split
    compared = 0
    for a, b in zip(gbm_sets.neutral_model(m1)["trees"],
                    gbm_sets.neutral_model(m8)["trees"]):
        ok = _same_ancestors(a, b)
        sp = ok & (np.maximum(a["gain"], b["gain"]) > NOISE_GAIN)
        compared += int((sp & a["is_split"]).sum())
        for f in ("is_split", "feat", "is_set", "na_left", "thr"):
            assert (a[f][sp] == b[f][sp]).all(), f
        assert (a["left"][sp] == b["left"][sp]).all()
        assert (a["cover"] == b["cover"])[ok].all()
    assert compared >= 100


# (d) NA levels, NA numbers and levels absent from a node
def test_na_and_absent_levels_route_as_documented(mesh8):
    X, y = _table(seed=4, na=True)
    fr = _frame(X, y)
    m = GBM(ntrees=3, seed=2, **PARAMS).train(y="y", training_frame=fr)
    mine = gbm_sets.neutral_model(m)
    B = m.bin_spec.n_bins
    binned = np.asarray(fr.binned(m.bin_spec))[: len(y)]
    assert (binned[X[:, 1] < 0, 1] == B - 1).all()       # NA level
    assert (binned[np.isnan(X[:, 3]), 3] == B - 1).all()
    # the reference follows the recorded sets and NA directions over
    # the raw table: every node's cover is what reaches it
    gaps = gbm_sets.compare(
        mine, X, y, {"params": PARAMS, "levels": LEVELS},
        {"check_trees": 3, "regret_trees": 1}, seed=1, blocks=2)
    assert gaps["cover_gap"] == 0.0 and gaps["regret_gap"] < 1e-6
    # a level with no rows in the node when it was split is not in the
    # set: it goes right
    t = mine["trees"][0]
    leaf = ref.descend(t, X)
    i = int(np.flatnonzero(t["is_set"] & (t["feat"] == 2))[-1])
    depth_i = int(np.floor(np.log2(i + 1)))
    at = np.floor(np.log2(leaf + 1)).astype(int)
    anc = ((leaf + 1) >> np.maximum(at - depth_i, 0)) - 1
    here = (anc == i) & (at >= depth_i)
    absent = np.setdiff1d(np.arange(300), X[here, 2].astype(int))
    assert len(absent) and not t["left"][i, absent].any()
    assert not np.asarray(m.trees.left_bins)[0, i, absent].any()


# (e) predict on the training frame is the train metric's margin
def test_predict_reproduces_the_train_metric(trained):
    _, y, fr, m = trained
    p1 = np.asarray(m.predict_raw(fr))[:, 1].astype(np.float64)
    ll = -np.mean(np.where(y, np.log(p1), np.log1p(-p1)))
    assert abs(ll - m.scoring_history[-1]["train_logloss"]) < 2e-6
    # bitwise: the heap descent over the binned training matrix, tree
    # by tree in the boost loop's order, is what the loop itself added
    from h2o_kubernetes_tpu.models.gbm import _stack_leaf_nodes

    binned = fr.binned(m.bin_spec)
    leaves = np.asarray(_stack_leaf_nodes(
        m.trees, binned, m.params.max_depth, m.bin_spec.n_bins))
    margin = np.full(leaves.shape[1], np.float32(m.init_score))
    for t in range(NTREES):
        margin = margin + np.asarray(m.trees.value)[t][leaves[t]]
    mine = np.asarray(m._margins(m._design_matrix(fr)))
    np.testing.assert_allclose(mine[: len(y)], margin[: len(y)],
                               rtol=0, atol=2e-6)
    perf = m.model_performance(fr, "y")
    assert abs(perf["logloss"]
               - m.scoring_history[-1]["train_logloss"]) < 2e-6


# (f) save / load
def test_save_load_round_trip(trained, tmp_path):
    _, _, fr, m = trained
    want = np.asarray(m.predict_raw(fr))
    m2 = h2o.load_model(h2o.save_model(m, str(tmp_path / "sets.model")))
    assert m2.bin_spec.encoding == "enum" and m2._set_splits
    assert (np.asarray(m2.trees.left_bins)
            == np.asarray(m.trees.left_bins)).all()
    assert (np.asarray(m2.predict_raw(fr)) == want).all()


def test_models_pickled_before_the_set_field_still_load(
        mesh8, tmp_path, monkeypatch):
    """A `Tree` pickled with seven fields (before `left_bins`) loads as
    what it was: no sets, the ordinal descent."""
    X, y = _table(n=2000)
    fr = _frame(X, y)
    m = GBM(ntrees=3, max_depth=3, seed=5).train(y="y", training_frame=fr)
    want = np.asarray(m.predict_raw(fr))
    monkeypatch.setattr(Tree, "__getnewargs__",
                        lambda self: tuple(self)[:7])
    path = h2o.save_model(m, str(tmp_path / "old.model"))
    monkeypatch.undo()
    m2 = h2o.load_model(path)
    assert len(m2.trees) == 8 and m2.trees.left_bins is None
    assert not m2._set_splits and m2.bin_spec.set_feats == ()
    assert (np.asarray(m2.predict_raw(fr)) == want).all()


# (g) 300 levels: a bin each under enum, ranges under label_encoder
def test_every_level_has_its_own_bin(trained):
    X, _, fr, m = trained
    spec = m.bin_spec
    assert spec.n_bins == 512 and spec.encoding == "enum"
    assert spec.is_enum == [True, True, True, False, False]
    assert spec.set_feats == (True, True, True, False, False)
    binned = np.asarray(fr.binned(spec))[: len(X)]
    assert binned.dtype == np.uint16
    assert (binned[:, :3] == X[:, :3].astype(int)).all()   # code = bin
    assert len(np.unique(binned[:, 2])) == len(np.unique(X[:, 2]))
    # numeric columns: the edges and codes of a 100-bin job
    plain = binning.fit_bins(fr, NAMES, n_bins=100)
    assert plain.n_bins == 100 and plain.encoding == "label_encoder"
    assert plain.is_enum == [True, True, False, False, False]
    pb = np.asarray(fr.binned(plain))[: len(X)]
    assert pb.dtype == np.uint8
    assert (pb[:, 3:] == binned[:, 3:]).all()
    np.testing.assert_array_equal(
        np.asarray(plain.edges_matrix())[3:, :97],
        np.asarray(spec.edges_matrix())[3:, :97])
    # label_encoder still folds 300 levels into nbins-2 ranges of codes
    assert len(np.unique(pb[:, 2])) <= 98
    assert (np.diff(pb[np.argsort(X[:, 2], kind="stable"), 2]) >= 0).all()


def test_bin_width_check_is_one_function():
    assert binning.bin_code_dtype(256) == np.uint8
    assert binning.bin_code_dtype(512, 100) == np.uint16
    with pytest.raises(ValueError, match=r"n_bins must be in \[4, 256\]"):
        binning.bin_code_dtype(512)
    with pytest.raises(ValueError, match="65536"):
        binning.bin_code_dtype(1 << 17, 100)
    with pytest.raises(ValueError, match="categorical_encoding"):
        binning.resolve_encoding("one_hot_explicit")
    assert binning.resolve_encoding("AUTO") == "label_encoder"


def test_all_numeric_frame_is_label_encoders_job(mesh8):
    """`enum` on a frame without enum columns bins and trains as
    `label_encoder` does: no wider matrix, no set table."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1500, 3)).astype(np.float32)
    fr = h2o.Frame.from_arrays({"a": X[:, 0], "b": X[:, 1], "c": X[:, 2],
                                "y": X[:, 0] * 2 + X[:, 1]})
    kw = dict(ntrees=3, max_depth=3, nbins=32, seed=3)
    a = GBM(categorical_encoding="enum", **kw).train(y="y",
                                                     training_frame=fr)
    b = GBM(**kw).train(y="y", training_frame=fr)
    assert a.trees.left_bins is None and a.bin_spec.n_bins == 32
    for f in ("split_feat", "split_bin", "value", "gain"):
        assert (np.asarray(getattr(a.trees, f))
                == np.asarray(getattr(b.trees, f))).all()


def test_spans_and_counters(trained):
    from h2o_kubernetes_tpu.runtime import telemetry

    X, y, _, _ = trained
    ctr = telemetry.REGISTRY.counter("h2o_train_splits_total",
                                     label="kind")
    before = ctr.value("set"), ctr.value("numeric")
    m = GBM(ntrees=NTREES, seed=1, **PARAMS).train(
        y="y", training_frame=_frame(X, y))
    sp = np.asarray(m.trees.split_feat)
    n_set = int(np.isin(sp, [0, 1, 2]).sum())
    assert ctr.value("set") - before[0] == n_set > 0
    assert ctr.value("numeric") - before[1] == int((sp >= 3).sum())
    root = telemetry.TRACER.by_root("train")[-1]["spans"][0]
    assert root["encoding"] == "enum" and root["bins"] == 512
    assert root["enum_features"] == 3


# -- what cannot carry a set split yet refuses it, by name ---------------

def _wide(n=400, F=70):
    rng = np.random.default_rng(1)
    cols = {f"s{j}": (rng.random(n) < 0.02).astype(np.float32)
            for j in range(F)}
    cols["e"] = rng.integers(0, 5, n).astype(np.int32)
    cols["y"] = rng.normal(size=n).astype(np.float32)
    return h2o.Frame.from_arrays(cols, domains={"e": list("abcde")})


def _multiclass(X, y):
    fr = _frame(X, y)
    fr["y"] = h2o.Frame.from_arrays(
        {"y": np.array(["a", "b", "c"])[np.arange(len(y)) % 3]}).vec("y")
    return fr


TRAIN_REFUSALS = {
    "DRF": (lambda: DRF(ntrees=2, max_depth=3,
                        categorical_encoding="enum"), {}, None),
    "the multinomial grower": (
        lambda: GBM(ntrees=2, max_depth=3, categorical_encoding="enum"),
        {}, "multi"),
    "the multinomial grower (a K-class forest)": (
        lambda: DRF(ntrees=2, max_depth=3, categorical_encoding="enum"),
        {}, "multi"),
    "the XGBoost facade": (
        lambda: XGBoost(ntrees=2, max_depth=3,
                        categorical_encoding="enum"), {}, None),
    "an EFB-bundled frame": (
        lambda: GBM(ntrees=2, max_depth=3, categorical_encoding="enum"),
        {"H2O_TPU_EFB": "1"}, "wide"),
    "GOSS": (lambda: GBM(ntrees=2, max_depth=3,
                         categorical_encoding="enum"),
             {"H2O_TPU_GOSS": "1"}, None),
    "the out-of-core path": (
        lambda: GBM(ntrees=2, max_depth=3, categorical_encoding="enum"),
        {"H2O_TPU_OOC": "1"}, None),
}


# a path that was refused and now carries set splits keeps its case
# here, as the case that trains (`test_drf_sets.py` holds it to the
# reference)
CARRIES_SETS = {"DRF"}


@pytest.mark.parametrize("name", list(TRAIN_REFUSALS))
def test_training_paths_refuse_set_splits_by_name(mesh8, monkeypatch,
                                                  name):
    make, env, table = TRAIN_REFUSALS[name]
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    X, y = _table(n=1200)
    fr = _wide() if table == "wide" else \
        _multiclass(X, y) if table == "multi" else _frame(X, y)
    if name in CARRIES_SETS:
        m = make().train(y="y", training_frame=fr)
        assert m._set_splits and m.bin_spec.encoding == "enum"
        return
    with pytest.raises(ValueError) as e:
        make().train(y="y", training_frame=fr)
    assert name.split(" (")[0] in str(e.value) and \
        "set split" in str(e.value)
    # and says what does carry one
    assert "GBM or DRF in device memory" in str(e.value)


def test_checkpoint_restart_refuses_set_splits(trained):
    _, _, fr, m = trained
    with pytest.raises(ValueError, match="checkpoint restart"):
        GBM(ntrees=NTREES + 2, seed=1, checkpoint=m, **PARAMS).train(
            y="y", training_frame=fr)
    with pytest.raises(ValueError, match="checkpoint restart"):
        GBM(ntrees=NTREES + 2, seed=1, checkpoint=m,
            **dict(PARAMS, categorical_encoding="label_encoder")).train(
            y="y", training_frame=fr)


def _flat(m):
    return flatten_trees(m.trees, np.asarray(m._edges),
                         np.asarray(m._enum_mask), m.params.max_depth)


def _publish(m, tmp):
    from h2o_kubernetes_tpu.operator.registry import ModelRegistry

    return ModelRegistry(str(tmp)).publish(m, "sets")


SCORE_REFUSALS = {
    "flat scorer": lambda m, fr, tmp: _flat(m),
    "MOJO export": lambda m, fr, tmp: h2o.export_mojo(m, io.BytesIO()),
    "registry": lambda m, fr, tmp: _publish(m, tmp),
    "TreeSHAP": lambda m, fr, tmp: m.predict_contributions(fr),
}


@pytest.mark.parametrize("name", list(SCORE_REFUSALS))
def test_scoring_paths_refuse_set_splits_by_name(trained, tmp_path, name):
    _, _, fr, m = trained
    with pytest.raises(ValueError) as e:
        SCORE_REFUSALS[name](m, fr, tmp_path)
    assert name in str(e.value) and "set split" in str(e.value)


def test_serving_entry_scores_sets_through_the_one_descent(trained):
    """`score_numpy` (the REST routes' entry, the jitted-scorer cache)
    reaches the heap descent `predict` does: no flat scorer is built."""
    X, _, fr, m = trained
    want = np.asarray(m.predict_raw(fr))
    got = np.asarray(m.score_numpy(X))
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert "_flat_trees" not in m.__dict__
