"""K class trees a round whose categorical columns split on SETS of
levels — H2O-3's multinomial GBM and its K-class DRF at their default
encoding, Enum (``categorical_encoding="enum"``) — through the normal
entry points: `GBM.train` / `DRF.train`, the boost plan, the class
batch of `_boost_multi_jit`. Held against the benchmark's plain
reference (`bench/reference/gbm_softmax_sets_plain.py`) tree by tree,
one shard against eight, `predict` against the trained margin; the
class batch reads every class tree's set table without a batched
gather; the scoring formats that cannot carry a set refuse it by
name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models import DRF, GBM, XGBoost
from h2o_kubernetes_tpu.models.gbm import (_MULTINOMIAL_STEP_CAP,
                                           _make_boost_params)
from h2o_kubernetes_tpu.models.tree import core
from h2o_kubernetes_tpu.runtime.mesh import ROWS as ROW_AXIS
from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY, TRACER
from test_set_splits import SCORE_REFUSALS

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
for p in (BENCH, os.path.join(BENCH, "compare"),
          os.path.join(BENCH, "tables")):
    if p not in sys.path:
        sys.path.insert(0, p)

import gbm_softmax_sets  # noqa: E402  (bench/compare)
from reference import gbm_softmax_sets_plain as ref  # noqa: E402

LEVELS = [5, 12, 0, 0]
NAMES = ["c5", "c12", "x1", "x2"]
K = 4
ROUNDS = 3
ROWS = 4096
NOISE_GAIN = 1e-3      # float32 gains below this are rounding
PARAMS = {
    "gbm": dict(max_depth=3, nbins=32, nbins_cats=1024,
                categorical_encoding="enum", learn_rate=0.1,
                min_rows=10.0, min_split_improvement=1e-5,
                distribution="multinomial"),
    "drf": dict(max_depth=3, nbins=32, nbins_cats=1024,
                categorical_encoding="enum", sample_rate=0.632,
                mtries=-1, min_rows=1.0, min_split_improvement=1e-5),
}
ESTIMATORS = {"gbm": GBM, "drf": DRF}


def _table(n=ROWS, seed=0):
    """Enums of 5 and 12 levels and two numeric columns, missing values
    in an enum and in a number; each class hangs on a random effect of
    every level, unrelated to its code, and on x1."""
    rng = np.random.default_rng(seed)
    codes = [rng.integers(0, lv, n) for lv in LEVELS[:2]]
    eff = [rng.normal(size=(lv, K)) * 1.5 for lv in LEVELS[:2]]
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    z = sum(e[c] for e, c in zip(eff, codes)) + np.outer(x1, np.arange(K))
    p = np.exp(z - z.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    y = (rng.random(n)[:, None] > np.cumsum(p, axis=1)).sum(axis=1)
    X = np.stack([c.astype(np.float32) for c in codes]
                 + [x1.astype(np.float32), x2.astype(np.float32)], axis=1)
    X[rng.random(n) < 0.08, 1] = -1            # NA level codes
    X[rng.random(n) < 0.05, 2] = np.nan
    return X, np.minimum(y, K - 1)


def _frame(X, y):
    cols = {nm: X[:, j].astype(np.int32) if LEVELS[j] else X[:, j]
            for j, nm in enumerate(NAMES)}
    cols["y"] = np.array(list("abcd"))[y]
    doms = {nm: [f"{nm}_{i:02d}" for i in range(LEVELS[j])]
            for j, nm in enumerate(NAMES) if LEVELS[j]}
    return h2o.Frame.from_arrays(cols, domains=doms)


def _train(kind, fr, seed=1):
    return ESTIMATORS[kind](ntrees=ROUNDS, seed=seed,
                            **PARAMS[kind]).train(y="y", training_frame=fr)


@pytest.fixture(scope="module")
def table():
    return _table()


@pytest.fixture(scope="module")
def trained(mesh8, table):
    X, y = table
    fr = _frame(X, y)
    return {kind: _train(kind, fr) for kind in ESTIMATORS}


def _same_ancestors(a, b):
    """Nodes whose path from the root took the same splits in both
    trees (a subtree under a differing split is another tree)."""
    L = max(LEVELS)
    N = len(a["feat"])
    ok = np.zeros(N, dtype=bool)
    ok[0] = True
    for i in range((N - 1) // 2):
        same = ok[i] and a["is_split"][i] and b["is_split"][i] and \
            a["feat"][i] == b["feat"][i] and \
            (a["left"][i, :L] == b["left"][i, :L]).all() and \
            a["na_left"][i] == b["na_left"][i] and \
            (a["is_set"][i] or a["thr"][i] == b["thr"][i])
        ok[2 * i + 1] = ok[2 * i + 2] = same
    return ok


def _flat(model):
    return [t for trees in model["trees"] for t in trees]


@pytest.mark.parametrize("kind", list(ESTIMATORS))
def test_agrees_with_the_reference_tree_by_tree(trained, table, kind):
    """Given the model's own cuts (and a forest's bags and candidates)
    the reference grows the same class trees: every split that is not
    rounding on the same feature, the same set of levels or the same
    threshold, the same NA side; covers exactly; leaves and gains to
    float32's rounding."""
    X, y = table
    m = trained[kind]
    mine = gbm_softmax_sets.neutral_model(m)
    edges = np.asarray(m.bin_spec.edges_matrix())[
        :, :PARAMS[kind]["nbins"] - 3]
    draws = {}
    if kind == "drf":
        T = ROUNDS * K
        draws = dict(forest=True,
                     bags=np.stack([m.tree_bag(t) for t in range(T)]),
                     candidates=np.stack([m.tree_candidates(t)
                                          for t in range(T)]))
    theirs = ref.train(X, y, LEVELS, PARAMS[kind], ROUNDS, K, edges=edges,
                       **draws)
    assert m.bin_spec.n_bins == 32 and m.bin_spec.encoding == "enum"
    assert mine["classes"] == K and len(mine["trees"]) == ROUNDS
    assert mine["splits"]["set"] > 0 and mine["splits"]["numeric"] > 0
    L = max(LEVELS)
    compared = 0
    for a, b in zip(_flat(mine), _flat(theirs)):
        ok = _same_ancestors(a, b)
        clear = ok & (np.maximum(a["gain"], b["gain"]) > NOISE_GAIN)
        assert (a["is_split"] == b["is_split"])[clear].all()
        sp = clear & a["is_split"]
        compared += int(sp.sum())
        for f in ("feat", "is_set", "na_left"):
            assert (a[f] == b[f])[sp].all(), f
        assert (a["left"][sp][:, :L] == b["left"][sp]).all()
        assert not a["left"][sp][:, L:].any()
        num = sp & ~a["is_set"]
        assert (a["thr"][num] == b["thr"][num]).all()
        assert (a["cover"] == b["cover"])[ok].all()     # exactly
        np.testing.assert_allclose(a["value"][ok], b["value"][ok],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(a["gain"][sp], b["gain"][sp],
                                   rtol=2e-3, atol=1e-3)
    assert compared >= 30
    if kind == "gbm":
        # the comparison the cell runs, over the reference's own bins
        gaps = gbm_softmax_sets.compare(
            mine, X, y, {"params": PARAMS[kind], "levels": LEVELS,
                         "classes": K},
            {"check_rounds": ROUNDS, "regret_rounds": 2}, seed=3,
            blocks=2)
        assert gaps["cover_gap"] == 0.0
        assert gaps["value_gap"] < 1e-4 and gaps["gain_gap"] < 1e-3
        assert abs(gaps["regret_gap"]) < 1e-3
        assert gaps["logloss_gap"] < 1e-5


@pytest.mark.parametrize("kind", list(ESTIMATORS))
def test_one_shard_and_eight_give_the_same_trees(trained, table, kind):
    """Every class tree's level order is taken after the psum: one
    device and the 8-device mesh grow the same splits (a gain that is
    rounding alone may fall either side of min_split_improvement). A
    forest's bag is drawn a shard (`core.row_keep`), so the forest is
    compared unbagged."""
    X, y = table
    kw = {"sample_rate": 1.0} if kind == "drf" else {}

    def train():
        return ESTIMATORS[kind](ntrees=ROUNDS, seed=1,
                                **dict(PARAMS[kind], **kw)).train(
            y="y", training_frame=_frame(X, y))

    m8 = train() if kw else trained[kind]
    with h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1])):
        m1 = train()
    compared = 0
    for a, b in zip(_flat(gbm_softmax_sets.neutral_model(m1)),
                    _flat(gbm_softmax_sets.neutral_model(m8))):
        ok = _same_ancestors(a, b)
        sp = ok & (np.maximum(a["gain"], b["gain"]) > NOISE_GAIN)
        compared += int((sp & a["is_split"]).sum())
        for f in ("is_split", "feat", "is_set", "na_left", "thr"):
            assert (a[f][sp] == b[f][sp]).all(), f
        assert (a["left"][sp] == b["left"][sp]).all()
        assert (a["cover"] == b["cover"])[ok].all()
    assert compared >= 30


@pytest.mark.parametrize("kind", list(ESTIMATORS))
def test_predict_reproduces_the_train_metric(trained, table, kind):
    """`predict` scores a K-class model with sets by the heap descent
    over bin codes, and its probabilities give the train metric the
    job reported from its own margin."""
    X, y = table
    m = trained[kind]
    fr = _frame(X, y)
    pr = np.asarray(m.predict_raw(fr))[: len(y)].astype(np.float64)
    assert pr.shape == (len(y), K) and "_flat_trees" not in m.__dict__
    ll = -np.mean(np.log(np.clip(pr[np.arange(len(y)), y], 1e-7, 1.0)))
    assert abs(ll - m.scoring_history[-1]["train_logloss"]) < 2e-6
    walked = gbm_softmax_sets.neutral_model(m)
    Xr = np.ascontiguousarray(X)
    if kind == "drf":
        want = ref.forest_probs(walked["trees"], Xr)
    else:
        margin = np.tile(np.asarray(walked["init"]), (len(y), 1))
        for trees in walked["trees"]:
            for k, tree in enumerate(trees):
                margin[:, k] += tree["value"][ref.descend(tree, Xr)]
        want = np.exp(margin - margin.max(axis=1, keepdims=True))
        want /= want.sum(axis=1, keepdims=True)
    np.testing.assert_allclose(pr, want, atol=2e-6)


def test_root_and_counters_say_what_the_job_did(table):
    """The `train` root of a K-class job with sets says its classes,
    its class batch and its set features; `h2o_train_splits_total`
    counts every class tree's splits by kind."""
    X, y = table
    ctr = REGISTRY.counter("h2o_train_splits_total", label="kind")
    before = ctr.value("set"), ctr.value("numeric")
    m = GBM(ntrees=2, seed=4, **PARAMS["gbm"]).train(
        y="y", training_frame=_frame(X, y))
    sp = np.asarray(m.trees.split_feat)
    assert sp.shape[0] == 2 * K
    n_set = int(np.isin(sp, [0, 1]).sum())
    assert ctr.value("set") - before[0] == n_set > 0
    assert ctr.value("numeric") - before[1] == int((sp >= 2).sum()) > 0
    root = TRACER.by_root("train")[-1]["spans"][0]
    assert root["classes"] == K and root["class_batch"] == "vmap"
    assert root["set_features"] == 2 and root["encoding"] == "enum"
    assert np.asarray(m.trees.left_bins).shape == (2 * K, 7, 32)


@pytest.mark.parametrize("cap", [None, _MULTINOMIAL_STEP_CAP])
def test_a_multinomial_step_is_capped_as_h2o_caps_it(mesh8, cap):
    """Rows whose margin puts p = 1 on a class they are not have h = 0
    there (float32), and the Newton step G / (H + 1e-10) of a node of
    them runs to 1e12. H2O-3's GBM caps a multinomial step at 1e4
    either way, and the margin takes the capped step; without a cap
    (the XGBoost facade's steps) it takes the whole step."""
    rows, F, lr = 1024, 3, 0.1
    tp = core.TreeParams(max_depth=2, n_bins=16, min_rows=1.0,
                         hist_impl="segment")
    bp = core.BoostParams(distribution="multinomial", learn_rate=lr,
                          leaf_cap=cap)
    margin = np.zeros((rows, 3), np.float32)
    margin[:, 0] = 100.0                       # p_0 = 1, every row y = 1
    binned = np.random.default_rng(0).integers(0, 15, (rows, F))
    out, trees = core._boost_multi_jit(
        binned.astype(np.uint8), np.ones(rows, np.float32),
        np.ones(rows, np.float32), margin,
        jax.random.split(jax.random.key(0), 1), None, tp, bp, 3, mesh8)
    step = float(np.asarray(trees.value)[0, 0, 0])      # class 0's root
    if cap is None:
        assert step < -1e9
    else:
        assert step == -cap
        np.testing.assert_array_equal(np.asarray(out)[:, 0],
                                      np.float32(100.0 - cap))
    assert np.isfinite(np.asarray(out)).all()


def test_the_cap_is_h2o_gbms_multinomial_alone():
    """H2O-3's multinomial steps are a multinomial GBM's with set
    features: a forest, another distribution and a multinomial GBM
    without sets keep the program they had, and the XGBoost facade
    refuses sets by name."""
    sets = (True, False)
    cap = {name: _make_boost_params(est().params, dist, feats).leaf_cap
           for name, est, dist, feats in (
               ("gbm", GBM, "multinomial", sets),
               ("gbm_no_sets", GBM, "multinomial", (False, False)),
               ("bernoulli", GBM, "bernoulli", sets),
               ("drf", DRF, "multinomial", sets))}
    assert cap == {"gbm": _MULTINOMIAL_STEP_CAP, "gbm_no_sets": None,
                   "bernoulli": None, "drf": None}
    X, y = _table(n=256)
    with pytest.raises(ValueError, match="XGBoost facade"):
        XGBoost(ntrees=1, max_depth=2, nbins=32, nbins_cats=1024,
                categorical_encoding="enum").train(
            y="y", training_frame=_frame(X, y))


def test_the_softmax_keeps_one_minus_p_for_a_likely_class():
    """H2O-3's multinomial gradients keep every 1 - p to float32's
    relative precision: a row at p = 1 - 2e-9 for a class reads h = 0
    there through ``1 - p`` in float32, and 2e-9 through the other
    classes' share."""
    margin = np.array([[20.0, 0.0, 0.0], [0.0, 0.5, -3.0],
                       [-40.0, 40.0, 0.0], [1.0, 1.0, 1.0]], np.float32)
    y = np.array([0, 1, 0, 2])
    yk = (y[:, None] == np.arange(3)).astype(np.float32)
    g, h = (np.asarray(a) for a in jax.jit(core._softmax_grad_hess)(
        margin, yk))
    m = margin.astype(np.float64)
    p = np.exp(m - m.max(axis=1, keepdims=True))
    p /= p.sum(axis=1, keepdims=True)
    q = 1.0 - p
    q[0, 0] = 2.0 * np.exp(-20.0) / (1.0 + 2.0 * np.exp(-20.0))
    q[2, 1] = (np.exp(-80.0) + np.exp(-40.0)) / (1.0 + np.exp(-80.0)
                                                 + np.exp(-40.0))
    np.testing.assert_allclose(g.T, np.where(yk > 0, -q, p), rtol=1e-5)
    np.testing.assert_allclose(h.T, p * q, rtol=1e-5)
    naive = jax.nn.softmax(margin, axis=1)
    assert float((naive * (1.0 - naive))[0, 0]) == 0.0 < h[0, 0]


def test_a_split_gain_from_its_childrens_sums():
    """`_kid_gain` is the three-term gain of `_split_gains` from the
    children's sums, and keeps the digits of a gain small beside the
    parent's G^2 / H, where the three terms' float32 difference keeps
    only the rounding of the largest."""
    rng = np.random.default_rng(5)
    Gl, Gr = rng.normal(0, 50, 64), rng.normal(0, 50, 64)
    Hl, Hr = rng.uniform(0.1, 100, 64), rng.uniform(0.1, 100, 64)
    Gr[:8] = Gl[:8] * Hr[:8] / Hl[:8] * (1 + 1e-3)   # nearly one step
    Hl[8], Hr[8], Gl[8] = 1e-12, 5.0, 0.0               # an empty side

    def term(G, H):
        return G * G / (H + 1e-10)

    for lam in (0.0, 1.0):
        tp = core.TreeParams(reg_lambda=lam)
        f32 = [np.float32(a) for a in (Gl, Hl, Gr, Hr)]
        got = np.asarray(core._kid_gain(*map(jnp.asarray, f32), tp))
        G64 = [a.astype(np.float64) for a in f32]
        want = (term(G64[0], G64[1] + lam) + term(G64[2], G64[3] + lam)
                - term(G64[0] + G64[2], G64[1] + G64[3] + lam))
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=1e-6 * np.abs(want).max())
        naive = np.asarray(core._gain_term(f32[0], f32[1], tp)
                           + core._gain_term(f32[2], f32[3], tp)
                           - core._gain_term(f32[0] + f32[2],
                                             f32[1] + f32[3], tp))
        if lam == 0.0:
            near = slice(0, 8)
            assert np.abs(got - want)[near].max() < \
                np.abs(naive - want)[near].max() / 10


def test_node_statistics_hold_a_skewed_tables_rows(mesh8):
    """On the KDD Cup table's shape (23 classes from 2,807,886 rows down
    to 2, cut to 16,384 rows) a child of a few rows under a parent of
    thousands is the common split: every node's step and every split's
    gain, taken from the rows that reach it, hold the reference's
    float64 sums over the model's own routing to float32's rounding, and
    a split stands only where its true gain passes min_split_improvement
    (with every split the level histograms chose kept, `split_gap` reads
    0.051 here: splits on float32's rounding)."""
    import kddcup99_like as table

    X, y = table.kddcup99_like(16_384, 2 ** 31 + 17)
    fr = h2o.Frame.from_arrays(table.as_columns(X, y),
                               domains=table.domains())
    params = dict(distribution="multinomial", max_depth=4,
                  learn_rate=0.1, min_rows=10.0,
                  min_split_improvement=1e-5, nbins=64, nbins_cats=1024,
                  categorical_encoding="enum")
    m = GBM(ntrees=2, seed=3, **params).train(y="y", training_frame=fr)
    gaps = gbm_softmax_sets.compare(
        gbm_softmax_sets.neutral_model(m), np.ascontiguousarray(X.T), y,
        {"params": params, "levels": table.LEVELS, "classes": table.K},
        {"check_rounds": 2, "regret_rounds": 1, "regret_classes": 4},
        seed=3, blocks=2)
    assert gaps["cover_gap"] == 0.0
    assert gaps["value_gap_99th"] < 1e-4 and gaps["gain_gap"] < 1e-5
    assert gaps["value_gap"] < 1e-5 and gaps["logloss_gap"] < 1e-5
    assert gaps["split_gap"] < 0.02


def _node_pass(mesh, tree, leaf, g, h, w, tp):
    """`core._node_sums` under the boost scan's shard_map: rows by
    shard, the tree and what comes out replicated."""
    return jax.shard_map(
        lambda t, *a: core._node_sums(t, *a, tp), mesh=mesh,
        in_specs=(P(),) + (P(ROW_AXIS),) * 4, out_specs=P(),
        check_vma=False)(tree, leaf, g, h, w)


def test_a_split_that_gains_nothing_over_its_rows_is_undone(mesh8):
    """A depth-2 tree whose node 1 splits rows of one step in two (its
    gain from the rows is 0) and whose node 2 splits two steps apart:
    node 1 becomes a leaf, its children leave the tree (no cover, no
    value, no split) and its rows rest at it; node 2's split stands
    with its gain and every reached node's value is -G / H of its
    rows."""
    tp = core.TreeParams(max_depth=2, n_bins=16, min_rows=1.0,
                         gamma=1e-5)
    N = 7
    tree = core.Tree(
        split_feat=jnp.array([0, 1, 2, -1, -1, -1, -1], jnp.int32),
        split_bin=jnp.zeros(N, jnp.int32), na_left=jnp.zeros(N, bool),
        is_split=jnp.array([1, 1, 1, 0, 0, 0, 0], bool),
        value=jnp.full(N, 7.0), gain=jnp.full(N, 3.0),
        cover=jnp.array([8.0, 4, 4, 2, 2, 2, 2]))
    leaf = jnp.array([3, 3, 4, 4, 5, 5, 6, 6], jnp.int32)
    g = jnp.array([1.0, 1, 1, 1, -1, -1, 2, 2])
    h = jnp.ones(8)
    w = jnp.ones(8)
    out, rest = _node_pass(mesh8, tree, leaf, g, h, w, tp)
    out = jax.tree.map(np.asarray, out)
    np.testing.assert_array_equal(out.is_split, [1, 0, 1, 0, 0, 0, 0])
    np.testing.assert_array_equal(out.split_feat,
                                  [0, -1, 2, -1, -1, -1, -1])
    np.testing.assert_array_equal(rest, [0, 1, 2, 1, 1, 5, 6])
    np.testing.assert_array_equal(out.cover, [8, 4, 4, 0, 0, 2, 2])
    np.testing.assert_allclose(out.value, [-0.75, -1, -0.5, 0, 0, 1, -2],
                               rtol=1e-6)
    # G^2 / H: node 0 4 + 1 - 4.5, node 2 2 + 8 - 1
    np.testing.assert_allclose(out.gain, [0.5, 0, 9, 0, 0, 0, 0],
                               rtol=1e-5)


@pytest.mark.parametrize("form", ["select", "segment"])
def test_the_node_pass_sums_rows_either_way(form, monkeypatch):
    """`core._resting_sums` sums each class tree's rows into the node
    they rest at as a fused compare where `_node_lookup` would select
    and as a segment sum past it, the same sums either way."""
    monkeypatch.setattr(core, "_SELECT_MAX_ENTRIES",
                        1 << 30 if form == "select" else 0)
    rng = np.random.default_rng(11)
    K_, rows, N = 3, 500, 31
    leaf = rng.integers(0, N, (K_, rows)).astype(np.int32)
    g = rng.normal(size=(K_, rows)).astype(np.float32)
    live = (rng.random(rows) < 0.9).astype(np.float32)
    got = np.asarray(jax.jit(lambda *a: core._resting_sums(
        a[0], a[1:], N))(leaf, g, live))
    assert got.shape == (K_, N, 2)
    for k in range(K_):
        np.testing.assert_allclose(
            got[k, :, 0], np.bincount(leaf[k], g[k], N), rtol=1e-5,
            atol=1e-5)
        np.testing.assert_array_equal(
            got[k, :, 1], np.bincount(leaf[k], live, N))


@pytest.mark.parametrize("nodes,bins,K", [
    (1, 16, 0), (7, 32, 0), (16, 256, 0), (15, 512, 0), (31, 40, 0),
    (8, 256, 5), (16, 256, 23), (3, 100, 4)])
def test_the_packed_set_lookup_is_the_table_read_by_row(nodes, bins, K):
    """`_set_word_lookup` reads ``left_bins[node, rowbin]`` for every
    row — bins packed 32 to a word, the last word padded — alone and
    under the class batch's `vmap`, as the flat gather does."""
    rng = np.random.default_rng(nodes * bins + K)
    lead = (K,) if K else ()
    lb = rng.random(lead + (nodes, bins)) < 0.5
    node = rng.integers(0, nodes, lead + (777,)).astype(np.int32)
    rowbin = rng.integers(0, bins, lead + (777,)).astype(np.int32)
    fn = core._set_word_lookup
    if K:
        fn = jax.vmap(fn)
    got = np.asarray(jax.jit(fn)(lb, node, rowbin))
    want = np.take_along_axis(
        lb.reshape(lead + (-1,)), node * bins + rowbin, axis=-1)
    np.testing.assert_array_equal(got, want)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def _set_descend_gathers(program, K_, mesh):
    """The `gather`s a boost program's trace holds in the set descent's
    scope, with the set table of three of six columns, 16 bins."""
    rows, F = 1024, 6
    tp = core.TreeParams(max_depth=3, n_bins=16, min_rows=1.0,
                         hist_impl="segment",
                         set_feats=(True, True, True, False, False,
                                    False))
    bp = core.BoostParams(distribution="multinomial" if K_ > 1
                          else "bernoulli")
    keys = jax.random.split(jax.random.key(0), 2)
    shape = (rows, K_) if K_ > 1 else (rows,)
    jaxpr = jax.make_jaxpr(
        lambda b, y, w, m, k: program(b, y, w, m, k, None, tp, bp, K_,
                                      mesh))(
        np.zeros((rows, F), np.uint8), np.zeros(rows, np.float32),
        np.ones(rows, np.float32), np.zeros(shape, np.float32), keys)
    return [e for e in _eqns(jaxpr.jaxpr)
            if e.primitive.name == "gather"
            and "set_descend" in str(e.source_info.name_stack)]


def test_the_class_batch_reads_set_tables_without_a_gather(mesh8):
    """Under the class batch's `vmap` a by-row gather of a node's set
    table is a batched gather (most of a K-class job, as a by-row lookup
    of a node table was before it became a select): each class
    tree's rows read their table by a select over its packed words.
    One tree a round keeps its gather, which the count sees."""
    assert core.multi_grow_vmapped(
        core.TreeParams(max_depth=3, n_bins=16), 6, 7)
    assert not _set_descend_gathers(core._boost_multi_jit, 7, mesh8)
    assert _set_descend_gathers(core._boost_jit, 1, mesh8)


@pytest.mark.parametrize("name", list(SCORE_REFUSALS))
def test_scoring_paths_refuse_set_splits_by_name(trained, tmp_path, name):
    fr = _frame(*_table(n=256))
    for m in trained.values():
        with pytest.raises(ValueError) as e:
            SCORE_REFUSALS[name](m, fr, tmp_path)
        assert name in str(e.value) and "set split" in str(e.value)
