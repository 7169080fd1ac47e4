"""What each tree of a forest saw, and how forest growth is sized.

The forest hands out each tree's bag and each node's candidate features
(`tree_bag`, `tree_candidates`: drawn again from the keys the model
kept, with the functions the grower itself calls); a plain float64
forest (`bench/reference/drf_plain.py`, which imports nothing of the
program) follows the system's trees over those bags: covers exactly,
values and gains to float32 rounding, every split the best among its
candidates. A forest grows one tree a scan step, and `BoostPlan.chunks` is
the one sizing of its dispatches, which `train()` and compile-ahead
share with boosted trees.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models import DRF, GBM
from h2o_kubernetes_tpu.models import gbm as gbm_mod
from h2o_kubernetes_tpu.models.tree import core
from h2o_kubernetes_tpu.runtime.mesh import ROWS
from h2o_kubernetes_tpu.runtime.telemetry import TRACER

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")
for path in (BENCH, os.path.join(BENCH, "compare")):
    if path not in sys.path:
        sys.path.insert(0, path)

import gbm_bernoulli  # noqa: E402
from reference import drf_plain  # noqa: E402
from reference.gbm_plain import bin_rows  # noqa: E402

F, DEPTH, NBINS, ROWS_N = 6, 5, 16, 5003


def _table(seed=0, rows=ROWS_N):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, F)).astype(np.float32)
    y = X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.standard_normal(rows) > 0
    cols = {f"f{j}": X[:, j] for j in range(F)}
    cols["y"] = np.where(y, "s", "b")
    return X, y.astype(np.float64), cols


def _on(devices):
    return h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:devices]))


def _forest(cols, ntrees=4, seed=3, **kw):
    fr = h2o.Frame.from_arrays(cols)
    return DRF(ntrees=ntrees, max_depth=DEPTH, nbins=NBINS, seed=seed,
               **kw).train(y="y", training_frame=fr)


def _plain_trees(m):
    """The model's trees as the plain reference reads them: value-space
    thresholds (a row goes right when x >= thr), host float64."""
    return gbm_bernoulli.neutral_model(m)["trees"]


def _covers_match(m, X, y):
    for t, tree in enumerate(_plain_trees(m)):
        S, C = drf_plain.node_sums(tree, X, y, m.tree_bag(t)).T
        np.testing.assert_array_equal(tree["cover"], C)


@pytest.mark.parametrize("devices", [1, 4])
def test_forest_against_a_plain_forest_on_the_handed_out_draws(devices):
    """Cover exact, values and gains to float32 rounding, every split
    the best among its node's candidates — over the model's own cuts,
    so that the best is the same cut and not merely as good."""
    X, y, cols = _table()
    with _on(devices):
        m = _forest(cols)
    edges = np.asarray(m.bin_spec.edges_matrix())[:, :NBINS - 2]
    bins = bin_rows(X, edges)
    for t, tree in enumerate(_plain_trees(m)):
        bag, cand = m.tree_bag(t), m.tree_candidates(t)
        assert bag.shape == (ROWS_N,) and cand.shape == (2 ** (DEPTH + 1)
                                                         - 1, F)
        S, C = drf_plain.node_sums(tree, X, y, bag).T
        np.testing.assert_array_equal(tree["cover"], C)
        reached = C > 0
        np.testing.assert_allclose(tree["value"][reached],
                                   S[reached] / C[reached], rtol=1e-6,
                                   atol=1e-7)
        sp = tree["is_split"]
        assert not (sp & ~reached).any()
        kids = 2 * np.flatnonzero(sp) + 1
        terms = drf_plain.gain_term(S[kids], C[kids]) + \
            drf_plain.gain_term(S[kids + 1], C[kids + 1])
        gain = terms - drf_plain.gain_term(S[sp], C[sp])
        np.testing.assert_allclose(tree["gain"][sp], gain,
                                   atol=1e-6 * terms.max())
        # the split's feature was offered, and no offered cut was better
        assert cand[np.flatnonzero(sp), tree["feat"][sp]].all()
        best = drf_plain.best_gains(tree, X, bins, y, bag, cand, NBINS,
                                    1.0, 1e-5)
        np.testing.assert_allclose(best[sp], gain, atol=1e-6 * terms.max())
        assert (best[~sp] == 0).all()
        # mtries: floor(sqrt(6)) = 2 of 6 at every inner node, none at
        # the leaves' level
        inner = np.arange(len(C)) < len(C) // 2
        assert (cand[inner].sum(axis=1) == 2).all()
        assert not cand[~inner].any()


@pytest.mark.parametrize("devices", [1, 4])
def test_tree_bag_is_the_draw_the_grower_made(devices):
    """`core.tree_bag` against `_round_sampling` itself under the
    mesh's shard_map: the same rows kept, shard by shard."""
    per, rate = 1251, 0.632
    bp = core.BoostParams(sample_rate=rate, drf_mode=True)
    k_row, k_col = jax.random.split(jax.random.key(11))
    mesh = h2o.make_mesh(devices=jax.devices()[:devices])
    w = jnp.ones(devices * per, jnp.float32)
    drawn = jax.jit(jax.shard_map(
        lambda w: core._round_sampling(bp, w, F, k_row, k_col)[0],
        mesh=mesh, in_specs=P(ROWS), out_specs=P(ROWS)))(w)
    bag = np.asarray(core.tree_bag(k_row, devices, per, rate))
    np.testing.assert_array_equal(np.asarray(drawn) > 0, bag)
    assert abs(bag.mean() - rate) < 0.03
    # another shard count is another draw: the layout is part of it
    if devices > 1:
        other = np.asarray(core.tree_bag(k_row, 1, devices * per, rate))
        assert (other != bag).any()


def test_a_tied_draw_still_offers_exactly_mtries():
    """The tree key of `drf-higgs.train`'s seed 28603 (PERF.md section
    6, PR 28): node 65 of level 7 draws 0.1347971 for its fifth and its
    sixth lowest feature, and `r <= kth` offered it six."""

    kt = jax.random.wrap_key_data(
        jnp.asarray([2899255371, 2606073358], dtype=jnp.uint32))
    k_tree = jax.random.split(kt, 3)[2]
    r = np.sort(np.asarray(jax.random.uniform(
        jax.random.fold_in(k_tree, 7), (2 ** 7, 28)))[65])
    assert r[4] == r[5]
    offered = np.asarray(core.level_candidates(
        k_tree, 7, jnp.ones(28, dtype=bool), 5))
    assert (offered.sum(axis=1) == 5).all()
    # with fewer features in the tree's column sample than mtries, a
    # node is offered what there is
    few = jnp.arange(28) < 3
    assert (np.asarray(core.level_candidates(k_tree, 7, few, 5))
            == np.asarray(few)).all()


@pytest.mark.parametrize("budget,dispatches", [(None, 1), (1, 3)])
def test_keys_survive_the_chunk_loop(mesh8, monkeypatch, budget,
                                     dispatches):
    """A forest grown in one dispatch and in three hands out bags that
    reproduce its covers, tree by tree."""
    if budget is not None:
        monkeypatch.setattr(gbm_mod, "_DISPATCH_BUDGET", budget)
    X, y, cols = _table(seed=1)
    m = _forest(cols, ntrees=3)
    sent = [s for s in TRACER.by_root("train")[-1]["spans"]
            if s["name"] == "train.dispatch"]
    assert len(sent) == dispatches
    assert sum(s["trees"] for s in sent) == 3
    assert len(m.tree_draws.keys) == 3
    _covers_match(m, X, y)
    bags = [m.tree_bag(t) for t in range(3)]
    assert (bags[0] != bags[1]).any() and (bags[1] != bags[2]).any()


def test_checkpoint_carries_the_keys_on(mesh8):
    X, y, cols = _table(seed=2)
    fr = h2o.Frame.from_arrays(cols)
    kw = dict(max_depth=DEPTH, nbins=NBINS, seed=5)
    first = DRF(ntrees=2, **kw).train(y="y", training_frame=fr)
    more = DRF(ntrees=4, checkpoint=first, **kw).train(
        y="y", training_frame=fr)
    assert more.tree_draws.keys[:2] == first.tree_draws.keys
    _covers_match(more, X, y)


def test_sampled_gbm_hands_out_its_bags_too(mesh8):
    X, y, cols = _table(seed=3)
    fr = h2o.Frame.from_arrays(cols)
    m = GBM(ntrees=3, max_depth=3, nbins=NBINS, sample_rate=0.5,
            seed=2).train(y="y", training_frame=fr)
    cover = np.asarray(m.trees.cover)
    for t in range(3):
        bag = m.tree_bag(t)
        assert cover[t, 0] == bag.sum()
        assert m.tree_candidates(t)[:7].all()      # no mtries: all offered
    plain = GBM(ntrees=2, max_depth=3, nbins=NBINS, seed=2).train(
        y="y", training_frame=fr)
    assert plain.tree_draws is None
    with pytest.raises(ValueError, match="keeps no tree keys"):
        plain.tree_bag(0)


def test_model_with_its_draws_goes_through_save_and_load(mesh8, tmp_path):
    _, _, cols = _table(seed=4)
    m = _forest(cols, ntrees=2)
    path = h2o.save_model(m, str(tmp_path / "forest.bin"))
    back = h2o.load_model(path)
    assert back.tree_draws == m.tree_draws
    np.testing.assert_array_equal(back.tree_bag(1), m.tree_bag(1))


# -- the one sizing ---------------------------------------------------------


def _forest_plan(depth, bins, ntrees=50):
    return gbm_mod.boost_plan(
        DRF(ntrees=ntrees, max_depth=depth, nbins=bins).params,
        "bernoulli", 2, 28)


@pytest.mark.parametrize("rows", [65_536, 1_048_576, 4_194_304, 8_388_608])
@pytest.mark.parametrize("depth,bins", [(6, 64), (8, 256), (12, 64)])
def test_a_forest_dispatches_by_the_boosted_trees_rule(rows, depth, bins):
    """One sizing: a forest's dispatches are what `BoostPlan.chunks`
    gives boosted trees of the same shape — all the trees, as many a
    dispatch as `_DISPATCH_BUDGET` holds and never less than one — and
    nothing sizes a group (one tree a scan step: a dispatch's
    temporaries are one tree's, tests/test_chip_compile.py)."""
    chunks = _forest_plan(depth, bins).chunks(rows)
    assert sum(chunks) == 50 and min(chunks) >= 1
    per_tree = rows * 28 * bins * 2 ** depth
    assert all(n == 1 or n * per_tree <= gbm_mod._DISPATCH_BUDGET
               for n in chunks)
    boosted = gbm_mod.boost_plan(
        GBM(ntrees=50, max_depth=depth, nbins=bins, score_every=0).params,
        "bernoulli", 2, 28)
    assert chunks == boosted.chunks(rows)


def test_the_forests_the_old_sizing_refused():
    """PERF.md section 6, PR 28: six depth-6 trees on 4,194,304 rows
    were one dispatch AND one vmapped group of six (25 G of temporaries
    on a 16 G chip). They are still one dispatch; the deep forest of
    `drf-higgs.train` goes a tree a dispatch."""
    assert _forest_plan(6, 64, 6).chunks(4_194_304) == [6]
    assert _forest_plan(12, 64, 3).chunks(4_194_304) == [1, 1, 1]


def test_the_boosting_scan_refuses_a_forest(mesh8):
    """A single-output forest grows in `_boost_drf_jit` (no margin to
    carry through the scan). `_boost_shard` kept `drf_mode` branches
    that nothing reached; they are gone, and the boosting program
    refuses a forest's parameters when it is traced."""
    _, _, cols = _table(seed=3, rows=256)
    fr = h2o.Frame.from_arrays(cols)
    plan = gbm_mod.boost_plan(
        DRF(ntrees=2, max_depth=3, nbins=NBINS).params, "bernoulli", 2, F)
    assert plan.mode == "forest" and plan.bp.drf_mode
    from h2o_kubernetes_tpu.models.base import resolve_xy
    from h2o_kubernetes_tpu.models.tree.binning import fused_fit_bins

    data = resolve_xy(fr, "y", materialize_x=False)
    _, binned = fused_fit_bins(fr, data.feature_names, NBINS)
    keys = core.round_keys(jax.random.key(0), 2)
    args = plan.operands(binned, data.y, data.w, jnp.zeros_like(data.y),
                         keys, None)
    with pytest.raises(AssertionError, match="_boost_shard_drf"):
        core._boost_jit(*args)
    margin, trees = core._boost_drf_jit(*args)
    assert trees.value.shape[0] == 2


def test_train_and_compile_ahead_agree_on_the_dispatches(mesh8,
                                                         monkeypatch):
    """Both take the trees of every dispatch from `BoostPlan.chunks`: with
    the budget steered to four trees a dispatch, train() sends 4 + 2
    and compile-ahead lowers the same two key shapes."""
    _, _, cols = _table(seed=6, rows=2048)
    fr = h2o.Frame.from_arrays(cols)
    est = DRF(ntrees=6, max_depth=3, nbins=NBINS, seed=1)
    padded = -(-2048 // 8) * 8
    monkeypatch.setattr(gbm_mod, "_DISPATCH_BUDGET",
                        4 * padded * F * NBINS * 2 ** 3)
    lowered = []
    monkeypatch.setattr(
        gbm_mod, "_aot", lambda fn, *a: lowered.append((fn, a)))
    for thunk in est.compile_ahead_lowerings("y", fr):
        thunk()
    shapes = {a[4].shape for fn, a in lowered
              if fn is core._boost_drf_jit}
    assert shapes == {(4,), (2,)}
    m = est.train(y="y", training_frame=fr)
    sent = [(s["first_tree"], s["trees"])
            for s in TRACER.by_root("train")[-1]["spans"]
            if s["name"] == "train.dispatch"]
    assert sent == [(0, 4), (4, 2)]
    assert m.ntrees == 6 and len(m.tree_draws.keys) == 6
    X, y, _ = _table(seed=6, rows=2048)
    _covers_match(m, X, y)


def test_a_multinomial_rounds_class_trees_share_one_bag(mesh8):
    """K class trees a round: one row sample a round, candidates a
    tree."""
    rng = np.random.default_rng(8)
    X = rng.standard_normal((3001, F)).astype(np.float32)
    cls = np.where(X[:, 0] > 0.5, 2, np.where(X[:, 1] > 0, 1, 0))
    cols = {f"f{j}": X[:, j] for j in range(F)}
    cols["y"] = np.array(["a", "b", "c"])[cls]
    fr = h2o.Frame.from_arrays(cols)
    m = DRF(ntrees=2, max_depth=3, nbins=NBINS, seed=9).train(
        y="y", training_frame=fr)
    assert m.ntrees == 6 and m.tree_draws.classes == 3
    cover = np.asarray(m.trees.cover)
    bags = [m.tree_bag(t) for t in range(6)]
    for t in range(6):
        assert cover[t, 0] == bags[t].sum()
        assert (bags[t] == bags[3 * (t // 3)]).all()
        feat = np.asarray(m.trees.split_feat)[t]
        cand = m.tree_candidates(t)
        split = feat >= 0
        assert cand[np.flatnonzero(split), feat[split]].all()
    assert (bags[0] != bags[3]).any()
    assert (m.tree_candidates(0) != m.tree_candidates(1)).any()
