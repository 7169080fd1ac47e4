"""What each tree of a forest saw, and how forest growth is sized.

The forest hands out each tree's bag and each node's candidate features
(`tree_bag`, `tree_candidates`: drawn again from the keys the model
kept, with the functions the grower itself calls); a plain float64
forest (`bench/reference/drf_plain.py`, which imports nothing of the
program) follows the system's trees over those bags: covers exactly,
values and gains to float32 rounding, every split the best among its
candidates. A forest grows one tree a scan step, and `BoostPlan.chunks` is
the one sizing of its dispatches, which `train()` and compile-ahead
share with boosted trees. Its scan carries the sum of its trees' leaf
values, every row's, and the train metric is read off that sum: bitwise
what walking every tree again gives (PR 33).
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


def test_the_boosting_scan_carries_a_forests_leaf_sums(mesh8):
    """A single-output forest grows in `_boost_jit`, the program of
    every job of one tree a round: its gradients never read the carry,
    and its rate of 1 leaves in the carry the sum of its trees' leaf
    values at each row, bitwise what `gbm._leaf_sums` walks off the
    trees it returns."""
    _, _, cols = _table(seed=3, rows=256)
    fr = h2o.Frame.from_arrays(cols)
    plan = gbm_mod.boost_plan(
        DRF(ntrees=2, max_depth=3, nbins=NBINS).params, "bernoulli", 2, F)
    assert plan.mode == "single" and plan.bp.drf_mode
    assert gbm_mod._BOOST_PROGRAMS[plan.mode] is core._boost_jit
    from h2o_kubernetes_tpu.models.base import resolve_xy
    from h2o_kubernetes_tpu.models.tree.binning import fused_fit_bins

    data = resolve_xy(fr, "y", materialize_x=False)
    _, binned = fused_fit_bins(fr, data.feature_names, NBINS)
    keys = core.round_keys(jax.random.key(0), 2)
    args = plan.operands(binned, data.y, data.w, jnp.zeros_like(data.y),
                         keys, None)
    margin, trees = core._boost_jit(*args)
    assert trees.value.shape[0] == 2
    walked = gbm_mod._leaf_sums(trees, binned, 1, 3, plan.tp.n_bins)
    assert np.asarray(margin).tobytes() == np.asarray(walked).tobytes()
    assert np.asarray(margin).any()


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
              if fn is core._boost_jit}
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


# ---------------------------------------------------------------------------
# The forest's train metric off the leaves the grower found (PR 33)
# ---------------------------------------------------------------------------

def _response_table(kind, rows=1500, na=False, seed=4):
    """A frame's columns with a two-class, numeric or three-class
    response; ``na`` blanks a twentieth of every feature."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((rows, F)).astype(np.float32)
    score = X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.standard_normal(rows)
    if na:
        X[rng.random(X.shape) < 0.05] = np.nan
    cols = {f"f{j}": X[:, j] for j in range(F)}
    if kind == "binomial":
        cols["y"] = np.where(score > 0, "s", "b")
    elif kind == "multinomial":
        cols["y"] = np.array(["a", "b", "c"])[np.digitize(score,
                                                          [-0.5, 0.5])]
    else:
        cols["y"] = score.astype(np.float32)
    return cols


def _walked_metric(m, fr):
    """(binned matrix, the train metric by the walk): every tree of the
    finished model descended again over every row."""
    binned = fr.binned(m.bin_spec)
    raw = np.asarray(m._response(m._margins_of_binned(binned)))
    return binned, {f"train_{k}": v
                    for k, v in m.performance_of(fr, "y", raw).items()}


def _metric_span():
    """The newest job's `train.metric` span."""
    (span,) = [s for s in TRACER.by_root("train")[-1]["spans"]
               if s["name"] == "train.metric"]
    return span


def _grown_again(m, fr, binned):
    """(trees, leaf [T, rows], bag weight [rounds, rows]) of the model's
    forest grown again from the keys it kept, round by round as the
    scan's body grows it (`_boost_shard`; K class trees a round from
    one bag for K classes), keeping what the grower returns beside a
    tree: every row's resting heap node."""
    from jax import lax

    from h2o_kubernetes_tpu.models.base import resolve_xy
    from h2o_kubernetes_tpu.runtime.mesh import global_mesh

    data = resolve_xy(fr, "y", materialize_x=False)
    plan = gbm_mod.boost_plan(m.params, data.distribution, data.nclasses,
                              F)
    tp, bp, K = plan.tp, plan.bp, plan.K
    keys = jax.random.wrap_key_data(
        jnp.asarray(m.tree_draws.keys, jnp.uint32))

    def shard(binned, y, w, keys):
        def body(_, kt):
            k_row, k_col, k_tree = jax.random.split(kt, 3)
            w_t, col_mask = core._round_sampling(bp, w, F, k_row, k_col)
            if K == 1:
                tree, leaf = core._grow_tree_shard(
                    binned, -y, jnp.ones_like(y), w_t, col_mask, k_tree, tp)
                return 0, (jax.tree.map(lambda a: a[None], tree),
                           leaf[None], w_t)
            g = -(y[:, None] == jnp.arange(K, dtype=y.dtype)[None, :]
                  ).astype(jnp.float32).T
            trees, leaf = jax.vmap(lambda gk, kk: core._grow_tree_shard(
                binned, gk, jnp.ones_like(gk), w_t, col_mask, kk, tp))(
                    g, jax.random.split(k_tree, K))
            return 0, (trees, leaf, w_t)

        _, (trees, leaf, w_t) = lax.scan(body, 0, keys)
        # [rounds, K, ...] -> [rounds * K, ...], class fastest
        flat = lambda a: a.reshape((-1,) + a.shape[2:])  # noqa: E731
        return jax.tree.map(flat, trees), flat(leaf), w_t

    return jax.jit(jax.shard_map(
        shard, mesh=global_mesh(),
        in_specs=(P(ROWS), P(ROWS), P(ROWS), P()),
        out_specs=(P(), P(None, ROWS), P(None, ROWS)),
        check_vma=False))(binned, data.y, data.w, keys)


@pytest.mark.parametrize("devices", [1, 8])
@pytest.mark.parametrize("sampling", ["bagged", "every_row"])
@pytest.mark.parametrize("kind,extra", [
    pytest.param("binomial", {}, id="binomial"),
    pytest.param("regression", {}, id="regression"),
    pytest.param("multinomial", {}, id="multinomial"),
    # nodes that stop above the last level: rows rest at inner nodes
    pytest.param("binomial", {"min_rows": 120.0}, id="binomial-early_stop"),
    # rows at the NA bin, routed by `na_left`
    pytest.param("binomial", {"na": True}, id="binomial-na_bins"),
])
def test_the_train_metric_is_read_off_the_leaves_the_grower_found(
        kind, extra, sampling, devices):
    """(a) the grower's resting node of every row of every tree is where
    `descend_tree` walks that row to — rows out of the bag (weight 0)
    and the padding included; (b) so the metric read off the sum the
    scan carried is BITWISE the one the walk gives."""
    extra = dict(extra)
    cols = _response_table(kind, na=extra.pop("na", False))
    kw = {"bagged": dict(sample_rate=0.632, mtries=-1),
          "every_row": dict(sample_rate=1.0)}[sampling]
    with _on(devices):
        fr = h2o.Frame.from_arrays(cols)
        m = DRF(ntrees=3, max_depth=4, nbins=NBINS, seed=7, **kw,
                **extra).train(y="y", training_frame=fr)
        assert _metric_span()["source"] == "carried"
        binned, walked = _walked_metric(m, fr)
        assert m.scoring_history[-1] == {"ntrees": 3, **walked}      # (b)
        trees, leaf, w_t = _grown_again(m, fr, binned)
        walked_to = gbm_mod._stack_leaf_nodes(m.trees, binned, 4, NBINS)
    for a, b in zip(trees[:-1], m.trees[:-1]):     # the same forest
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    leaf, w_t = np.asarray(leaf), np.asarray(w_t)
    np.testing.assert_array_equal(leaf, np.asarray(walked_to))       # (a)
    out_of_bag = (w_t == 0)[:, : fr.nrows].mean()
    if sampling == "bagged":
        assert 0.3 < out_of_bag < 0.45
    else:
        assert out_of_bag == 0
    if devices == 8:
        assert leaf.shape[1] > fr.nrows            # padding rows descend too
    if "min_rows" in extra:
        # some rows rest above the last level (heap nodes under 15)
        assert (leaf < 2 ** 4 - 1).any() and (leaf >= 2 ** 4 - 1).any()


def test_a_scan_goes_on_from_the_sum_it_is_given(mesh8):
    """Two trees and then two more, the second dispatch starting from
    the first's sum or from a walk of the first's trees (what a
    restart does), carry what four trees at once carry: the same sums
    in the same order, bitwise `_stack_predict` of the four."""
    from h2o_kubernetes_tpu.models.base import resolve_xy
    from h2o_kubernetes_tpu.models.tree.binning import fused_fit_bins

    fr = h2o.Frame.from_arrays(_response_table("binomial", rows=1000))
    plan = gbm_mod.boost_plan(
        DRF(ntrees=4, max_depth=4, nbins=NBINS, mtries=2).params,
        "bernoulli", 2, F)
    data = resolve_xy(fr, "y", materialize_x=False)
    _, binned = fused_fit_bins(fr, data.feature_names, NBINS)
    keys = core.round_keys(jax.random.key(2), 4)

    def grow(margin, keys):
        return core._boost_jit(*plan.operands(
            binned, data.y, data.w, margin, keys, None))

    zeros = jnp.zeros_like(data.y)
    at_once, trees = grow(zeros, keys)
    first, two = grow(zeros, keys[:2])
    walked = gbm_mod._stack_predict(two, binned, 4, NBINS)
    for start in (first, walked):
        after, more = grow(start, keys[2:])
        np.testing.assert_array_equal(np.asarray(after),
                                      np.asarray(at_once))
        np.testing.assert_array_equal(np.asarray(more.value),
                                      np.asarray(trees.value)[2:])
    np.testing.assert_array_equal(
        np.asarray(at_once),
        np.asarray(gbm_mod._stack_predict(trees, binned, 4, NBINS)))
    assert np.asarray(at_once).any()


@pytest.mark.parametrize("kind", ["binomial", "multinomial"])
def test_a_continued_forests_metric_covers_all_its_trees(mesh8, kind):
    """A forest continued from a checkpoint starts its carry at a walk
    of the checkpoint's trees, so its metric row is the whole forest's:
    bitwise the walk over all four trees, and read off the carry."""
    fr = h2o.Frame.from_arrays(_response_table(kind, rows=1200))
    kw = dict(max_depth=4, nbins=NBINS)
    # another seed for the continuation: with the first job's it draws
    # the first job's keys again (`test_checkpoint_carries_the_keys_on`
    # pins only the kept ones), and a forest's trees hang on their keys
    # alone (ROADMAP.md Queue 3)
    first = DRF(ntrees=2, seed=5, **kw).train(y="y", training_frame=fr)
    more = DRF(ntrees=4, seed=6, checkpoint=first, **kw).train(
        y="y", training_frame=fr)
    assert _metric_span()["source"] == "carried"
    K = 3 if kind == "multinomial" else 1
    assert more.ntrees == 4 * K
    np.testing.assert_array_equal(np.asarray(more.trees.value)[:2 * K],
                                  np.asarray(first.trees.value))
    _, walked = _walked_metric(more, fr)
    assert more.scoring_history[-1] == {"ntrees": 4, **walked}
    # and not the last two trees' alone
    _, of_first = _walked_metric(first, fr)
    assert of_first["train_logloss"] != walked["train_logloss"]
