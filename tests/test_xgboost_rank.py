"""LambdaMART on the boost plan (ISSUE 34): the grouped gradients, the
query layout and the trained trees against the plain reference
(`bench/reference/lambdamart_plain.py`, numpy float64, which imports
nothing of the program), one shard against eight, and everything that
refuses a grouped objective by name."""

import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu import metrics as M
from h2o_kubernetes_tpu.models import XGBoost
from h2o_kubernetes_tpu.models import gbm as gbm_mod
from h2o_kubernetes_tpu.models.tree import rank
from h2o_kubernetes_tpu.ops import histogram as hist_mod
from h2o_kubernetes_tpu.runtime.mesh import ROWS
from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY, TRACER

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(HERE), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import lambdamart_plain as ref  # noqa: E402

OBJECTIVES = ["rank:ndcg", "rank:pairwise"]


def _ragged(seed=0, sizes=None, F=6):
    """A ranking table of ragged queries: features, labels 0-4 that hang
    on two of them, ascending contiguous qid."""
    rng = np.random.default_rng(seed)
    if sizes is None:
        sizes = np.concatenate([[1, 300, 7, 129], rng.integers(1, 90, 36)])
    sizes = np.asarray(sizes)
    n = int(sizes.sum())
    X = rng.normal(size=(n, F)).astype(np.float32)
    raw = 1.1 * X[:, 0] - 0.7 * X[:, 1] + rng.normal(scale=0.5, size=n)
    y = np.clip(np.digitize(raw, [-1.2, -0.3, 0.6, 1.5]), 0, 4)
    qid = np.repeat(np.arange(len(sizes)), sizes)
    return X, y.astype(np.float32), qid, sizes


def _frame(X, y, qid, rows=None):
    cols = {f"f{j}": X[:, j] for j in range(X.shape[1])}
    cols["rel"] = y
    cols["qid"] = qid.astype(np.float32)
    if rows is not None:
        cols = {k: v[rows] for k, v in cols.items()}
    return h2o.Frame.from_arrays(cols)


def _one_device():
    return h2o.use_mesh(h2o.make_mesh(devices=jax.devices()[:1]))


def _grads(dist, margin, qid, y):
    """The program's (g, h) over the current mesh, in the frame's rows."""
    from h2o_kubernetes_tpu.runtime.mesh import global_mesh, row_sharding
    from h2o_kubernetes_tpu.runtime.mrtask import _padded_len

    mesh = global_mesh()
    n = len(y)
    padded = _padded_len(n, mesh.shape[ROWS])
    lay = rank.rank_layout(qid, y, padded, mesh)
    m = np.zeros(padded, dtype=np.float32)
    m[:n] = margin
    fn = jax.jit(jax.shard_map(
        lambda mm, gr: rank.rank_grad_hess(dist, mm, gr), mesh=mesh,
        in_specs=(P(ROWS), rank.groups_specs(lay.groups)),
        out_specs=(P(ROWS), P(ROWS))))
    g, h = fn(jax.device_put(m, row_sharding(mesh)), lay.groups)
    return np.asarray(g)[:n], np.asarray(h)[:n], lay


# (a) the gradients against the reference, ragged queries of 1-300 rows
@pytest.mark.parametrize("objective", OBJECTIVES)
@pytest.mark.parametrize("margin_kind", ["normal", "ties", "zeros"])
def test_gradients_match_the_reference(mesh8, objective, margin_kind):
    X, y, qid, sizes = _ragged(3)
    # a query whose labels are all equal gives zeros
    lo = int(sizes[:5].sum())
    y[lo: lo + sizes[5]] = 2.0
    rng = np.random.default_rng(9)
    margin = {"normal": rng.normal(size=len(y)),
              "ties": rng.integers(0, 3, len(y)) * 0.25,
              "zeros": np.zeros(len(y))}[margin_kind].astype(np.float32)
    g, h, lay = _grads(objective, margin, qid, y)
    starts, sz = ref.query_bounds(qid)
    assert (sz == sizes).all() and lay.queries == len(sizes)
    assert lay.max_query == 300
    wg, wh = ref.lambda_grads(margin.astype(np.float64),
                              y.astype(np.float64), starts, sz, objective)
    scale = np.abs(wg).max()
    assert np.abs(g - wg).max() < 2e-5 * scale
    assert np.abs(h - wh).max() < 2e-5 * np.abs(wh).max()
    assert (g[:1] == 0).all() and (h[:1] == 0).all()    # one document
    assert (g[lo: lo + sizes[5]] == 0).all()            # equal labels
    with _one_device():
        g1, h1, _ = _grads(objective, margin, qid, y)
    # queries straddle the eight shards' edges: bitwise one shard's
    assert (g1 == g).all() and (h1 == h).all()


def _train(fr, objective="rank:ndcg", **kw):
    args = dict(ntrees=3, max_depth=4, eta=0.3, nbins=32,
                min_child_weight=0.05, objective=objective, seed=0)
    args.update(kw)
    return XGBoost(**args).train(y="rel", training_frame=fr,
                                 group_column="qid")


def _splits(m):
    t = m.trees
    isp = np.asarray(t.is_split)
    return (isp, np.where(isp, np.asarray(t.split_feat), -1),
            np.where(isp, np.asarray(t.split_bin), -1))


# (b) a trained model against the reference, tree by tree
@pytest.mark.parametrize("objective", OBJECTIVES)
def test_trees_match_the_reference(mesh8, objective):
    X, y, qid, _ = _ragged(5)
    fr = _frame(X, y, qid)
    m = _train(fr, objective)
    edges = np.asarray(m.bin_spec.edges_matrix())[:, :30]
    assert (ref.bin_rows(X, edges)
            == np.asarray(fr.binned(m.bin_spec))[:len(y)]).all()
    params = {"max_depth": 4, "nbins": 32, "eta": 0.3, "reg_lambda": 1.0,
              "min_child_weight": 0.05, "objective": objective}
    want = ref.train(X, y, qid, params, 3, edges=edges)
    isp, feat, _ = _splits(m)
    cover = np.asarray(m.trees.cover)
    value = np.asarray(m.trees.value)
    gain = np.asarray(m.trees.gain)
    for t, tree in enumerate(want["trees"]):
        assert (isp[t] == tree["is_split"]).all()
        sp = tree["is_split"]
        assert (feat[t][sp] == tree["feat"][sp]).all()
        assert (cover[t] == tree["cover"]).all()          # exact
        thr = edges[feat[t][sp], np.asarray(m.trees.split_bin)[t][sp]]
        assert (thr == tree["thr"][sp]).all()
        reached = tree["cover"] > 0
        np.testing.assert_allclose(value[t][reached],
                                   tree["value"][reached], rtol=2e-4,
                                   atol=1e-6)
        np.testing.assert_allclose(gain[t][sp], tree["gain"][sp],
                                   rtol=2e-3)
    assert m.scoring_history[-1]["train_ndcg@10"] == pytest.approx(
        want["train_ndcg@10"], abs=1e-6)


# (c) one shard against eight; (d) a shuffled group column
def test_one_shard_eight_shards_and_a_shuffled_group_column(mesh8):
    X, y, qid, _ = _ragged(7)
    m8 = _train(_frame(X, y, qid))
    with _one_device():
        m1 = _train(_frame(X, y, qid))
    for a, b in zip(_splits(m1), _splits(m8)):
        assert (a == b).all()
    np.testing.assert_allclose(np.asarray(m1.trees.value),
                               np.asarray(m8.trees.value), rtol=1e-3,
                               atol=1e-5)
    # the queries' rows interleaved, each query's own order kept (ties
    # go by row order): the same gradients, the same trees
    rng = np.random.default_rng(1)
    new_q = qid[rng.permutation(len(qid))]
    rows = np.empty(len(qid), dtype=np.int64)
    rows[np.argsort(new_q, kind="stable")] = np.argsort(qid, kind="stable")
    assert (qid[rows] == new_q).all() and (np.diff(new_q) < 0).any()
    ms = _train(_frame(X, y, qid, rows))
    for a, b in zip(_splits(ms), _splits(m8)):
        assert (a == b).all()
    assert (np.asarray(ms.trees.cover) == np.asarray(m8.trees.cover)).all()
    assert ms.scoring_history[-1]["train_ndcg@10"] == pytest.approx(
        m8.scoring_history[-1]["train_ndcg@10"], abs=1e-6)


# (e) predict_raw reproduces the margin the metric read; (f) save/load
def test_predict_reproduces_the_training_margin_and_survives_save(
        mesh8, monkeypatch, tmp_path):
    X, y, qid, _ = _ragged(11)
    fr = _frame(X, y, qid)
    seen = []
    real = gbm_mod._margin_metrics

    def spy(dist, margin, *a, **kw):
        seen.append(np.asarray(margin))
        return real(dist, margin, *a, **kw)

    monkeypatch.setattr(gbm_mod, "_margin_metrics", spy)
    # at eta 0.3 XLA:CPU contracts the scan's `margin + eta * leaf` into
    # one fused multiply-add, which rounds once where the stored leaves
    # (eta * leaf, rounded) summed by predict round twice: an ulp
    m = _train(fr, ntrees=5)
    score = np.asarray(m.predict_raw(fr))
    assert np.abs(seen[-1][:len(y)] - score).max() <= 2.4e-7
    # a power of two scales exactly: the same leaves in the same order
    m = _train(fr, ntrees=5, eta=0.5)
    score = np.asarray(m.predict_raw(fr))
    assert score.shape == (len(y),)
    assert (seen[-1][:len(y)] == score).all()               # bitwise
    # the train metric is ranked on the device over the query layout
    # (float32 a query), `model_performance` by `metrics.ndcg`
    assert m.scoring_history[-1]["train_ndcg@10"] == pytest.approx(
        M.ndcg(y, score, qid, k=10), abs=1e-6)
    assert m.model_performance(fr, "rel")["ndcg@10"] == M.ndcg(
        y, score, qid, k=10)
    assert "qid" not in m.feature_names and m._group_column == "qid"
    m2 = h2o.load_model(h2o.save_model(m, str(tmp_path / "rank.model")))
    assert m2._group_column == "qid" and m2.distribution == "rank:ndcg"
    assert (np.asarray(m2.predict_raw(fr)) == score).all()
    assert m2.model_performance(fr, "rel") == m.model_performance(fr, "rel")


def test_a_ranking_model_pickled_before_the_boost_plan_still_loads(mesh8):
    """`tests/data/rank_model_pr33.model`: written by the parent of
    ISSUE 34 (`_train_rank`'s host loop) on `_rank_frame(15, 8, 7)`."""
    from test_xgboost import _rank_frame

    fr, rel, group = _rank_frame(n_groups=15, docs=8, seed=7)
    data = os.path.join(HERE, "data")
    m = h2o.load_model(os.path.join(data, "rank_model_pr33.model"))
    assert m.algo == "xgboost" and m.distribution == "rank:ndcg"
    assert m._group_column == "qid" and m.ntrees == 3
    want = np.load(os.path.join(data, "rank_model_pr33_scores.npy"))
    assert (np.asarray(m.predict_raw(fr)) == want).all()
    assert m.model_performance(fr, "rel")["ndcg@10"] == pytest.approx(
        m.scoring_history[-1]["train_ndcg@10"], abs=1e-12)


# (g) what cannot carry a grouped objective refuses it by name
@pytest.mark.parametrize("case,name", [
    ("efb", "an EFB-bundled frame"),
    ("goss", r"GOSS \(H2O_TPU_GOSS\)"),
    ("ooc", "the out-of-core path"),
    ("checkpoint", "checkpoint restart"),
    ("offset", "offset_column"),
    ("cv", "cross-validation"),
    ("sets", "the XGBoost facade"),
])
def test_refusals_name_what_cannot_carry_a_grouped_objective(
        mesh8, monkeypatch, case, name):
    X, y, qid, _ = _ragged(13, sizes=[5, 9, 30, 2, 14])
    fr = _frame(X, y, qid)
    kw, train_kw = {}, {}
    if case == "efb":
        p = XGBoost(objective="rank:ndcg").params
        plan = gbm_mod.boost_plan(p, "rank:ndcg", 1, 6)
        assert plan.grouped and plan.mode == "single"
        with pytest.raises(ValueError, match=name):
            plan.validate("xgboost", efb=True)
        return
    if case == "goss":
        monkeypatch.setenv("H2O_TPU_GOSS", "1")
    elif case == "ooc":
        monkeypatch.setenv("H2O_TPU_OOC", "1")
    elif case == "checkpoint":
        kw.update(checkpoint=_train(fr, ntrees=1), max_depth=4, nbins=32)
    elif case == "offset":
        fr["off"] = h2o.Vec.from_numpy(np.zeros(len(y), np.float32), "off")
        train_kw["offset_column"] = "off"
    elif case == "cv":
        kw["nfolds"] = 2
    elif case == "sets":
        fr["cat"] = h2o.Vec.from_numpy(
            (np.arange(len(y)) % 3).astype(np.int32), domain=list("abc"))
        kw["categorical_encoding"] = "enum"
    with pytest.raises(ValueError, match=name):
        XGBoost(ntrees=2, objective="rank:ndcg", **kw).train(
            y="rel", training_frame=fr, group_column="qid", **train_kw)


def test_a_wide_ranking_frame_skips_the_efb_planning_pass(
        mesh8, monkeypatch):
    from h2o_kubernetes_tpu.models.tree import efb

    X, y, qid, _ = _ragged(17, sizes=[20, 40, 9, 31], F=70)
    assert efb.efb_eligible(70, None)

    def never(*a, **kw):
        raise AssertionError("the EFB planning pass ran")

    monkeypatch.setattr(efb, "fit_plan_cached", never)
    m = _train(_frame(X, y, qid), ntrees=1, max_depth=2)
    assert len(m.feature_names) == 70


# (h) a 136-column frame through the Pallas path (interpret mode)
def test_a_136_column_frame_is_not_padded_to_192(mesh8):
    for ht in (2, 4, 8, 16, 32, 64, 128, 256):
        fg, padded = hist_mod._feature_groups(136, 3, ht)
        assert padded % fg == 0 and fg % 8 == 0 and 136 <= padded < 150
    # within the 64-column cap a frame keeps the group it had
    for F in (8, 28, 48, 64):
        for C in (2, 3):
            for ht in (2, 32, 64, 128, 256):
                cap = min(F, 64, max(1, (3 << 20) // (C * ht * 512)))
                old = (F, F) if cap >= F else (
                    max(8, cap // 8 * 8),
                    -(-F // max(8, cap // 8 * 8)) * max(8, cap // 8 * 8))
                assert hist_mod._feature_groups(F, C, ht) == old
    rng = np.random.default_rng(2)
    r, F, n_nodes, n_bins = 512, 136, 4, 16
    binned = rng.integers(0, n_bins, (r, F)).astype(np.uint8)
    rel = rng.integers(-1, n_nodes, r).astype(np.int32)
    g, h = rng.normal(size=(2, r)).astype(np.float32)
    w = np.ones(r, np.float32)
    # shrink the out budget so that the small test shape is grouped
    import unittest.mock as mock
    with mock.patch.object(hist_mod, "_OUT_BUDGET", 3 * 4 * 512 * 30):
        assert hist_mod._feature_groups(F, 3, 1) == (8, 136)
        got = hist_mod.build_histogram(binned, rel, g, h, w, n_nodes,
                                       n_bins, impl="pallas")
    want = hist_mod.build_histogram(binned, rel, g, h, w, n_nodes, n_bins,
                                    impl="segment")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# (i) the two pair counters, on a table whose sum of n_q^2 is known
def test_pair_counters_and_root_attributes(mesh8):
    sizes = [3, 8, 9, 17, 100, 1]
    X, y, qid, _ = _ragged(19, sizes=sizes)
    ctr = REGISTRY.counter("h2o_train_rank_pairs_total", label="kind")
    before = {k: ctr.value(k) for k in ("real", "slots")}
    m = _train(_frame(X, y, qid), ntrees=2)
    real = sum(s * s for s in sizes)
    # classes of 8 (3, 8, 1), 16 (9), 24 (17) and 128 (100)
    slots = 3 * 8 * 8 + 16 * 16 + 24 * 24 + 128 * 128
    assert ctr.value("real") - before["real"] == 2 * real
    assert ctr.value("slots") - before["slots"] == 2 * slots
    job = TRACER.by_root("train")[-1]
    names = [s["name"] for s in job["spans"]]
    for name in ("train", "train.prepare", "train.group_layout",
                 "train.bin", "train.init_margin", "train.boost",
                 "train.dispatch", "train.read_model", "train.metric"):
        assert name in names, name
    root = next(s for s in job["spans"] if s["name"] == "train")
    assert root["objective"] == "rank:ndcg" and root["queries"] == 6
    assert root["max_query"] == 100 and root["features"] == 6
    metric = next(s for s in job["spans"] if s["name"] == "train.metric")
    assert metric["source"] == "margin"
    assert m.ntrees == 2


def _dispatched(monkeypatch, fr, **kw):
    sent = []
    real = gbm_mod._BOOST_PROGRAMS["single"]

    def call(*a):
        sent.append(a)
        return real(*a)

    monkeypatch.setitem(gbm_mod._BOOST_PROGRAMS, "single", call)
    _train(fr, **kw)
    monkeypatch.undo()
    return real, sent


# (j) the program hangs on the multiset of query sizes alone
def test_two_orders_of_one_multiset_of_sizes_lower_to_one_program(
        mesh8, monkeypatch):
    sizes = np.array([3, 40, 9, 17, 100, 1, 9, 64])
    texts = []
    for seed in (0, 1):
        order = np.random.default_rng(seed).permutation(len(sizes))
        X, y, qid, _ = _ragged(23 + seed, sizes=sizes[order])
        fn, sent = _dispatched(monkeypatch, _frame(X, y, qid), ntrees=2)
        assert len(sent) == 1
        texts.append(fn.lower(*sent[0]).as_text())
    assert texts[0] == texts[1]
    import re
    scopes = {part for name in re.findall(
        r'loc\("([^"]+)"', fn.lower(*sent[0]).as_text(debug_info=True))
        for part in name.split("/")}
    assert {"grad_hess", "rank_sort", "rank_pairs"} <= scopes


def test_compile_ahead_lowers_what_a_ranking_job_dispatches(
        mesh8, monkeypatch):
    X, y, qid, _ = _ragged(29)
    fr = _frame(X, y, qid)
    est = XGBoost(ntrees=3, max_depth=3, nbins=16, objective="rank:ndcg")
    assert est.compile_ahead_lowerings("rel", fr) == []    # no query
    lowered = []
    monkeypatch.setattr(gbm_mod, "_aot",
                        lambda fn, *a: lowered.append((fn, a)))
    for thunk in est.compile_ahead_lowerings("rel", fr,
                                             group_column="qid"):
        thunk()
    monkeypatch.undo()
    fn, sent = _dispatched(monkeypatch, fr, ntrees=3, max_depth=3,
                           nbins=16, min_child_weight=1.0, eta=0.3)
    assert len(lowered) == 1 and lowered[0][0] is fn and len(sent) == 1

    def leaf(x):
        if not hasattr(x, "dtype"):
            return x
        return (x.shape, str(x.dtype))

    la, ta = jax.tree.flatten(lowered[0][1])
    sa, tb = jax.tree.flatten(sent[0])
    assert ta == tb
    assert [leaf(x) for x in la] == [leaf(x) for x in sa]
    for a, b in zip(la, sa):
        if hasattr(a, "sharding") and a.sharding is not None \
                and isinstance(b, jax.Array) and b.committed:
            assert a.sharding.is_equivalent_to(b.sharding, len(a.shape))
    assert fn.lower(*lowered[0][1]).as_text() \
        == fn.lower(*sent[0]).as_text()


@pytest.mark.parametrize("k", [1, 3, 10])
def test_ndcg_ranks_every_query_at_once_as_the_loop_does(mesh8, k):
    """`metrics.ndcg` against the reference's loop a query: ragged
    sizes, ties in the score, an all-zero query, shuffled rows."""
    X, y, qid, sizes = _ragged(31)
    y[: sizes[0] + sizes[1]] = 0.0                  # two queries of zeros
    rng = np.random.default_rng(4)
    score = rng.integers(0, 5, len(y)) * 0.5        # heavy ties
    starts, sz = ref.query_bounds(qid)
    want = ref.ndcg_at(score, y.astype(np.float64), starts, sz, k)
    assert M.ndcg(y, score, qid, k=k) == pytest.approx(want, abs=1e-12)
    # a group column that is not contiguous: each query's order kept
    new_q = qid[rng.permutation(len(qid))]
    rows = np.empty(len(qid), dtype=np.int64)
    rows[np.argsort(new_q, kind="stable")] = np.argsort(qid, kind="stable")
    assert M.ndcg(y[rows], score[rows], new_q, k=k) == pytest.approx(
        want, abs=1e-12)
    assert M.ndcg(y[:0], score[:0], qid[:0]) == 0.0
    # the train metric's form: ranked on the device over the layout
    from h2o_kubernetes_tpu.runtime.mrtask import _padded_len
    for yy, ss, qq in ((y, score, qid), (y[rows], score[rows], new_q)):
        padded = _padded_len(len(yy), 8)
        lay = rank.rank_layout(qq, yy, padded)
        m = np.zeros(padded, np.float32)
        m[:len(yy)] = ss
        assert rank.ndcg_at(m, lay.groups, k) == pytest.approx(
            want, abs=1e-6)


def test_query_runs_takes_one_pass_where_the_column_is_contiguous(
        monkeypatch):
    qid = np.repeat([5, 2, 9, 7], [3, 1, 4, 2])     # contiguous, unsorted
    monkeypatch.setattr(np, "argsort", None)        # would fail if called
    order, starts, sizes = rank.query_runs(qid)
    monkeypatch.undo()
    assert order is None and starts.tolist() == [0, 3, 4, 8]
    assert sizes.tolist() == [3, 1, 4, 2]
    order, starts, sizes = rank.query_runs(np.array([1, 2, 1, 2, 2]))
    assert order.tolist() == [0, 2, 1, 3, 4] and sizes.tolist() == [2, 3]
    assert rank.CLASS_LENGTHS[:8].tolist() == [8, 16, 24, 32, 48, 64, 96,
                                               128]
    # a class's batches: the fewest the slot budget allows, evenly filled
    assert rank._class_shape(1000, 128) == (4, 250)
    assert rank._class_shape(3, 2048) == (3, 1)
