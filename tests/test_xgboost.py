"""XGBoost-hist estimator tests (config #3: hist + lambdarank)."""

import os

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu import metrics as M
from h2o_kubernetes_tpu.models import XGBoost


def _binary_frame(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    x = {f"x{i}": rng.normal(size=n).astype(np.float32) for i in range(5)}
    logit = 1.5 * x["x0"] - 1.0 * x["x1"] + 0.5 * x["x2"] * x["x3"]
    y = (logit + rng.normal(scale=0.7, size=n)) > 0
    x["y"] = np.where(y, "yes", "no")
    return h2o.Frame.from_arrays(x)


def _rank_frame(n_groups=60, docs=25, seed=0):
    """Synthetic LTR data: relevance 0-4 driven by two features."""
    rng = np.random.default_rng(seed)
    n = n_groups * docs
    f1 = rng.normal(size=n).astype(np.float32)
    f2 = rng.normal(size=n).astype(np.float32)
    f3 = rng.normal(size=n).astype(np.float32)  # noise
    raw = 1.2 * f1 - 0.8 * f2 + rng.normal(scale=0.4, size=n)
    rel = np.clip(np.digitize(raw, [-1.5, -0.5, 0.5, 1.5]), 0, 4)
    group = np.repeat(np.arange(n_groups), docs)
    fr = h2o.Frame.from_arrays({
        "f1": f1, "f2": f2, "f3": f3,
        "rel": rel.astype(np.float32), "qid": group.astype(np.float32)})
    return fr, rel, group


def test_binary_classification(mesh8):
    fr = _binary_frame()
    m = XGBoost(ntrees=20, max_depth=4, learn_rate=0.3, seed=1).train(
        y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["auc"] > 0.9
    assert m.algo == "xgboost"


def test_objective_aliases(mesh8):
    fr = _binary_frame(n=1000)
    m = XGBoost(ntrees=5, objective="binary:logistic").train(
        y="y", training_frame=fr)
    assert m.distribution == "bernoulli"
    with pytest.raises(ValueError):
        XGBoost(objective="nope:nope")
    with pytest.raises(ValueError):
        XGBoost(booster="dart")


def test_regression_squarederror(mesh8):
    rng = np.random.default_rng(2)
    n = 3000
    x0 = rng.normal(size=n).astype(np.float32)
    x1 = rng.uniform(-2, 2, size=n).astype(np.float32)
    y = 3.0 * x0 + np.sin(2 * x1) + rng.normal(scale=0.1, size=n)
    fr = h2o.Frame.from_arrays({"x0": x0, "x1": x1, "y": y})
    m = XGBoost(ntrees=40, max_depth=5, learn_rate=0.3,
                objective="reg:squarederror").train(y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["r2"] > 0.95


@pytest.mark.slow
def test_min_child_weight_regularizes(mesh8):
    """High hessian floor must forbid tiny leaves (fewer splits)."""
    fr = _binary_frame(n=600, seed=3)
    loose = XGBoost(ntrees=5, max_depth=6, min_child_weight=0.0,
                    seed=1).train(y="y", training_frame=fr)
    tight = XGBoost(ntrees=5, max_depth=6, min_child_weight=30.0,
                    seed=1).train(y="y", training_frame=fr)
    n_loose = int(np.asarray(loose.trees.is_split).sum())
    n_tight = int(np.asarray(tight.trees.is_split).sum())
    assert n_tight < n_loose


def test_lambdarank_ndcg_improves(mesh8):
    fr, rel, group = _rank_frame()
    m = XGBoost(ntrees=30, max_depth=4, learn_rate=0.3,
                objective="rank:ndcg", seed=0).train(
        y="rel", training_frame=fr, group_column="qid")
    score = m.predict_raw(fr)
    got = M.ndcg(rel, score, group, k=10)
    random_ndcg = M.ndcg(rel, np.random.default_rng(0).normal(size=len(rel)),
                         group, k=10)
    ideal_on_f1 = M.ndcg(rel, fr.vec("f1").to_numpy(), group, k=10)
    assert got > random_ndcg + 0.1
    assert got > ideal_on_f1           # beats the single best raw feature
    perf = m.model_performance(fr, "rel")
    assert perf["ndcg@10"] == pytest.approx(got, abs=1e-6)


def test_rank_pairwise_runs(mesh8):
    fr, rel, group = _rank_frame(n_groups=20, docs=10, seed=5)
    m = XGBoost(ntrees=10, objective="rank:pairwise", seed=0).train(
        y="rel", training_frame=fr, group_column="qid")
    score = m.predict_raw(fr)
    assert M.ndcg(rel, score, group) > M.ndcg(
        rel, np.zeros_like(rel), group) - 1e-9
    # group column must not leak into features
    assert "qid" not in m.feature_names


def test_rank_with_enum_relevance(mesh8):
    """Graded relevance stored as a categorical must still rank (and
    score) as a single-output model, not take the multinomial path."""
    fr, rel, group = _rank_frame(n_groups=15, docs=8, seed=7)
    fr["rel_cat"] = h2o.Vec.from_numpy(
        rel.astype(np.int32), domain=[str(i) for i in range(5)])
    m = XGBoost(ntrees=3, objective="rank:ndcg", seed=0).train(
        y="rel_cat", training_frame=fr, x=["f1", "f2", "f3"],
        group_column="qid")
    score = m.predict_raw(fr)          # crashed before nclasses fix
    assert score.shape == (fr.nrows,)


def test_h2o_param_aliases(mesh8):
    """H2O spellings (min_rows, sample_rate, …) map to XGBoost params."""
    m = XGBoost(ntrees=2, min_rows=5.0, sample_rate=0.8,
                col_sample_rate_per_tree=0.9)
    assert m.params.min_child_weight == 5.0
    assert m.params.sample_rate == 0.8
    assert m.params.col_sample_rate_per_tree == 0.9


def test_rank_requires_group(mesh8):
    fr, _, _ = _rank_frame(n_groups=5, docs=5)
    with pytest.raises(ValueError, match="group_column"):
        XGBoost(ntrees=2, objective="rank:ndcg").train(
            y="rel", training_frame=fr)


def test_ndcg_metric_known_answer():
    # two groups; perfect ordering in g0, inverted in g1
    y = np.array([2, 1, 0, 0, 1, 2])
    s = np.array([3.0, 2.0, 1.0, 3.0, 2.0, 1.0])
    g = np.array([0, 0, 0, 1, 1, 1])
    perfect = M.ndcg(y[:3], s[:3], g[:3])
    assert perfect == pytest.approx(1.0)
    mixed = M.ndcg(y, s, g)
    assert 0.5 < mixed < 1.0


# ---------------------------------------------------------------------------
# multi:softprob, K class trees a round (ISSUE 38): against the plain
# reference `bench/reference/gbm_softmax_plain.py` (numpy float64, which
# imports nothing of the program), over rows of the benchmark's own
# Covertype-like table
# ---------------------------------------------------------------------------

def _bench(*parts):
    import importlib.util
    import sys

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    for p in (bench, os.path.join(bench, "compare")):
        if p not in sys.path:       # a comparison imports its neighbours
            sys.path.insert(0, p)
    if not parts:
        from reference import gbm_softmax_plain

        return gbm_softmax_plain
    spec = importlib.util.spec_from_file_location(
        parts[-1], os.path.join(bench, *parts) + ".py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MULTI = dict(objective="multi:softprob", max_depth=4, nbins=32, eta=0.3,
             reg_lambda=1.0, min_child_weight=1.0)


def _covtype(K, rows=4000, seed=38):
    """(X [rows, 54], y 0..K-1, the frame): K 7 is the table's own
    response, K 3 its classes folded (spruce / lodgepole / the rest)."""
    table = _bench("tables", "covtype_like")
    X, y = table.covtype_like(rows, seed)
    y = np.minimum(y, K - 1)
    cols = table.as_columns(X, y)
    return np.ascontiguousarray(X.T), y, h2o.Frame.from_arrays(cols)


def _class_trees(m):
    t = m.trees
    isp = np.asarray(t.is_split)
    return {"is_split": isp,
            "feat": np.where(isp, np.asarray(t.split_feat), -1),
            "bin": np.where(isp, np.asarray(t.split_bin), -1),
            "cover": np.asarray(t.cover), "value": np.asarray(t.value),
            "gain": np.asarray(t.gain)}


@pytest.mark.parametrize("K", [3, 7])
def test_class_trees_match_the_softmax_reference(mesh8, K):
    """Every class tree of 3 rounds, followed by the reference over the
    table as the benchmark's comparison follows it
    (`bench/compare/gbm_softmax.py`): the margin goes forward with the
    model's leaves and the reference takes its own float64 softmax
    gradients at every round's start. Held so and not node by node
    against trees the reference grows itself, because splits TIE on
    this table: in a node that holds two wilderness areas their two
    0/1 columns part the rows alike, mirrored, at the same gain, and
    float32 and float64 break the tie differently (the prior, the root
    of every first-round tree and the final logloss are held to the
    reference's own model too). Tolerances, each about four times the
    largest of three seeds' readings here: every node's cover exactly
    (integers); the worst node's value -eta G/(H + lambda) to 5e-4 of
    the larger of its own and the tree's median |value| — the program
    sums float32 gradients in float32, the reference float64 ones in
    float64, and a small right child's sums are its parent's less its
    sibling's; the worst split's gain, a difference of three such
    terms, to 2e-3; no gain left on the table (regret 1e-6: at 4,000
    rows the program's sample quantiles ARE the reference's cuts); the
    logloss, a mean of float32 logs, to 1e-6 relative."""
    ref = _bench()
    cmp = _bench("compare", "gbm_softmax")
    X, y, fr = _covtype(K)
    m = XGBoost(ntrees=3, seed=0, **MULTI).train(y="y", training_frame=fr)
    assert m.distribution == "multinomial" and m.ntrees == 3 * K
    model = cmp.neutral_model(m)
    assert [len(r) for r in model["trees"]] == [K] * 3
    got = cmp.compare(model, X, y, {"params": MULTI, "classes": K},
                      {"check_rounds": 3, "regret_rounds": 3}, seed=0,
                      blocks=2)
    assert got["cover_gap"] == 0
    assert got["value_gap_worst"] < 5e-4 and got["value_gap"] < 1e-5
    assert got["gain_gap_worst"] < 2e-3 and got["gain_gap"] < 1e-4
    assert got["regret_gap"] < 1e-6 and got["logloss_gap"] < 1e-6
    # the reference's own model, on the program's cuts
    edges = np.asarray(m.bin_spec.edges_matrix())[:, :30]
    assert (ref.bin_rows(X, edges)
            == np.asarray(fr.binned(m.bin_spec))[:len(y)]).all()
    want = ref.train(X, y, dict(MULTI), 3, K, edges=edges)
    np.testing.assert_allclose(model["init"], want["init"], rtol=1e-6)
    for mine, tree in zip(model["trees"][0], want["trees"][0]):
        assert mine["is_split"][0] and tree["is_split"][0]
        assert mine["cover"][0] == tree["cover"][0] == len(y)
        # (from the classes' log shares the root's G is 0 but for
        # rounding: its value says nothing)
        assert mine["gain"][0] == pytest.approx(tree["gain"][0], rel=1e-4)
        assert abs(mine["value"][0]) < 1e-6
    assert model["train_logloss"] == pytest.approx(
        want["train_logloss"], rel=1e-3)


@pytest.mark.parametrize("K", [3, 7])
def test_vmapped_and_mapped_class_trees_are_the_same(mesh8, monkeypatch,
                                                     K):
    """Past `core._MULTI_HIST_BUDGET` the K trees of a round grow a
    class at a time under `lax.map`: the same trees as under `vmap`,
    and the job says which way it went — the `train` root's
    `class_batch`, and `h2o_train_class_trees_total{kind}`."""
    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY, TRACER

    _, _, fr = _covtype(K, rows=2000)
    ctr = REGISTRY.counter("h2o_train_class_trees_total", label="kind")

    def train():
        before = {k: ctr.value(k) for k in ("batched", "mapped")}
        m = XGBoost(ntrees=2, seed=0, **MULTI).train(
            y="y", training_frame=fr)
        root = TRACER.by_root("train")[-1]["spans"][0]
        return m, root, {k: ctr.value(k) - v for k, v in before.items()}

    mv, root, grown = train()
    assert (root["classes"], root["rounds"], root["class_batch"]) == \
        (K, 2, "vmap")
    assert grown == {"batched": 2 * K, "mapped": 0}
    monkeypatch.setattr(core, "_MULTI_HIST_BUDGET", 1)
    mm, root, grown = train()
    assert (root["classes"], root["rounds"], root["class_batch"]) == \
        (K, 2, "map")
    assert grown == {"batched": 0, "mapped": 2 * K}
    a, b = _class_trees(mv), _class_trees(mm)
    for k in ("is_split", "feat", "bin", "cover"):
        assert (a[k] == b[k]).all(), k
    np.testing.assert_allclose(a["value"], b["value"], rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(a["gain"], b["gain"], rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("K", [3, 7])
def test_a_k_class_job_is_one_executable(mesh8, monkeypatch, K):
    """A 3-round job of 3 dispatches compiles `_boost_multi_jit` ONCE:
    the first dispatch's `[rows, K]` margin lies as every later one's
    does, sharded by rows (it was `_init_margin`'s replicated
    broadcast: a second executable of the same program, PERF.md section
    7 "Open since PR 30 (a)")."""
    from h2o_kubernetes_tpu.models import gbm as gbm_mod
    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.runtime.backend import (compile_watch_snapshot,
                                                    start_compile_watch)

    h2o.init()
    start_compile_watch()
    # (shapes no other test trains, so that the one compile is this job's)
    _, _, fr = _covtype(K, rows=1234 + K)
    monkeypatch.setattr(gbm_mod, "_DISPATCH_BUDGET", 1.0)
    sent = []
    real = gbm_mod._BOOST_PROGRAMS["multi"]
    monkeypatch.setitem(gbm_mod._BOOST_PROGRAMS, "multi",
                        lambda *a: sent.append(a[3].sharding) or real(*a))

    def compiled():
        """(executables jit holds for the program, compiles the watch
        credited to it — under `other` once a long-lived process has
        filled the watch's table of names)."""
        by = compile_watch_snapshot()["by_program"]
        return (core._boost_multi_jit._cache_size(),
                sum(by.get(k, {}).get("compiles", 0)
                    for k in ("_boost_multi_jit", "other")))

    before = compiled()
    m = XGBoost(ntrees=3, seed=0, **dict(MULTI, nbins=29)).train(
        y="y", training_frame=fr)
    assert m.ntrees == 3 * K and len(sent) == 3
    assert all(s.is_equivalent_to(sent[0], 2) for s in sent)
    after = compiled()
    assert after[0] - before[0] == 1
    # (the job's other fresh shapes — binning, the metric — may land
    # under `other` beside it)
    assert after[1] - before[1] >= 1
