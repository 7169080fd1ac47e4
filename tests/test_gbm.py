import numpy as np
import pytest

from h2o_kubernetes_tpu import Frame
from h2o_kubernetes_tpu import metrics as M
from h2o_kubernetes_tpu.models import GBM


def _binary_data(n=4000, seed=0):
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    x3 = rng.integers(0, 4, size=n)
    logit = 1.5 * x1 - 2.0 * (x2 ** 2) + 1.2 * (x3 == 2) + \
        rng.normal(scale=0.3, size=n)
    y = (logit > 0).astype(int)
    fr = Frame.from_arrays({
        "x1": x1, "x2": x2,
        "x3": np.array(["a", "b", "c", "d"])[x3],
        "y": np.array(["no", "yes"])[y],
    })
    X = np.stack([x1, x2, x3.astype(float)], axis=1)
    return fr, X, y


def test_gbm_binary_auc_beats_sklearn_parity(mesh8):
    fr, X, y = _binary_data()
    m = GBM(ntrees=40, max_depth=4, learn_rate=0.2, seed=1).train(
        y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["auc"] > 0.97
    assert perf["logloss"] < 0.25

    from sklearn.ensemble import HistGradientBoostingClassifier
    sk = HistGradientBoostingClassifier(
        max_iter=40, max_depth=4, learning_rate=0.2,
        categorical_features=[2]).fit(X, y)
    sk_auc = M.roc_auc(y, sk.predict_proba(X)[:, 1])
    assert perf["auc"] > sk_auc - 0.01  # parity with sklearn hist-GBM


def test_gbm_regression(mesh8):
    rng = np.random.default_rng(3)
    n = 3000
    x1 = rng.normal(size=n)
    x2 = rng.uniform(-2, 2, size=n)
    y = 3.0 * x1 + np.sin(2 * x2) * 2 + rng.normal(scale=0.1, size=n)
    fr = Frame.from_arrays({"x1": x1, "x2": x2, "y": y})
    m = GBM(ntrees=60, max_depth=4, learn_rate=0.2, seed=2).train(
        y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["rmse"] < 0.4
    assert perf["r2"] > 0.97


def test_gbm_multinomial(mesh8):
    rng = np.random.default_rng(4)
    n = 3000
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    cls = np.where(x1 + x2 > 0.7, 2, np.where(x1 - x2 > 0.3, 1, 0))
    fr = Frame.from_arrays({
        "x1": x1, "x2": x2,
        "y": np.array(["lo", "mid", "hi"])[cls]})
    m = GBM(ntrees=20, max_depth=4, learn_rate=0.3, seed=5).train(
        y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["accuracy"] > 0.93
    pred = m.predict(fr)
    assert set(pred.names) == {"predict", "plo", "pmid", "phi"}
    probs = np.stack([pred[c].to_numpy() for c in ("plo", "pmid", "phi")], 1)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)


def test_gbm_na_handling(mesh8):
    rng = np.random.default_rng(6)
    n = 3000
    x1 = rng.normal(size=n)
    # y depends on whether x1 is missing — the learned NA direction must
    # pick this up
    miss = rng.uniform(size=n) < 0.3
    y = np.where(miss, 1, (x1 > 0).astype(int))
    x1 = np.where(miss, np.nan, x1)
    fr = Frame.from_arrays({"x1": x1, "noise": rng.normal(size=n),
                            "y": np.array(["n", "p"])[y]})
    m = GBM(ntrees=20, max_depth=3, learn_rate=0.3, seed=7).train(
        y="y", training_frame=fr)
    assert m.model_performance(fr, "y")["auc"] > 0.98


def test_gbm_sampling_reproducible(mesh8):
    fr, X, y = _binary_data(n=2000, seed=8)
    kw = dict(ntrees=15, max_depth=3, sample_rate=0.7,
              col_sample_rate_per_tree=0.8, seed=42)
    a = GBM(**kw).train(y="y", training_frame=fr)
    b = GBM(**kw).train(y="y", training_frame=fr)
    np.testing.assert_array_equal(a.predict_raw(fr), b.predict_raw(fr))


def test_gbm_weights_column(mesh8):
    rng = np.random.default_rng(9)
    n = 2000
    x = rng.normal(size=n)
    y = (x > 0).astype(int)
    w = np.where(np.arange(n) < 1000, 1.0, 0.0)  # second half ignored
    y2 = y.copy()
    y2[1000:] = 1 - y2[1000:]  # corrupt ignored rows
    fr = Frame.from_arrays({"x": x, "w": w,
                            "y": np.array(["a", "b"])[y2]})
    m = GBM(ntrees=10, max_depth=2, seed=1).train(
        y="y", training_frame=fr, weights_column="w")
    sub = Frame.from_arrays({"x": x[:1000],
                             "y": np.array(["a", "b"])[y[:1000]]})
    assert m.model_performance(sub, "y")["auc"] > 0.99


def test_varimp_ranks_signal_over_noise(mesh8):
    rng = np.random.default_rng(10)
    n = 3000
    sig = rng.normal(size=n)
    noise = rng.normal(size=n)
    y = (sig > 0).astype(int)
    fr = Frame.from_arrays({"sig": sig, "noise": noise,
                            "y": np.array(["n", "p"])[y]})
    m = GBM(ntrees=10, max_depth=3, seed=2).train(y="y", training_frame=fr)
    vi = m.varimp()
    assert vi["sig"] == 1.0
    assert vi["noise"] < 0.05


def test_predict_remaps_enum_domains(mesh8):
    rng = np.random.default_rng(11)
    n = 3000
    c = np.array(["a", "b", "c", "d"])[rng.integers(0, 4, size=n)]
    y = np.where(np.isin(c, ["c", "d"]), "p", "n")  # y determined by c
    fr = Frame.from_arrays({"c": c, "noise": rng.normal(size=n), "y": y})
    m = GBM(ntrees=10, max_depth=2, seed=0).train(y="y", training_frame=fr)
    # scoring frame whose enum only contains b, d: local codes differ
    c2 = np.array(["b", "d"])[rng.integers(0, 2, size=200)]
    fr2 = Frame.from_arrays({"c": c2, "noise": rng.normal(size=200)})
    out = m.predict_raw(fr2)
    # all 'd' rows must score high, all 'b' rows low
    assert out[c2 == "d", 1].min() > 0.8
    assert out[c2 == "b", 1].max() < 0.2


def test_nbins_validation(mesh8):
    fr = Frame.from_arrays({"x": np.arange(100.0),
                            "y": np.arange(100.0)})
    with pytest.raises(ValueError, match="n_bins"):
        GBM(ntrees=2, nbins=512).train(y="y", training_frame=fr)


def test_scoring_history(mesh8):
    fr, X, y = _binary_data(n=2000, seed=12)
    m = GBM(ntrees=10, max_depth=3, score_every=5, seed=0).train(
        y="y", training_frame=fr)
    # @5 and @10; the final row IS the @10 row (no duplicate append)
    assert len(m.scoring_history) == 2
    assert [h["ntrees"] for h in m.scoring_history] == [5, 10]
    assert m.scoring_history[0]["train_logloss"] > \
        m.scoring_history[-1]["train_logloss"]


def test_time_feature_binning_consistent(mesh8):
    rng = np.random.default_rng(13)
    n = 2000
    base = np.datetime64("2026-01-01T00:00:00", "ms")
    offs = rng.integers(0, 90 * 86400_000, size=n)
    t = base + offs.astype("timedelta64[ms]")
    y = np.where(offs > 45 * 86400_000, "late", "early")  # split on time
    fr = Frame.from_arrays({"t": t, "y": y})
    m = GBM(ntrees=5, max_depth=2, seed=0).train(y="y", training_frame=fr)
    assert m.model_performance(fr, "y")["auc"] > 0.99


# -- round-2 distribution breadth (hex/genmodel DistributionFamily) ----------

def test_gbm_gamma_distribution(mesh8):
    rng = np.random.default_rng(31)
    n = 3000
    x = rng.normal(size=n)
    mu = np.exp(0.6 * x + 1.0)
    y = rng.gamma(shape=3.0, scale=mu / 3.0)
    fr = Frame.from_arrays({"x": x.astype(np.float32), "y": y})
    m = GBM(ntrees=40, max_depth=3, learn_rate=0.2,
            distribution="gamma", seed=1).train(y="y", training_frame=fr)
    pred = m.predict_raw(fr)
    assert np.all(np.asarray(pred)[:n] > 0)       # log link → positive
    corr = np.corrcoef(np.asarray(pred)[:n], mu)[0, 1]
    assert corr > 0.9, corr


def test_gbm_tweedie_distribution(mesh8):
    rng = np.random.default_rng(32)
    n = 3000
    x = rng.normal(size=n)
    mu = np.exp(0.5 * x)
    npois = rng.poisson(mu)
    y = np.array([rng.gamma(s, 1.0) if s > 0 else 0.0 for s in npois])
    fr = Frame.from_arrays({"x": x.astype(np.float32), "y": y})
    m = GBM(ntrees=40, max_depth=3, learn_rate=0.2,
            distribution="tweedie", seed=1).train(y="y",
                                                  training_frame=fr)
    pred = np.asarray(m.predict_raw(fr))[:n]
    assert np.all(pred > 0)
    assert np.corrcoef(pred, mu)[0, 1] > 0.8


def test_gbm_laplace_robust_to_outliers(mesh8):
    rng = np.random.default_rng(33)
    n = 3000
    x = rng.normal(size=n)
    y = 2.0 * x + rng.normal(scale=0.1, size=n)
    y[::50] += 100.0                              # gross outliers
    fr = Frame.from_arrays({"x": x.astype(np.float32),
                            "y": y.astype(np.float32)})
    m_l1 = GBM(ntrees=40, max_depth=3, learn_rate=0.3,
               distribution="laplace", seed=1).train(
        y="y", training_frame=fr)
    clean = np.ones(n, dtype=bool); clean[::50] = False
    pred = np.asarray(m_l1.predict_raw(fr))[:n]
    mae_clean = float(np.mean(np.abs(pred[clean] - y[clean])))
    assert mae_clean < 0.5, mae_clean             # outliers ignored


def test_gbm_laplace_large_scale_response(mesh8):
    # leaf steps are bounded by learn_rate, so without the internal
    # median/MAD scaling a y spanning thousands could never be fit
    rng = np.random.default_rng(34)
    n = 2000
    x = rng.normal(size=n)
    y = 1000.0 * x + rng.normal(scale=10.0, size=n)
    fr = Frame.from_arrays({"x": x.astype(np.float32),
                            "y": y.astype(np.float32)})
    m = GBM(ntrees=40, max_depth=3, learn_rate=0.3,
            distribution="laplace", seed=1).train(
        y="y", training_frame=fr)
    pred = np.asarray(m.predict_raw(fr))[:n]
    assert float(np.mean(np.abs(pred - y))) < 150.0
    assert pred.std() > 500.0             # predictions span the range


def test_gbm_gamma_rejects_nonpositive(mesh8):
    fr = Frame.from_arrays({"x": np.arange(10.0),
                            "y": np.arange(10.0) - 5.0})
    with pytest.raises(ValueError, match="positive"):
        GBM(distribution="gamma").train(y="y", training_frame=fr)


def test_gbm_laplace_zero_inflated_mad(mesh8):
    # 70% of y at exactly 0 → MAD = 0; the scale must fall back to std
    # instead of collapsing to 1e-8 (which froze predictions at 0)
    rng = np.random.default_rng(35)
    n = 2000
    y = np.where(rng.random(n) < 0.7, 0.0, rng.uniform(100, 1000, n))
    x = y + rng.normal(scale=20.0, size=n)
    fr = Frame.from_arrays({"x": x.astype(np.float32),
                            "y": y.astype(np.float32)})
    m = GBM(ntrees=30, max_depth=3, learn_rate=0.3,
            distribution="laplace", seed=1).train(
        y="y", training_frame=fr)
    pred = np.asarray(m.predict_raw(fr))[:n]
    assert pred.std() > 50.0


def test_zero_weight_frame_raises(mesh8):
    """All-zero effective weight (every response NA) must raise, not
    return a silently-NaN model."""
    fr = Frame.from_arrays(
        {"x": np.arange(64, dtype=np.float32),
         "y": np.full(64, np.nan, dtype=np.float32)})
    with pytest.raises(ValueError, match="positive weight"):
        GBM(ntrees=2, max_depth=2, seed=0).train(y="y", training_frame=fr)


def test_sampled_quantile_binning_parity(mesh8, monkeypatch):
    """Past _QUANTILE_SAMPLE rows fit_bins sketches edges from a fixed
    uniform sample (the reference's hist path also bins from
    approximate sketches). Forced onto the sampled path, edges must
    stay monotone and the model must match the exact-edge model's AUC
    to within noise."""
    from h2o_kubernetes_tpu.models.tree import binning as B

    fr, _, _ = _binary_data(n=6000, seed=9)
    m_exact = GBM(ntrees=5, max_depth=4, seed=1).train(
        y="y", training_frame=fr)
    auc_exact = m_exact.scoring_history[-1]["train_auc"]

    monkeypatch.setattr(B, "_QUANTILE_SAMPLE", 1024)
    B._device_quantiles.clear_cache()
    try:
        spec = B.fit_bins(fr, ["x1", "x2", "x3"], n_bins=64)
        edges = np.asarray(spec.edges_matrix())[0]
        finite = edges[np.isfinite(edges)]
        assert len(finite) > 10
        assert np.all(np.diff(finite) >= 0), "sampled edges not sorted"
        m_s = GBM(ntrees=5, max_depth=4, seed=1).train(
            y="y", training_frame=fr)
        auc_s = m_s.scoring_history[-1]["train_auc"]
        assert abs(auc_s - auc_exact) < 0.02, (auc_s, auc_exact)
    finally:
        B._device_quantiles.clear_cache()


@pytest.fixture
def ordered_by_node_block(monkeypatch):
    """The kernel on the CPU (interpret mode) with hi blocks of 2 slots,
    so that a depth-6 tree of 64 bins histograms levels 4 and 5 in 2
    and 4 blocks of 4 nodes; yields the switch that makes the rule's
    costs nothing (``"compacted"``) or prohibitive (``"blocked"``).
    Every compiled program is dropped at each switch: the rule is read
    as the program is traced."""
    import jax

    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.ops import histogram as H

    prev = h2o.get_config("hist_impl")
    h2o.set_config("hist_impl", "pallas")
    monkeypatch.setattr(H, "_FACT_MAX_NHI", 2)
    monkeypatch.setattr(core, "_ARRAY_NS", 0.0)

    def use(form):
        monkeypatch.setattr(core, "_ORDER_NS",
                            0.0 if form == "compacted" else float("inf"))
        jax.clear_caches()

    try:
        yield use
    finally:
        h2o.set_config("hist_impl", prev)
        jax.clear_caches()


def _hist_levels():
    from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

    ctr = REGISTRY.counter("h2o_train_hist_levels_total", label="form")
    return {k: ctr.value(k) for k in ("compacted", "blocked")}


def test_gbm_over_rows_ordered_by_node_block_agrees(mesh8,
                                                    ordered_by_node_block):
    """A boosted job whose trees order their rows by node block grows
    the trees it grows in the caller's order — the same splits and
    covers, leaf values and gains to float32's reordering of the
    gradient sums — and counts its levels past one hi block by form
    (`h2o_train_hist_levels_total`, which `compact_level_share`
    reads): 3 trees x 2 levels a job, compacted or blocked."""
    import importlib.util
    import os

    from h2o_kubernetes_tpu.models.tree import core

    fr, _, _ = _binary_data(n=2400, seed=4)
    models, counted = {}, {}
    for form in ("blocked", "compacted"):
        ordered_by_node_block(form)
        before = _hist_levels()
        models[form] = GBM(ntrees=3, max_depth=6, nbins=64, seed=2).train(
            y="y", training_frame=fr)
        counted[form] = {k: v - before[k]
                         for k, v in _hist_levels().items()}
        tp = core.TreeParams(max_depth=6, n_bins=64, hist_impl="pallas")
        assert core.hist_level_forms(tp, 3) == ["fact"] * 4 + [form] * 2
    assert counted == {"blocked": {"compacted": 0, "blocked": 6},
                       "compacted": {"compacted": 6, "blocked": 0}}
    t0, t1 = models["blocked"].trees, models["compacted"].trees
    assert int(np.asarray(t0.is_split).sum()) > 20
    for name in ("split_feat", "split_bin", "na_left", "is_split",
                 "cover"):
        np.testing.assert_array_equal(np.asarray(getattr(t1, name)),
                                      np.asarray(getattr(t0, name)))
    for name in ("value", "gain"):
        np.testing.assert_allclose(np.asarray(getattr(t1, name)),
                                   np.asarray(getattr(t0, name)),
                                   rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(models["compacted"].predict_raw(fr),
                               models["blocked"].predict_raw(fr),
                               rtol=1e-5, atol=1e-6)
    # the benchmark's reader of the counter
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench", "metrics",
        "compact_level_share.py")
    spec = importlib.util.spec_from_file_location("compact_share", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    now = _hist_levels()
    assert reader.read({}) == pytest.approx(
        100.0 * now["compacted"] / (now["compacted"] + now["blocked"]))


def test_class_batch_and_ooc_keep_the_rows_order(mesh8, monkeypatch,
                                                 ordered_by_node_block):
    """Where the rule would engage, two paths keep the caller's row
    order and the blocked call: the class batch's `vmap` (an order a
    class would copy the shared codes K times: its program is the one
    it is with the rule off) and the out-of-core trainer, whose levels
    stream chunks (no call of the kernel takes ``starts``, and such a
    job counts no levels)."""
    import jax
    import jax.numpy as jnp

    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.ops import histogram as H

    tp = core.TreeParams(max_depth=6, n_bins=64, hist_impl="pallas",
                         min_rows=1.0)
    bp = core.BoostParams(distribution="multinomial", learn_rate=0.1)
    assert core.multi_grow_vmapped(tp, 3, 3)
    rows = 8 * 64
    args = (jnp.zeros((rows, 3), jnp.uint8), jnp.zeros(rows),
            jnp.ones(rows), jnp.zeros((rows, 3)),
            core.round_keys(jax.random.key(0), 1), None)
    programs = {}
    for form in ("blocked", "compacted"):
        ordered_by_node_block(form)
        assert "compacted" not in core.hist_level_forms(tp, 3,
                                                        batched=True)
        programs[form] = str(jax.make_jaxpr(
            core._boost_multi_jit, static_argnums=(6, 7, 8, 9))(
            *args, tp, bp, 3, mesh8))
    assert programs["compacted"] == programs["blocked"]
    assert "row_order" not in programs["compacted"]

    starts = []
    real = H._hist_pallas

    def spy(*a, **kw):
        starts.append(a[5] if len(a) > 5 else kw.get("starts"))
        return real(*a, **kw)

    monkeypatch.setattr(H, "_hist_pallas", spy)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "512")
    fr, _, _ = _binary_data(n=1200, seed=5)
    before = _hist_levels()
    GBM(ntrees=1, max_depth=6, nbins=64, seed=2).train(
        y="y", training_frame=fr)
    assert starts and all(s is None for s in starts)
    assert _hist_levels() == before
