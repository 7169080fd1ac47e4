"""Threshold-table binomial metrics (ModelMetricsBinomial analogs) —
parity-checked against sklearn on the same predictions."""

import numpy as np
import pytest

from h2o_kubernetes_tpu import metrics as M


@pytest.fixture(scope="module")
def scored():
    rng = np.random.default_rng(3)
    n = 5000
    y = (rng.random(n) < 0.35).astype(np.float32)
    p = np.clip(y * 0.4 + rng.normal(scale=0.25, size=n) + 0.3, 0, 1)
    return y, p.astype(np.float32)


def test_stats_match_sklearn(scored):
    y, p = scored
    from sklearn import metrics as SK

    stats = M.binomial_stats(y, p)
    assert abs(stats["auc"] - SK.roc_auc_score(y, p)) < 2e-3
    assert abs(stats["gini"] - (2 * SK.roc_auc_score(y, p) - 1)) < 4e-3
    prec, rec, _ = SK.precision_recall_curve(y, p)
    assert abs(stats["pr_auc"] - SK.auc(rec, prec)) < 2e-2
    # max F1 over sklearn's threshold sweep
    f1s = 2 * prec * rec / np.maximum(prec + rec, 1e-12)
    assert abs(stats["f1"] - f1s.max()) < 5e-3
    t = stats["max_f1_threshold"]
    pred = p >= t
    sk_f1 = SK.f1_score(y, pred)
    assert abs(stats["f1"] - sk_f1) < 5e-3


def test_confusion_matrix_explicit_threshold(scored):
    y, p = scored
    cm = M.confusion_matrix(y, p, threshold=0.5)
    pred = p >= 0.5
    want = np.array([[np.sum(~pred & (y == 0)), np.sum(pred & (y == 0))],
                     [np.sum(~pred & (y == 1)), np.sum(pred & (y == 1))]])
    np.testing.assert_allclose(cm, want)


def test_confusion_matrix_f1_default_consistent(scored):
    y, p = scored
    stats = M.binomial_stats(y, p)
    cm = M.confusion_matrix(y, p)          # F1-optimal threshold
    tn, fp, fn, tp = cm[0, 0], cm[0, 1], cm[1, 0], cm[1, 1]
    f1 = 2 * tp / max(2 * tp + fp + fn, 1e-12)
    assert abs(f1 - stats["f1"]) < 5e-3


def test_single_class_raises():
    y = np.ones(100, dtype=np.float32)
    p = np.linspace(0, 1, 100).astype(np.float32)
    with pytest.raises(ValueError, match="both classes"):
        M.binomial_stats(y, p)


def test_model_performance_includes_threshold_metrics():
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    rng = np.random.default_rng(1)
    n = 400
    x = rng.normal(size=n).astype(np.float32)
    fr = h2o.Frame.from_arrays({
        "x": x, "y": np.where(x + rng.normal(scale=0.4, size=n) > 0,
                              "b", "a")})
    m = GBM(ntrees=5, max_depth=3, seed=0).train(
        y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    for k in ("pr_auc", "gini", "f1", "mean_per_class_error"):
        assert k in perf, k
    cm = m.confusion_matrix(fr, "y")
    assert cm.shape == (2, 2)
    assert cm.sum() == n


def test_nan_scores_surface_as_nan_stats(scored):
    y, p = scored
    p2 = p.copy(); p2[5] = np.nan
    stats = M.binomial_stats(y, p2)
    assert np.isnan(stats["auc"]) and np.isnan(stats["pr_auc"])
    assert np.isnan(stats["confusion"]).all()


def test_multinomial_perf_includes_macro_auc_and_mpce():
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM
    from sklearn import metrics as SK

    rng = np.random.default_rng(5)
    n = 450
    x0 = rng.normal(size=n).astype(np.float32)
    x1 = rng.normal(size=n).astype(np.float32)
    cls = np.where(x0 > 0.5, "a", np.where(x1 > 0, "b", "c"))
    fr = h2o.Frame.from_arrays({"x0": x0, "x1": x1, "y": cls})
    m = GBM(ntrees=5, max_depth=3, seed=0).train(
        y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert {"logloss", "accuracy", "mean_per_class_error",
            "auc"} <= set(perf)
    # macro-OVR AUC parity with sklearn on the same predictions
    preds = m.predict_raw(fr)
    dom = m.response_domain
    yc = fr.vec("y").to_numpy()
    want = SK.roc_auc_score(yc, preds, multi_class="ovr",
                            average="macro", labels=range(len(dom)))
    assert abs(perf["auc"] - want) < 2e-3
    assert 0 <= perf["mean_per_class_error"] <= 1


@pytest.mark.parametrize("impl", ["segment", "pallas"])
def test_score_histogram_over_a_mesh(mesh8, impl):
    """Rows spread over the mesh (a train margin, a Frame column) are
    binned per shard under shard_map and psum-ed — same histogram, same
    AUC as on one device. A plain jit over sharded rows would ask the
    compiler to partition the Pallas kernel, which Mosaic refuses: the
    4-chip failure of PR 22 (interpret mode stands in for Mosaic here;
    tests/test_chip_compile.py compiles the real thing)."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.runtime import shard_rows

    rng = np.random.default_rng(11)
    n = 1 << 17                      # past _AUC_EXACT_MAX: histogram path
    y = (rng.random(n) < 0.4).astype(np.float32)
    s = (y * 0.3 + rng.normal(scale=0.35, size=n)).astype(np.float32)
    w = (rng.random(n) < 0.9).astype(np.float32)
    prev = h2o.get_config("hist_impl")
    h2o.set_config("hist_impl", impl)
    try:
        one = M._score_hist(*(np.asarray(a) for a in (y, s, w)))
        over = M._score_hist(*(shard_rows(a, mesh=mesh8)
                               for a in (y, s, w)))
        auc = M.roc_auc(shard_rows(y, mesh=mesh8),
                        shard_rows(s, mesh=mesh8),
                        w=shard_rows(w, mesh=mesh8))
    finally:
        h2o.set_config("hist_impl", prev)
    for a, b in zip(one, over):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-3)
    assert len(over[0].sharding.device_set) == 8    # replicated result
    assert abs(auc - M.roc_auc(y, s, w=w, exact=True)) < 2e-3
