"""Model metrics — the analog of hex.ModelMetrics* in the reference
(h2o-core hex/ModelMetricsBinomial, ModelMetricsRegression etc.,
SURVEY.md §2b C9/C18): AUC, logloss, RMSE/MAE, confusion-style accuracy.

All metrics are jittable jnp code; callers may pass device or host
arrays. Distributed callers gather first (metrics are O(n) scalar
reductions — cheap next to training).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec


_AUC_BINS = 4096        # reference AUC2 uses 400 bins; 4096 is ~free here
_AUC_EXACT_MAX = 65536  # above this, the histogram path takes over


@functools.partial(jax.jit, static_argnums=(3,))
def _pad_jit(y, s, wt, pad):
    # jitted (NOT eager) because the inputs are often committed
    # multi-device arrays — eager sharded ops are the XLA:CPU
    # rendezvous-flake pattern purged from the training paths
    return (jnp.concatenate([y, jnp.zeros(pad, y.dtype)]),
            jnp.concatenate([s, jnp.zeros(pad, s.dtype)]),
            jnp.concatenate([wt, jnp.zeros(pad, wt.dtype)]))


def _pad_pow2(y, s, wt):
    """Pad metric inputs to the next power of two with w=0 rows.

    Every distinct holdout length would otherwise compile a fresh XLA
    executable for the sort/histogram jits — grids, CV, and AutoML
    score hundreds of slightly-different-sized frames, and per-shape
    compiles dominated the CPU test-suite wall clock. All metric jits
    ignore w=0 rows, so bucketing shapes is free (the tiny pad program
    still compiles per shape, but in milliseconds, not seconds).
    """
    n = y.shape[0]
    m = 1 << max(n - 1, 1).bit_length()
    if m == n:
        return y, s, wt
    return _pad_jit(y, s, wt, m - n)


def roc_auc(y_true, score, w=None, exact: bool | None = None) -> float:
    """AUC with average-rank tie handling (Mann-Whitney U).

    Two paths, both jitted:
    - exact: full sort — O(n log n), used for n <= 65536 (or exact=True);
    - histogram: scores binned into 4096 equal-width bins, in-bin pairs
      tied at 0.5 — the reference's own design (hex/AUC2 computes AUC
      from a 400-bin score histogram [U3]), error bounded by in-bin pair
      mass (~1e-4 here). The binning rides ops/histogram's MXU kernel,
      replacing a ~0.5 s 1M-row device sort with one histogram pass.

    Optionally weighted; rows with w == 0 (e.g. shard padding) are
    excluded entirely, so callers can pass padded device arrays without
    a host-side mask round trip.
    """
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    s = jnp.asarray(score).astype(jnp.float32).ravel()
    wt = jnp.ones_like(y) if w is None else \
        jnp.asarray(w).astype(jnp.float32).ravel()
    if exact is None:
        exact = y.shape[0] <= _AUC_EXACT_MAX
    y, s, wt = _pad_pow2(y, s, wt)
    if exact:
        return float(_auc_impl(y, s, wt))
    return float(_auc_hist_impl(y, s, wt))


@jax.named_scope("auc")
def _score_hist_shard(y, s, wt, axis=None):
    """Shared score-binning pass: [NB, 2] (pos, neg) mass per bin +
    (smin, smax, bad). `bad` flags NaN on a live row — callers must
    surface it as NaN metrics, not plausible numbers.

    With ``axis`` this is ONE SHARD's rows under shard_map: the bin
    scale and `bad` are agreed across the axis and the per-shard
    histograms psum-ed — the same map/reduce as the tree learners'
    level histograms.

    NaN scores are parked at 0 with the NaN→bad flag set (nan_to_num
    would also finitize ±inf); ±inf live scores (diverged model) must
    not set the bin scale — they'd collapse every finite score into
    bin 0 — so the finite range is binned and infinities pin to the
    end bins (= the exact-path rank)."""
    from .ops.histogram import build_histogram

    live = wt > 0
    bad = jnp.any(live & (jnp.isnan(y) | jnp.isnan(s)))
    y = jnp.where(live, jnp.nan_to_num(y), 0.0)
    sx = jnp.where(live & ~jnp.isnan(s), s, 0.0)
    fin = live & jnp.isfinite(sx)
    smin = jnp.min(jnp.where(fin, sx, jnp.inf))
    smax = jnp.max(jnp.where(fin, sx, -jnp.inf))
    if axis is not None:
        smin, smax = lax.pmin(smin, axis), lax.pmax(smax, axis)
        bad = lax.pmax(bad.astype(jnp.int32), axis) > 0
    scale = (_AUC_BINS - 1) / jnp.maximum(smax - smin, 1e-30)
    idx = jnp.clip((sx - smin) * scale, 0, _AUC_BINS - 1).astype(jnp.int32)
    idx = jnp.where(sx == jnp.inf, _AUC_BINS - 1, idx)
    idx = jnp.where(sx == -jnp.inf, 0, idx)
    rel = jnp.where(live, 0, -1).astype(jnp.int32)
    # per-bin (Σ y·w, Σ (1-y)·w, Σ w) in one kernel pass
    hist = build_histogram(idx[:, None], rel, y, 1.0 - y, wt,
                           1, _AUC_BINS)[0, 0]
    if axis is not None:
        hist = lax.psum(hist, axis)
    return hist[:, :2], smin, smax, bad


_score_hist_one = jax.jit(_score_hist_shard)


@functools.lru_cache(maxsize=None)
def _score_hist_on(mesh, impl: str):
    from .runtime.mesh import ROWS

    return jax.jit(jax.shard_map(
        functools.partial(_score_hist_shard, axis=ROWS), mesh=mesh,
        in_specs=(PartitionSpec(ROWS),) * 3, out_specs=PartitionSpec(),
        # pallas_call's interpret mode can't thread vma (models/tree/core)
        check_vma=impl == "segment"))


def _score_hist(y, s, wt):
    """`_score_hist_shard` over inputs wherever they live: rows spread
    over a mesh (a train margin, a Frame column) are binned per shard
    under shard_map — a Mosaic kernel cannot be partitioned by the
    compiler, and a plain jit over sharded rows asks for exactly that
    (the 4-chip failure of PR 22) — anything else on its one device."""
    from .ops.histogram import resolve_impl

    for a in (y, s, wt):
        sh = getattr(a, "sharding", None)
        if isinstance(sh, NamedSharding) and len(sh.device_set) > 1:
            return _score_hist_on(sh.mesh, resolve_impl("auto"))(y, s, wt)
    return _score_hist_one(y, s, wt)


def _auc_hist_impl(y, s, wt):
    hist, _, _, bad = _score_hist(y, s, wt)
    return _auc_of_score_hist(hist, bad)


@jax.jit
@jax.named_scope("auc")
def _auc_of_score_hist(hist, bad):
    posb, negb = hist[:, 0], hist[:, 1]
    below = jnp.cumsum(negb) - negb
    P, N = jnp.sum(posb), jnp.sum(negb)
    auc = jnp.sum(posb * (below + 0.5 * negb)) / (P * N)
    return jnp.where(bad, jnp.nan, auc)


@jax.jit
@jax.named_scope("auc")
def _auc_impl(y, s, wt):
    # one compiled program: eagerly this is ~15 dispatches, each with
    # its own first-call compile
    live = wt > 0
    # NaN on a LIVE row (diverged model, NA leak) must surface as NaN
    # AUC, not be silently ranked at score 0
    bad = jnp.any(live & (jnp.isnan(y) | jnp.isnan(s)))
    wt = jnp.where(live, wt, 0.0)
    y = jnp.where(live, jnp.nan_to_num(y), 0.0)
    s = jnp.where(live, jnp.nan_to_num(s), jnp.inf)  # dead rows sort last
    order = jnp.argsort(s)
    ss, ys, ws = s[order], y[order], wt[order]
    negw = ws * (1.0 - ys)
    posw = ws * ys
    cneg = jnp.cumsum(negw)                          # inclusive
    lo = jnp.searchsorted(ss, ss, side="left")
    hi = jnp.searchsorted(ss, ss, side="right")
    below = jnp.where(lo > 0, cneg[jnp.maximum(lo - 1, 0)], 0.0)
    tied = cneg[hi - 1] - below
    auc = jnp.sum(posw * (below + 0.5 * tied)) / \
        (jnp.sum(posw) * jnp.sum(negw))
    return jnp.where(bad, jnp.nan, auc)


def binomial_stats(y_true, p1, w=None) -> dict:
    """Threshold-derived binomial metrics from one score histogram —
    the reference's ModelMetricsBinomial/AUC2 surface [U3]: pr_auc,
    Gini, max-F1 (+ its threshold), max-accuracy, mean_per_class_error
    at the F1-optimal threshold, and the confusion counts there.

    One device histogram pass (4096 bins of p1 with pos/neg mass), then
    host-side cumulative sweeps over bin-edge thresholds — exactly how
    hex/AUC2 computes its threshold tables from 400 bins.
    """
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    s = jnp.asarray(p1).astype(jnp.float32).ravel()
    wt = jnp.ones_like(y) if w is None else \
        jnp.asarray(w).astype(jnp.float32).ravel()
    y, s, wt = _pad_pow2(y, s, wt)
    hist, smin, smax, bad = (np.asarray(a) for a in _score_hist(y, s, wt))
    if bool(bad):
        # NaN on a live row: every derived metric is NaN, same as
        # roc_auc — finite-looking stats would mask a diverged model
        nan = float("nan")
        return {k: nan for k in
                ("auc", "gini", "pr_auc", "f1", "max_f1_threshold",
                 "accuracy", "mean_per_class_error")} | {
                "confusion": np.full((2, 2), nan)}
    pos, neg = hist[:, 0].astype(np.float64), hist[:, 1].astype(
        np.float64)
    P, N = pos.sum(), neg.sum()
    if P == 0 or N == 0:
        raise ValueError("binomial metrics need both classes present")
    # threshold k: predict positive when the score bin >= k
    tp = np.cumsum(pos[::-1])[::-1]
    fp = np.cumsum(neg[::-1])[::-1]
    fn = P - tp
    tn = N - fp
    with np.errstate(divide="ignore", invalid="ignore"):
        precision = np.where(tp + fp > 0, tp / (tp + fp), 1.0)
        recall = tp / P
        f1 = np.where(precision + recall > 0,
                      2 * precision * recall / (precision + recall), 0.0)
    acc = (tp + tn) / (P + N)
    k_f1 = int(np.argmax(f1))
    span = max(float(smax) - float(smin), 1e-30)
    thr = float(smin) + k_f1 * span / (_AUC_BINS - 1)
    # PR AUC: trapezoid over (recall, precision) with the conventional
    # (0, 1) endpoint appended (an "above max score" threshold) — the
    # same convention sklearn's precision_recall_curve uses
    r_ext = np.append(recall, 0.0)
    p_ext = np.append(precision, 1.0)
    order = np.argsort(r_ext)
    r_s, p_s = r_ext[order], p_ext[order]
    pr_auc = float(np.trapezoid(p_s, r_s)) if hasattr(np, "trapezoid") \
        else float(np.trapz(p_s, r_s))
    auc = float(_auc_from_hist(pos, neg))
    return {
        "auc": auc,
        "gini": 2 * auc - 1,
        "pr_auc": pr_auc,
        "f1": float(f1[k_f1]),
        "max_f1_threshold": thr,
        "accuracy": float(acc.max()),
        "mean_per_class_error": float(
            0.5 * (fn[k_f1] / P + fp[k_f1] / N)),
        "confusion": np.array([[tn[k_f1], fp[k_f1]],
                               [fn[k_f1], tp[k_f1]]]),
    }


def _auc_from_hist(pos, neg):
    below = np.cumsum(neg) - neg
    return (pos * (below + 0.5 * neg)).sum() / (pos.sum() * neg.sum())


def confusion_matrix(y_true, p1, threshold: float | None = None,
                     w=None) -> np.ndarray:
    """2x2 [[TN, FP], [FN, TP]] (rows actual, cols predicted) at the
    given threshold — F1-optimal when None, like the reference."""
    if threshold is None:
        return binomial_stats(y_true, p1, w=w)["confusion"]
    y = np.asarray(y_true).ravel()
    p = np.asarray(p1).ravel()
    wt = np.ones_like(p) if w is None else np.asarray(w).ravel()
    pred = p >= threshold
    pos = y > 0
    tp = float(wt[pred & pos].sum())
    fp = float(wt[pred & ~pos].sum())
    fn = float(wt[~pred & pos].sum())
    tn = float(wt[~pred & ~pos].sum())
    return np.array([[tn, fp], [fn, tp]])


def logloss(y_true, p, eps: float = 1e-7, w=None) -> float:
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    p = jnp.asarray(p).astype(jnp.float32).ravel()
    if w is None:
        return float(_logloss_unw(y, p, eps))
    return float(_logloss_w(y, p, jnp.asarray(w).astype(
        jnp.float32).ravel(), eps))


@functools.partial(jax.jit, static_argnums=(2,))
@jax.named_scope("logloss")
def _logloss_unw(y, p, eps):
    # eps must stay f32-representable: with 1e-15, 1-eps rounds to 1.0
    # and the (1-y)*log1p(-1) term produces 0*inf = NaN
    p = jnp.clip(p, eps, 1 - eps)
    return -jnp.mean(y * jnp.log(p) + (1 - y) * jnp.log1p(-p))


@functools.partial(jax.jit, static_argnums=(3,))
@jax.named_scope("logloss")
def _logloss_w(y, p, wt, eps):
    p = jnp.clip(p, eps, 1 - eps)
    bad = jnp.any((wt > 0) & jnp.isnan(y))     # NaN on live rows surfaces
    y = jnp.where(wt > 0, jnp.nan_to_num(y), 0.0)
    ll = y * jnp.log(p) + (1 - y) * jnp.log1p(-p)
    out = -jnp.sum(wt * jnp.where(wt > 0, ll, 0.0)) / jnp.sum(wt)
    return jnp.where(bad, jnp.nan, out)


def multinomial_logloss(y_true, probs, eps: float = 1e-7, w=None) -> float:
    """y_true: int class ids [n]; probs: [n, K]."""
    yraw = jnp.asarray(y_true).astype(jnp.float32).ravel()
    y = jnp.nan_to_num(yraw).astype(jnp.int32)
    p = jnp.clip(jnp.asarray(probs), eps, 1.0)
    ll = jnp.log(p[jnp.arange(y.shape[0]), y])
    if w is None:
        return float(-jnp.mean(ll))
    wt = jnp.asarray(w).astype(jnp.float32).ravel()
    bad = jnp.any((wt > 0) & jnp.isnan(yraw))
    out = -jnp.sum(wt * jnp.where(wt > 0, ll, 0.0)) / jnp.sum(wt)
    return float(jnp.where(bad, jnp.nan, out))


def rmse(y_true, pred, w=None) -> float:
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    p = jnp.asarray(pred).astype(jnp.float32).ravel()
    if w is None:
        return float(_rmse_unw(y, p))
    return float(_rmse_w(y, p,
                         jnp.asarray(w).astype(jnp.float32).ravel()))


@jax.jit
def _rmse_unw(y, p):
    return jnp.sqrt(jnp.mean((y - p) ** 2))


@jax.jit
def _rmse_w(y, p, wt):
    bad = jnp.any((wt > 0) & jnp.isnan(y - p))
    se = jnp.where(wt > 0, jnp.nan_to_num(y - p) ** 2, 0.0)
    out = jnp.sqrt(jnp.sum(wt * se) / jnp.sum(wt))
    return jnp.where(bad, jnp.nan, out)


def mae(y_true, pred) -> float:
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    p = jnp.asarray(pred).astype(jnp.float32).ravel()
    return float(jnp.mean(jnp.abs(y - p)))


def mean_residual_deviance(y_true, pred, distribution: str = "gaussian") -> float:
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    p = jnp.asarray(pred).astype(jnp.float32).ravel()
    if distribution == "gaussian":
        return float(jnp.mean((y - p) ** 2))
    if distribution == "poisson":
        p = jnp.clip(p, 1e-10, None)
        yl = jnp.where(y > 0, y * jnp.log(y / p), 0.0)
        return float(2.0 * jnp.mean(yl - (y - p)))
    raise ValueError(distribution)


def accuracy(y_true, label) -> float:
    y = np.asarray(y_true).ravel()
    l = np.asarray(label).ravel()
    return float((y == l).mean())


def ndcg(y_true, score, group, k: int = 10) -> float:
    """Mean NDCG@k over query groups (learning-to-rank metric).

    Analog of the reference XGBoost extension's ranking eval
    (h2o-extensions/xgboost eval_metric=ndcg, SURVEY.md §2b C14).
    y_true: graded relevance per row; group: query id per row.
    """
    y = np.asarray(y_true).ravel().astype(np.float64)
    s = np.asarray(score).ravel().astype(np.float64)
    g = np.asarray(group).ravel()
    if not len(g):
        return 0.0
    # every query at once: rows by query, then by score descending, ties
    # in row order (lexsort is stable); a row's place in its query is
    # its index less its query's first
    by_score = np.lexsort((-s, g))
    gs = g[by_score]
    first = np.concatenate([[True], gs[1:] != gs[:-1]])
    q = np.cumsum(first) - 1
    at = np.arange(len(g)) - np.flatnonzero(first)[q]
    disc = np.where(at < k, 1.0 / np.log2(at + 2.0), 0.0)
    dcg = np.bincount(q, weights=(2.0 ** y[by_score] - 1.0) * disc)
    # the ideal list has the same queries in the same places
    ideal = np.bincount(
        q, weights=(2.0 ** y[np.lexsort((-y, g))] - 1.0) * disc)
    ok = ideal > 0
    return float(np.sum(dcg[ok] / ideal[ok]) / max(int(ok.sum()), 1))


def r2(y_true, pred) -> float:
    y = jnp.asarray(y_true).astype(jnp.float32).ravel()
    p = jnp.asarray(pred).astype(jnp.float32).ravel()
    ss_res = jnp.sum((y - p) ** 2)
    ss_tot = jnp.sum((y - jnp.mean(y)) ** 2)
    return float(1.0 - ss_res / ss_tot)
