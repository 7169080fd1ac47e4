"""Plain reference for K-class boosting whose categorical columns split
on SETS of levels — H2O-3's GBM with ``distribution = "multinomial"``
at its default encoding (AUTO, which for GBM means Enum), as the
configuration `gbm-kddcup99` states it — and for the K-class random
forest over the same trees, in numpy float64. It imports nothing of
the program (only the helpers of `gbm_plain`, `gbm_sets_plain` and
`gbm_softmax_plain`, which import nothing of it either)
and takes nothing the program made except the answer under test (a
model's trees) and, for a forest, what the program hands out about it:
each round's bag and each class tree's candidate features.

The semantics:

- K classes; a row's margin is a vector m[0..K). The prior is the log
  of the classes' shares of the rows, clipped below at 1e-8 (the
  program's rule, `gbm_softmax_plain.init_margin`).
- p = softmax(m) over the K classes of a row; g_k = p_k - [y = k];
  h_k = p_k (1 - p_k). A round grows K trees, class k's from
  (g_k, h_k), ALL from the probabilities at the round's START; then
  class k's tree is added to class k's margin.
- Binning as in `gbm_sets_plain`: a categorical column has one bin a
  level (code = bin), a numeric column is cut at ``nbins - 3`` interior
  quantiles, a missing value (NaN, or a negative code) has the last
  bin.
- Per class tree, level by level to ``max_depth``: at every node the
  levels of a categorical that hold rows (count > 0) are ordered by
  G / H ascending (equal ratios by code), the levels without rows after
  them by code, and every prefix of that order goes left; a numeric
  feature's bins in code order. The missing-value bin is tried on each
  side, ``min_rows`` is held on both children, gain = GL²/HL + GR²/HR
  − G²/H; the best (feature, prefix, na_left) wins, the first of equal
  gains in (feature, prefix) order, and a node splits where that gain
  passes ``min_split_improvement`` and it holds at least 2 x
  ``min_rows`` rows.
- Descent (`gbm_sets_plain.descend`): at a set split a row goes left
  iff its level is in the split's set — a level that held no rows in
  the node goes RIGHT —, a numeric split by its threshold, a missing
  value by `na_left`.
- The leaf: -lr G / H, the Newton step as the program takes it,
  WITHOUT H2O-3's factor (K - 1) / K on the multinomial leaf (the
  configuration states the departure), and capped at `STEP_CAP` either
  way, as H2O-3's GBM caps a multinomial step.
- The metric: mean multiclass logloss -log p_y of the final margin,
  p clipped below at 1e-7.

The K-class forest (``forest=True``: H2O-3 DRF on a response of K > 2
classes) is the same grower with g_k = -[y = k], h = 1 and a rate of
1: a leaf is class k's share of the bagged rows in it, the levels of a
node in class k's mean order, highest first (compared exactly, -S / C,
so that levels of equal share tie as they do in float32). A round's K
trees share one bag; each class tree draws its own candidate features
a node. The probability of class k is the mean over the rounds of its
trees' leaves, clipped below at 0 and normalised over the classes.

Trees are dense heaps of the arrays `gbm_sets_plain` names (``feat``,
``is_split``, ``is_set``, ``thr``, ``left``, ``na_left``, ``value``,
``gain``, ``cover``); a model is ``init`` [K], ``learn_rate``,
``classes``, ``trees`` — a list of rounds, each a list of the K class
trees — the splits by kind and the ``train_logloss`` it reports.
`train` grows a model of its own; put in the program's place it is the
control (every row's g and h of every class rounded to bfloat16) and
carries the planted faults, and at small sizes, given the program's
own cuts (and a forest's bags and candidates), it is what the tests
compare with.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import gbm_plain
from reference.gbm_plain import (_round, gain_term,  # noqa: F401
                                 may_split, reaching_sums, resting_sums)
from reference.gbm_sets_plain import (_empty_tree, bin_rows,  # noqa: F401
                                      bins_of, descend, level_hist)
from reference.gbm_softmax_plain import grad_hess, init_margin, logloss

FAULTS = ("ordinal_codes", "wrong_side", "sequential_softmax",
          "stale_state", "half_batch", "second_best", "float32_split")

# the most a boosted leaf's step moves a margin, either way
STEP_CAP = 1e4


def quantile_edges(Xr: np.ndarray, nbins: int) -> np.ndarray:
    """[F, nbins-3] interior quantiles of the values present in every
    column (a missing value has the last bin; a column with none
    present cuts nowhere: +inf)."""
    qs = np.linspace(0.0, 1.0, nbins - 1)[1:-1]
    with ThreadPoolExecutor(8) as pool:
        Q = np.stack(list(pool.map(
            lambda f: np.nanquantile(Xr[:, f], qs) if
            np.isfinite(Xr[:, f]).any() else np.full(len(qs), np.inf),
            range(Xr.shape[1]))))
    return Q.astype(np.float32)


def leaf_value(G, H, lr: float, cap: float | None = STEP_CAP):
    """-lr G / H, capped at ``cap`` either way (None: a forest's leaf,
    uncapped)."""
    v = gbm_plain.leaf_value(G, H, lr)
    return v if cap is None else np.clip(v, -cap, cap)


def level_order(hist: np.ndarray, is_set: np.ndarray,
                exact: bool = False) -> np.ndarray:
    """[n, F, B-1] the body bins of every node and feature in the
    order their prefixes are scanned: a categorical's levels that hold
    rows by G / H ascending (``exact``: G / C from integer sums, where
    H is the count), the others after them; a numeric feature's by
    code. Stable: equal keys by code."""
    body = hist[:, :, :-1, :]
    G, H, C = body[..., 0], body[..., 1], body[..., 2]
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = G / C if exact else G / (H + 1e-10)
    ratio = np.where(C > 0, ratio, np.inf)
    codes = np.arange(body.shape[2], dtype=np.float64)
    key = np.where(is_set[None, :, None], ratio, codes[None, None, :])
    return np.argsort(key, axis=2, kind="stable")


def split_gains(hist: np.ndarray, is_set: np.ndarray, min_rows: float,
                exact: bool = False):
    """From one level's histograms [n, F, B, 3] of (G, H, count): the
    gain of every candidate [n, F, B-1] (prefix k of the feature's order
    goes left, the missing-value bin on the better side; -inf where a
    side would hold under ``min_rows`` rows), whether that better side
    is the left [n, F, B-1], the order [n, F, B-1] and the nodes' sums
    [n, 3]."""
    order = level_order(hist, is_set, exact)
    body = np.take_along_axis(hist[:, :, :-1, :], order[..., None], axis=2)
    na = hist[:, :, -1, :]
    cum = np.cumsum(body, axis=2)
    tot = cum[:, 0, -1, :] + na[:, 0, :]
    parent = gain_term(tot[:, 0], tot[:, 1])[:, None, None]

    def gains(left):
        right = tot[:, None, None, :] - left
        g = (gain_term(left[..., 0], left[..., 1])
             + gain_term(right[..., 0], right[..., 1]) - parent)
        ok = (left[..., 2] >= min_rows) & (right[..., 2] >= min_rows)
        return np.where(ok, g, -np.inf)

    g_r, g_l = gains(cum), gains(cum + na[:, :, None, :])
    return np.maximum(g_l, g_r), g_l > g_r, order, tot


def tree_params(params: dict) -> dict:
    """The numbers a tree is grown with, from a configuration's
    ``params``."""
    return {"depth": int(params["max_depth"]),
            "nbins": int(params["nbins"]),
            "lr": float(params.get("learn_rate", 0.1)),
            "min_rows": float(params.get("min_rows", 10.0)),
            "gamma": float(params.get("min_split_improvement", 1e-5))}


def grow(bins: np.ndarray, edges: np.ndarray, g, h, use, is_set, B: int,
         L: int, tp: dict, lr: float, scale: float = 1.0,
         cands: np.ndarray | None = None, exact: bool = False,
         search_set: np.ndarray | None = None,
         fault: str | None = None, cap: float | None = STEP_CAP) -> dict:
    """One class tree from (g, h) over the rows ``use`` keeps, level by
    level; ``cands`` [N, F] the features each node may split on (all
    where None); ``search_set`` the features searched as sets where
    that differs from ``is_set`` (the `ordinal_codes` fault); ``cap``
    the leaf's cap (`leaf_value`)."""
    depth, min_rows, gamma = tp["depth"], tp["min_rows"], tp["gamma"]
    search_set = is_set if search_set is None else search_set
    n = len(g)
    N = 2 ** (depth + 1) - 1
    tree = _empty_tree(N, L)
    rel = np.zeros(n, dtype=np.int64)
    live = np.ones(n, dtype=bool)
    for d in range(depth + 1):
        n_nodes, off = 2 ** d, 2 ** d - 1
        ids = off + np.arange(n_nodes)
        if d == depth:
            tot = np.zeros((n_nodes, 3))
            m = live & use
            for c, v in enumerate((g, h, None)):
                tot[:, c] = scale * np.bincount(
                    rel[m], weights=None if v is None else v[m],
                    minlength=n_nodes)
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr, cap)
            tree["cover"][ids] = tot[:, 2]
            break
        hist = scale * level_hist(bins, rel, live & use, (g, h, None),
                                  n_nodes, B)
        if fault == "float32_split":
            # every level's sums, the prefix scan and the gains' three
            # terms in float32: a node's splits then gain float32's
            # rounding of G^2 / H where they truly gain nothing
            hist = hist.astype(np.float32)
        gains, na_l, order, tot = split_gains(hist, search_set, min_rows,
                                              exact)
        if cands is not None:
            gains = np.where(cands[ids][:, :, None], gains, -np.inf)
        if fault == "second_best":
            first = gains.max(axis=2).argmax(axis=1)
            gains[np.arange(n_nodes), first] = -np.inf
        flat = gains.reshape(n_nodes, -1)
        best = flat.argmax(axis=1)
        bg = flat[np.arange(n_nodes), best]
        bf, bk = best // (B - 1), best % (B - 1)
        can = may_split(bg, tot[:, 2], min_rows, gamma)
        nl = na_l.reshape(n_nodes, -1)[np.arange(n_nodes), best]
        mine = order[np.arange(n_nodes), bf]                # [n, B-1]
        left_bins = np.argsort(mine, axis=1) <= bk[:, None]
        tree["feat"][ids] = np.where(can, bf, 0)
        tree["is_split"][ids] = can
        tree["is_set"][ids] = can & is_set[bf]
        tree["na_left"][ids] = nl & can
        # a numeric cut after bin k: x >= edges[k] goes right; past the
        # last edge every value goes left
        k_safe = np.minimum(bk, edges.shape[1] - 1)
        tree["thr"][ids] = np.where(bk < edges.shape[1], edges[bf, k_safe],
                                    np.nan)
        tree["left"][ids] = left_bins[:, :L] & (can & is_set[bf])[:, None]
        tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr, cap)
        tree["gain"][ids] = np.where(can, bg, 0.0)
        tree["cover"][ids] = tot[:, 2]
        idx = np.flatnonzero(live)
        r = rel[idx]
        moved = can[r]
        b = bins[idx, bf[r]].astype(np.int64)
        go_left = np.where(b == B - 1, nl[r],
                           left_bins[r, np.minimum(b, B - 2)])
        rel[idx] = np.where(moved, 2 * r + ~go_left, r)
        live[idx] = moved
        if fault == "wrong_side" and tree["is_set"][ids].any():
            # one level of one set handed out on the other side than
            # the rows took: the first of its node's order
            i = int(np.flatnonzero(tree["is_set"][ids])[0])
            tree["left"][ids[i], int(mine[i, 0])] ^= True
            fault = None
    return tree


def forest_probs(rounds: list, Xr: np.ndarray) -> np.ndarray:
    """[rows, K] a K-class forest's probabilities: the mean over the
    rounds of class k's leaves, clipped below at 0, normalised."""
    K = len(rounds[0])
    total = np.zeros((len(Xr), K))
    for trees in rounds:
        for k, tree in enumerate(trees):
            total[:, k] += tree["value"][descend(tree, Xr)]
    m = np.clip(total / len(rounds), 0.0, None)
    return m / (m.sum(axis=1, keepdims=True) + 1e-10)


def train(Xr: np.ndarray, y: np.ndarray, levels, params: dict,
          rounds: int, K: int, precision: str = "float64",
          fault: str | None = None, edges: np.ndarray | None = None,
          forest: bool = False, bags: np.ndarray | None = None,
          candidates: np.ndarray | None = None) -> dict:
    """Grow ``rounds`` rounds of ``K`` class trees as the module's
    docstring states them; returns the model in the neutral form
    `compare/gbm_softmax_sets.py` reads. ``precision`` rounds every
    row's g and h of every class before they are summed (the control);
    ``fault`` plants one of `FAULTS`; ``edges`` [F, nbins-3] replace
    this file's quantile cuts of the numeric columns. ``forest`` grows
    the K-class forest, on the program's ``bags`` [rounds·K, rows] (a
    tree's bag, the same for the K trees of a round) and
    ``candidates`` [rounds·K, N, F] (every row and feature where
    None)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    tp = tree_params(params)
    lr = 1.0 if forest else tp["lr"]
    levels = np.asarray(levels, dtype=np.int64)
    n = len(y)
    y = np.asarray(y).astype(np.int64)
    is_set = levels > 0
    search_set = np.zeros_like(is_set) if fault == "ordinal_codes" \
        else is_set
    L = max(int(levels.max(initial=0)), 1)
    B = bins_of(levels, tp["nbins"])
    if edges is None:
        edges = quantile_edges(Xr, tp["nbins"])
    bins = bin_rows(Xr, edges, levels, B)
    init = np.zeros(K) if forest else init_margin(y, K)
    margin = np.tile(init, (n, 1))
    use = np.ones(n, dtype=bool)
    scale = 1.0
    if fault == "half_batch":        # the sums over the kept half, doubled
        use[n // 2:] = False
        scale = 2.0
    onehot = (y[:, None] == np.arange(K)[None, :]).astype(np.float64)
    out = []
    for r in range(rounds):
        if forest:
            g, h = -onehot, np.ones_like(onehot)
        else:
            g, h = grad_hess(margin, y)
        trees = []
        for k in range(K):
            if fault == "sequential_softmax" and k:
                # class k's gradients taken after class k-1's tree of
                # this round moved the margin
                g, h = grad_hess(margin, y)
            t = r * K + k
            tree = grow(bins, edges, _round(g[:, k], precision),
                        _round(h[:, k], precision),
                        use & (True if bags is None else bags[t]), is_set,
                        B, L, tp, lr, scale,
                        None if candidates is None else candidates[t],
                        exact=forest, search_set=search_set,
                        fault=fault if fault in (
                            "second_best", "wrong_side",
                            "float32_split") else None,
                        cap=None if forest else STEP_CAP)
            trees.append(tree)
            if fault != "stale_state":
                margin[:, k] += tree["value"][descend(tree, Xr)]
        out.append(trees)
    flat = [t for trees in out for t in trees]
    n_set = sum(int(t["is_set"].sum()) for t in flat)
    n_all = sum(int(t["is_split"].sum()) for t in flat)
    if forest:
        p = np.clip(margin / rounds, 0.0, None)
        p = p / (p.sum(axis=1, keepdims=True) + 1e-10)
        ll = float(-np.mean(np.log(np.clip(p[np.arange(n), y], 1e-7,
                                           1.0))))
    else:
        ll = logloss(margin, y)
    return {"init": init, "learn_rate": lr, "classes": K, "trees": out,
            "splits": {"set": n_set, "numeric": n_all - n_set},
            "train_logloss": ll}
