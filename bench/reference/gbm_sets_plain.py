"""Plain reference for the histogram GBM (binomial) with SET splits on
categorical columns, in numpy float64: H2O-3's
``categorical_encoding = "enum"`` as the configuration `gbm-airline`
states it. It imports nothing of the program (only `gbm_plain`'s
helpers, which import nothing of it either) and takes nothing the
program made except the answer under test (a model's trees).

The semantics, and where they depart from H2O-3:

- Binning. A numeric column is cut at ``nbins - 3`` interior quantiles
  (`gbm_plain.quantile_edges`), exactly as a job without categorical
  columns is. A categorical column arrives as its integer level codes
  (what parsing leaves) and has one bin a level: code = bin. A missing
  value (NaN, or a negative code) has a bin of its own, the last. No
  two levels share a bin (H2O-3 folds levels only past `nbins_cats`,
  1024 by default; the configuration's largest column has 300).
- Split search at a node, for a categorical feature with level
  histograms (G_l, H_l, C_l): the levels with rows in the node
  (C_l > 0) are ordered by G_l / H_l ascending, equal ratios by code;
  levels without rows in the node come after them, by code. For every
  prefix k of that order, gain_k = GL²/HL + GR²/HR − G²/H, with the
  missing-value bin given to each side in turn and `min_rows` held on
  both children, exactly as for a numeric cut; the best (feature, k,
  na_left) over all features wins, the first of equal gains in
  (feature, k) order. (Fisher 1958; Breiman et al. 1984, section
  4.2.2: for a convex loss the best two-way partition of the levels is
  a prefix of this order.) Departure: H2O-3 orders a categorical's
  bins by their mean response; with this (G, H) gain the order is by
  the Newton step G/H, which is the same order for squared error and
  not for bernoulli.
- Descent. At a set split a row goes left iff its level is in the
  split's set. A level that had no rows in the node when it was split
  is not in the set and goes RIGHT; a missing value goes by `na_left`.
  Numeric splits are as in `gbm_plain`: right when `x >= thr`, a
  missing value by `na_left`.

Trees are dense heaps (node i has children 2i+1 and 2i+2) of arrays
``feat``, ``is_split``, ``is_set`` (the split is on a categorical
feature), ``thr`` (numeric splits), ``left`` (bool [N, L]: the level
codes a set split sends left), ``na_left``, ``value``, ``gain``,
``cover``. A table is ``Xr`` [rows, F] float32 with the level codes of
the categorical columns as numbers, and ``levels`` [F]: a categorical
column's number of levels, 0 for a numeric one.

`train` grows a model of its own. In the program's place it is the
control (gradients rounded to bfloat16) and carries the planted faults;
at small sizes it is what the tests compare with.
"""

from __future__ import annotations

import numpy as np

from reference.gbm_plain import (_round, auc, gain_term, grad_hess,
                                 init_margin, leaf_value, logloss,
                                 may_split, quantile_edges,
                                 reaching_sums, resting_sums,
                                 resting_table)

__all__ = ["FAULTS", "auc", "bin_rows", "bins_of", "descend", "gain_term",
           "grad_hess", "init_margin", "leaf_value", "level_hist",
           "logloss", "may_split", "reaching_sums", "resting_sums",
           "split_gains", "train"]

FAULTS = ("ordinal_codes", "range_grouped", "stale_state", "half_batch",
          "altered_answer", "second_best", "wrong_side")

RANGES = 254       # `range_grouped`: what 256 bins left of 300 levels


def bins_of(levels: np.ndarray, nbins: int) -> int:
    """Bins a feature of the reference's histograms: room for ``nbins``
    numeric bins or the widest categorical's levels, and one more, the
    last, for missing values."""
    return int(max(nbins - 1, int(np.max(levels, initial=0)))) + 1


def bin_rows(Xr: np.ndarray, edges: np.ndarray, levels: np.ndarray,
             B: int) -> np.ndarray:
    """[rows, F] int16 bins: a numeric value by ``edges``, a level by
    its code, a missing value (NaN, negative code) in bin B-1."""
    bins = np.empty(Xr.shape, dtype=np.int16)
    for f in range(Xr.shape[1]):
        x = Xr[:, f]
        if levels[f]:
            b = np.where(x >= 0, x, B - 1)
        else:
            b = np.searchsorted(edges[f], x, side="right")
        bins[:, f] = np.where(np.isnan(x), B - 1, b)
    return bins


def level_hist(bins, rel, live, vals, n_nodes: int, B: int):
    """[n_nodes, F, B, 3] sums of ``vals`` columns (g, h, 1) over the
    live rows, by relative node, feature and bin."""
    F = bins.shape[1]
    hist = np.zeros((n_nodes, F, B, 3))
    idx = np.flatnonzero(live)
    seg0 = rel[idx].astype(np.int64) * B
    for f in range(F):
        seg = seg0 + bins[idx, f]
        for c in range(3):
            hist[:, f, :, c] = np.bincount(
                seg, weights=None if vals[c] is None else vals[c][idx],
                minlength=n_nodes * B).reshape(n_nodes, B)
    return hist


def level_order(hist: np.ndarray, is_set: np.ndarray) -> np.ndarray:
    """[n, F, B-1] the body bins of every node and feature in the
    order their prefixes are scanned (the module's docstring): a
    categorical's by G/H over the levels that hold rows, the others
    after; a numeric feature's by code."""
    body = hist[:, :, :-1, :]
    G, H, C = body[..., 0], body[..., 1], body[..., 2]
    with np.errstate(divide="ignore", invalid="ignore"):
        ratio = np.where(C > 0, G / (H + 1e-10), np.inf)
    codes = np.arange(body.shape[2], dtype=np.float64)
    key = np.where(is_set[None, :, None], ratio, codes[None, None, :])
    return np.argsort(key, axis=2, kind="stable")


def split_gains(hist: np.ndarray, is_set: np.ndarray, min_rows: float):
    """From one level's histograms [n, F, B, 3]: the gain of every
    candidate [n, F, B-1] (prefix k of the feature's order goes left,
    the missing-value bin on the better side; -inf where a side would
    hold under ``min_rows`` rows), whether that better side is the
    left [n, F, B-1], the order [n, F, B-1] and the nodes' sums
    [n, 3]."""
    order = level_order(hist, is_set)
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


def descend(tree: dict, Xr: np.ndarray) -> np.ndarray:
    """Each row's resting heap node, by the recorded splits: a set
    split by the row's level code among ``left``, a numeric one by
    ``thr``, a missing value by ``na_left``; at a node that is not
    split all go left, and the row is then taken back to where it came
    to rest."""
    n, F = Xr.shape
    N = len(tree["feat"])
    depth = int(np.log2(N + 1)) - 1
    feat = tree["feat"].astype(np.int64)
    sp = tree["is_split"]
    thr = np.where(sp, tree["thr"], np.inf).astype(np.float32)
    left = tree["left"]
    L = left.shape[1]
    flat = Xr.reshape(-1)
    base = np.arange(n, dtype=np.int64) * F
    node = np.zeros(n, dtype=np.int64)
    for _ in range(depth):
        x = flat[base + feat[node]]
        missing = np.isnan(x) | (tree["is_set"][node] & (x < 0))
        code = np.clip(np.nan_to_num(x, nan=0.0), 0, L - 1).astype(np.int64)
        go_right = np.where(tree["is_set"][node], ~left[node, code],
                            x >= thr[node])
        go_right = np.where(missing, ~tree["na_left"][node], go_right)
        node = 2 * node + 1 + (go_right & sp[node])
    return resting_table(sp, depth)[node - (2 ** depth - 1)]


def _empty_tree(N: int, L: int) -> dict:
    return {"feat": np.zeros(N, dtype=np.int64),
            "thr": np.zeros(N, dtype=np.float32),
            "is_split": np.zeros(N, dtype=bool),
            "is_set": np.zeros(N, dtype=bool),
            "left": np.zeros((N, L), dtype=bool),
            "na_left": np.zeros(N, dtype=bool),
            "value": np.zeros(N), "gain": np.zeros(N),
            "cover": np.zeros(N)}


def train(Xr: np.ndarray, y: np.ndarray, levels, params: dict,
          ntrees: int, precision: str = "float64",
          fault: str | None = None, edges: np.ndarray | None = None
          ) -> dict:
    """Grow ``ntrees`` trees as the module's docstring states them;
    returns the model in the neutral form the comparison reads
    (``init``, ``learn_rate``, ``trees``, ``train_logloss``,
    ``train_auc``). ``precision`` rounds every row's gradient and
    hessian before they are summed (the control); ``fault`` plants one
    of `FAULTS`; ``edges`` [F, nbins-3] replaces the reference's own
    quantile cuts of the numeric columns (a test that holds a split
    search against this one hands both the same cuts)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    levels = np.asarray(levels, dtype=np.int64)
    depth, nbins = int(params["max_depth"]), int(params["nbins"])
    lr = float(params["learn_rate"])
    min_rows = float(params.get("min_rows", 10.0))
    gamma = float(params.get("min_split_improvement", 1e-5))
    n, F = Xr.shape
    y = y.astype(np.float64)
    is_set = levels > 0
    L = max(int(levels.max(initial=0)), 1)
    B = bins_of(levels, nbins)
    if edges is None:
        edges = quantile_edges(Xr, nbins)
    bins = bin_rows(Xr, edges, levels, B)
    # under `range_grouped` a column with more levels than RANGES is
    # folded into contiguous ranges of codes and grown on those, and
    # its sets are handed out as if a bin were still a level (what a
    # program that grouped and said nothing would hand out)
    search_set = is_set.copy()
    if fault == "ordinal_codes":
        search_set[:] = False      # prefixes in code order: the parent's
    if fault == "range_grouped":
        for f in np.flatnonzero(levels > RANGES):
            keep = bins[:, f] < B - 1
            bins[keep, f] = bins[keep, f].astype(np.int64) * RANGES \
                // levels[f]
    N = 2 ** (depth + 1) - 1
    init = init_margin(y)
    margin = np.full(n, init)
    use = np.ones(n, dtype=bool)
    scale = 1.0
    if fault == "half_batch":        # the mean over the kept half
        use[n // 2:] = False
        scale = 2.0
    trees = []
    for t in range(ntrees):
        g, h = grad_hess(margin, y)
        g, h = _round(g, precision), _round(h, precision)
        tree = _empty_tree(N, L)
        rel = np.zeros(n, dtype=np.int64)
        rest = np.zeros(n, dtype=np.int64)
        live = np.ones(n, dtype=bool)
        wrong = False
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
                tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr)
                tree["cover"][ids] = tot[:, 2]
                break
            hist = scale * level_hist(bins, rel, live & use, (g, h, None),
                                      n_nodes, B)
            gains, na_l, order, tot = split_gains(hist, search_set,
                                                  min_rows)
            if fault == "second_best":
                first = gains.max(axis=2).argmax(axis=1)
                gains[np.arange(n_nodes), first] = -np.inf
            flat = gains.reshape(n_nodes, -1)
            best = flat.argmax(axis=1)
            bg = flat[np.arange(n_nodes), best]
            bf, bk = best // (B - 1), best % (B - 1)
            can = may_split(bg, tot[:, 2], min_rows, gamma)
            if fault == "altered_answer" and d == 1 and can[0]:
                # one node's split moved after it was found, by a
                # quarter of the bins that hold rows there: the rows
                # follow the new prefix, the recorded stats the old
                held = int((hist[0, bf[0], :-1, 2] > 0).sum())
                bk[0] = (bk[0] + max(held // 4, 1)) % max(held - 1, 1)
            nl = na_l.reshape(n_nodes, -1)[np.arange(n_nodes), best]
            mine = order[np.arange(n_nodes), bf]            # [n, B-1]
            place = np.argsort(mine, axis=1)
            left_bins = place <= bk[:, None]                # [n, B-1]
            tree["feat"][ids] = np.where(can, bf, 0)
            tree["is_split"][ids] = can
            tree["is_set"][ids] = can & is_set[bf]
            tree["na_left"][ids] = nl & can
            # a numeric cut after bin k: x >= edges[k] goes right; past
            # the last edge every value goes left
            k_safe = np.minimum(bk, edges.shape[1] - 1)
            tree["thr"][ids] = np.where(bk < edges.shape[1],
                                        edges[bf, k_safe], np.nan)
            tree["left"][ids] = left_bins[:, :L] & \
                (can & is_set[bf])[:, None]
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr)
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
            rest[idx] = np.where(moved, 2 ** (d + 1) - 1 + rel[idx],
                                 rest[idx])
            if fault == "wrong_side" and not wrong and \
                    tree["is_set"][ids].any():
                # one level of one set handed out on the other side
                # than the rows took: the first of its node's order
                i = int(np.flatnonzero(tree["is_set"][ids])[0])
                tree["left"][ids[i], int(mine[i, 0])] ^= True
                wrong = True
        trees.append(tree)
        if fault != "stale_state":
            # by the node each row came to rest at as the tree grew
            margin = margin + tree["value"][rest]
    return {"init": init, "learn_rate": lr, "trees": trees,
            "train_logloss": logloss(margin, y),
            "train_auc": auc(margin, y)}
