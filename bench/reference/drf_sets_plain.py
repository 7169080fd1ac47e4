"""Plain reference for a bagged random forest on 0/1 targets whose
categorical columns split on SETS of levels — H2O-3 DRF at its default
``categorical_encoding`` (AUTO, which for DRF means Enum), as the
configuration `drf-airline` states it — in numpy float64. It imports
nothing of the program (only the helpers of `gbm_plain`,
`gbm_sets_plain` and `drf_plain`, which import nothing of it either)
and takes nothing the program made except the answer under test (a
forest's trees) and what the program hands out about it: each tree's
bag and each node's candidate features.

The semantics:

- A bag a tree (each row kept with probability ``sample_rate``) and
  exactly ``mtries`` candidate features a node, as in `drf_plain`.
- Binning as in `gbm_sets_plain`: a categorical column has one bin a
  level (code = bin; no two levels share one below `nbins_cats`), a
  numeric column is cut at this file's own ``nbins - 3`` interior
  quantiles, a missing value (NaN, or a negative code) has the last
  bin.
- Split search at a node, over its candidates, from the (sum of y,
  count) of the bagged rows in every bin. A categorical feature: the
  levels that hold rows in the node in mean-response order, the
  highest mean first (equal means by code), the levels without rows
  after them by code; every prefix k of that order goes left. A
  numeric feature: its bins in code order. Either way the
  missing-value bin is tried on each side, `min_rows` is held on both
  children and gain = SL²/CL + SR²/CR − S²/C; the best (feature, k,
  na_left) wins, the first of equal gains in (feature, k) order, and a
  node splits where that gain passes `min_split_improvement` and it
  holds at least 2 x `min_rows` rows. (H2O-3 sorts a categorical's bins
  by mean response; highest first gives the same two-way partitions
  and the orientation the program records: its G/H order with g = −y,
  h = 1. The means are compared exactly, −S/C, so that levels of equal
  mean tie as they do in float32.)
- Descent as in `gbm_sets_plain.descend`: at a set split a row goes
  left iff its level is in the split's set (a level that held no rows
  in the node goes right), a numeric split by its threshold, a missing
  value by `na_left`.
- A leaf is the mean of y over the bagged rows in it; the forest's
  probability is the mean of its trees' leaves, clipped to [0, 1].

Trees are dense heaps of the arrays `gbm_sets_plain` names (``feat``,
``is_split``, ``is_set``, ``thr``, ``left``, ``na_left``, ``value``,
``gain``, ``cover``). `train` grows a forest of its own; put in the
program's place it is the control (every histogram sum rounded to
bfloat16) and carries the planted faults, and at small sizes, given the
program's own bags, candidates and cuts, it is what the tests compare
with.
"""

from __future__ import annotations

import numpy as np

from reference.drf_plain import (draw_bags, draw_candidates, gain_term,
                                 leaf_value, level_hist, logloss)
from reference.gbm_plain import (_round, auc, may_split, quantile_edges,
                                 reaching_sums)
from reference.gbm_sets_plain import (RANGES, _empty_tree, bin_rows,
                                      bins_of, descend)

__all__ = ["FAULTS", "auc", "best_gains", "bin_rows", "bins_of",
           "descend", "forest_prob", "gain_term", "leaf_value", "logloss",
           "node_sums", "quantile_edges", "resolve_mtries", "split_gains",
           "train"]

FAULTS = ("ordinal_codes", "range_grouped", "wrong_side", "stale_bag",
          "unbagged", "all_features", "second_best", "half_batch",
          "bag_metric")


def resolve_mtries(mtries: int, F: int) -> int:
    """Candidates a node: -1 is H2O-3's classification default,
    ⌊√F⌋; anything outside (0, F) is every feature."""
    if mtries == -1:
        return max(int(np.sqrt(F)), 1)
    return mtries if 0 < mtries < F else F


def split_gains(hist: np.ndarray, is_set: np.ndarray, min_rows: float):
    """From one level's histograms [n, F, B, 2] of (sum of y, count):
    the gain of every candidate [n, F, B-1] (prefix k of the feature's
    order goes left, the missing-value bin on the better side; -inf
    where a side would hold under ``min_rows`` rows), whether that
    better side is the left [n, F, B-1], the order [n, F, B-1] and the
    nodes' sums [n, 2]."""
    body = hist[:, :, :-1, :]
    S, C = body[..., 0], body[..., 1]
    with np.errstate(divide="ignore", invalid="ignore"):
        key = np.where(C > 0, -S / C, np.inf)
    codes = np.arange(body.shape[2], dtype=np.float64)
    order = np.argsort(np.where(is_set[None, :, None], key,
                                codes[None, None, :]), axis=2, kind="stable")
    body = np.take_along_axis(body, order[..., None], axis=2)
    na = hist[:, :, -1, :]
    cum = np.cumsum(body, axis=2)
    tot = cum[:, 0, -1, :] + na[:, 0, :]
    parent = gain_term(tot[:, 0], tot[:, 1])[:, None, None]

    def gains(left):
        right = tot[:, None, None, :] - left
        g = (gain_term(left[..., 0], left[..., 1])
             + gain_term(right[..., 0], right[..., 1]) - parent)
        ok = (left[..., 1] >= min_rows) & (right[..., 1] >= min_rows)
        return np.where(ok, g, -np.inf)

    g_r, g_l = gains(cum), gains(cum + na[:, :, None, :])
    return np.maximum(g_l, g_r), g_l > g_r, order, tot


def node_sums(tree: dict, Xr: np.ndarray, y: np.ndarray,
              bag: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """([N, 2] float64 (sum of y, count) of the bagged rows that REACH
    each node of ``tree``, each bagged row's resting node)."""
    N = len(tree["feat"])
    idx = np.flatnonzero(bag)
    leaf = descend(tree, Xr[idx])
    resting = np.stack([np.bincount(leaf, weights=y[idx], minlength=N),
                        np.bincount(leaf, minlength=N).astype(np.float64)],
                       axis=1)
    return reaching_sums(resting), leaf


def forest_prob(trees: list, Xr: np.ndarray) -> np.ndarray:
    """The forest's probability of class 1 for every row: the mean of
    the trees' leaf values, clipped to [0, 1]."""
    total = np.zeros(len(Xr))
    for tree in trees:
        total += tree["value"][descend(tree, Xr)]
    return np.clip(total / len(trees), 0.0, 1.0)


def best_gains(tree: dict, leaf: np.ndarray, bins: np.ndarray,
               y: np.ndarray, cand: np.ndarray, is_set: np.ndarray, B: int,
               min_rows: float, gamma: float) -> np.ndarray:
    """[N] for every node of ``tree`` that bagged rows reach, the best
    gain of this file's own search (its bins: ``bins``, the bagged rows
    under them; ``leaf`` their resting nodes by the tree's own splits)
    among the node's candidates ``cand`` [N, F]; 0 where no split may
    be taken."""
    N = len(tree["feat"])
    depth = int(np.log2(N + 1)) - 1
    at = np.floor(np.log2(leaf + 1)).astype(np.int64)
    best = np.zeros(N)
    for d in range(depth):
        n_nodes, off = 2 ** d, 2 ** d - 1
        live = np.flatnonzero(at >= d)
        rel = ((leaf[live] + 1) >> (at[live] - d)) - n_nodes
        gains, _, _, tot = split_gains(
            level_hist(bins, rel, live, y[live], n_nodes, B), is_set,
            min_rows)
        gains = np.where(cand[off: off + n_nodes, :, None], gains, -np.inf)
        bg = gains.reshape(n_nodes, -1).max(axis=1)
        ok = may_split(bg, tot[:, 1], min_rows, gamma)
        best[off: off + n_nodes] = np.where(ok, bg, 0.0)
    return best


def train(Xr: np.ndarray, y: np.ndarray, levels, params: dict,
          ntrees: int, seed: int, precision: str = "float64",
          fault: str | None = None, edges: np.ndarray | None = None,
          bags: np.ndarray | None = None,
          candidates: np.ndarray | None = None) -> dict:
    """Grow ``ntrees`` bagged trees as the module's docstring states
    them; returns the forest in the neutral form `compare/drf_sets.py`
    reads: ``trees``, what the grower hands out about them (``bags``
    [ntrees, rows], ``candidates`` [ntrees, N, F]), the splits by kind
    and the ``train_logloss`` / ``train_auc`` it reports.

    ``precision`` rounds every histogram sum before it is used (the
    control); ``fault`` plants one of `FAULTS`; ``edges`` [F, nbins-3]
    replace this file's quantile cuts of the numeric columns, and
    ``bags`` / ``candidates`` its own draws (a test that holds the
    program's forest against this one hands it the program's)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    levels = np.asarray(levels, dtype=np.int64)
    depth, nbins = int(params["max_depth"]), int(params["nbins"])
    min_rows = float(params.get("min_rows", 1.0))
    gamma = float(params.get("min_split_improvement", 1e-5))
    n, F = Xr.shape
    mtries = resolve_mtries(int(params.get("mtries", -1)), F)
    y = y.astype(np.float64)
    is_set = levels > 0
    L = max(int(levels.max(initial=0)), 1)
    B = bins_of(levels, nbins)
    if edges is None:
        edges = quantile_edges(Xr, nbins)
    bins = bin_rows(Xr, edges, levels, B)
    search_set = is_set.copy()
    if fault == "ordinal_codes":
        search_set[:] = False      # prefixes in code order, recorded truly
    if fault == "range_grouped":
        # levels folded into ranges of codes and grown on those; the
        # sets are handed out as if a bin were still a level
        for f in np.flatnonzero(levels > RANGES):
            keep = bins[:, f] < B - 1
            bins[keep, f] = bins[keep, f].astype(np.int64) * RANGES \
                // levels[f]
    N = 2 ** (depth + 1) - 1
    rate = float(params.get("sample_rate", 0.632))
    bags = draw_bags(n, ntrees, rate, seed) if bags is None \
        else np.array(bags, dtype=bool)
    cands = draw_candidates(ntrees, depth, F, mtries, seed) \
        if candidates is None else np.array(candidates, dtype=bool)
    if fault == "unbagged":
        bags[:] = True
    elif fault == "all_features":
        cands[:, : 2 ** depth - 1] = True
    trees = []
    for t in range(ntrees):
        used, scale = bags[t], 1.0
        if fault == "stale_bag":        # grown on another bag than told
            used = np.roll(bags[t], 1)
        elif fault == "half_batch":     # half the bag, sums doubled
            used = bags[t].copy()
            used[n // 2:] = False
            scale = 2.0
        tree = _empty_tree(N, L)
        idx = np.flatnonzero(used)
        yb = y[idx]
        rel = np.zeros(len(idx), dtype=np.int64)
        wrong = False
        for d in range(depth + 1):
            n_nodes, off = 2 ** d, 2 ** d - 1
            ids = off + np.arange(n_nodes)
            if d == depth:
                tot = _round(scale * np.stack(
                    [np.bincount(rel, weights=yb, minlength=n_nodes),
                     np.bincount(rel, minlength=n_nodes)], axis=1),
                    precision)
                tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1])
                tree["cover"][ids] = tot[:, 1]
                break
            hist = _round(scale * level_hist(bins, rel, idx, yb, n_nodes, B),
                          precision)
            gains, na_l, order, tot = split_gains(hist, search_set,
                                                  min_rows)
            gains = np.where(cands[t, ids][:, :, None], gains, -np.inf)
            if fault == "second_best":
                # every node takes the best split of its second-best
                # candidate: a valid split, recorded as it is
                first = gains.max(axis=2).argmax(axis=1)
                gains[np.arange(n_nodes), first] = -np.inf
            flat = gains.reshape(n_nodes, -1)
            best = flat.argmax(axis=1)
            bg = flat[np.arange(n_nodes), best]
            bf, bk = best // (B - 1), best % (B - 1)
            can = may_split(bg, tot[:, 1], min_rows, gamma)
            nl = na_l.reshape(n_nodes, -1)[np.arange(n_nodes), best]
            mine = order[np.arange(n_nodes), bf]            # [n, B-1]
            left_bins = np.argsort(mine, axis=1) <= bk[:, None]
            tree["feat"][ids] = np.where(can, bf, 0)
            tree["is_split"][ids] = can
            tree["is_set"][ids] = can & is_set[bf]
            tree["na_left"][ids] = nl & can
            k_safe = np.minimum(bk, edges.shape[1] - 1)
            tree["thr"][ids] = np.where(bk < edges.shape[1],
                                        edges[bf, k_safe], np.nan)
            tree["left"][ids] = left_bins[:, :L] & \
                (can & is_set[bf])[:, None]
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1])
            tree["gain"][ids] = np.where(can, bg, 0.0)
            tree["cover"][ids] = tot[:, 1]
            moved = can[rel]
            idx, yb, rel = idx[moved], yb[moved], rel[moved]
            b = bins[idx, bf[rel]].astype(np.int64)
            go_left = np.where(b == B - 1, nl[rel],
                               left_bins[rel, np.minimum(b, B - 2)])
            rel = 2 * rel + ~go_left
            if fault == "wrong_side" and not wrong and \
                    tree["is_set"][ids].any():
                # one level of one set handed out on the other side
                # than the rows took: the first of its node's order
                i = int(np.flatnonzero(tree["is_set"][ids])[0])
                tree["left"][ids[i], int(mine[i, 0])] ^= True
                wrong = True
        trees.append(tree)
    # a sound grower reports the whole forest's metric over the whole
    # table; `bag_metric` reports it over the first tree's bag alone
    p, ym = forest_prob(trees, Xr), y
    if fault == "bag_metric":
        p, ym = p[bags[0]], y[bags[0]]
    n_set = sum(int(t["is_set"].sum()) for t in trees)
    n_all = sum(int(t["is_split"].sum()) for t in trees)
    return {"trees": trees, "bags": bags, "candidates": cands,
            "sample_rate": rate, "mtries": mtries,
            "splits": {"set": n_set, "numeric": n_all - n_set},
            "train_logloss": logloss(p, ym), "train_auc": auc(p, ym)}
