"""Plain reference for a bagged random forest on 0/1 targets (H2O-3 DRF,
binomial), in numpy float64.

It imports nothing of the program and takes nothing the program made
except the answer under test (a model's trees) and what the program
hands out about it: each tree's bag (the rows it kept) and each node's
candidate features. Two uses:

- `node_sums` follows a given tree over the bagged rows: the (sum of y,
  count) that really reach every node. The comparison
  (`compare/drf_bagged.py`) holds a trained forest's `cover`, `value`
  and `gain` against them, and the gain its splits took against the
  best that `split_gains` finds, among the node's candidates, over
  this file's own quantile cuts; `forest_prob` is the forest's answer
  on every row (the mean of the trees' leaves, clipped to [0, 1]).
- `train` grows a forest of its own, level by level, as the
  configuration states it (CART on 0/1 targets: a leaf is the mean of
  y over the bagged rows in it, a split's gain is
  SL²/CL + SR²/CR - S²/C, `min_rows`, `min_split_improvement`,
  `mtries` candidates a node, a bag a tree). Put in the program's
  place it is the control (every histogram sum rounded to bfloat16) and
  carries the planted faults; at small sizes it is what the tests
  compare with.

Trees are dense heaps: node i has children 2i+1 and 2i+2, a row goes
right when `x[feat] >= thr`. The heap walk, the quantile cuts and the
exact AUC are `gbm_plain`'s.
"""

from __future__ import annotations

import numpy as np

from reference.gbm_plain import (_round, auc, bin_rows, descend,
                                 quantile_edges, reaching_sums)

FAULTS = ("unbagged", "shared_bag", "all_features", "second_best",
          "half_batch", "stale_bag", "half_forest_metric", "bag_metric")


def leaf_value(S, C):
    """Mean of y over a node's rows (0 where it has none)."""
    return S / (C + 1e-10)


def gain_term(S, C):
    return S * S / (C + 1e-10)


def logloss(p: np.ndarray, y: np.ndarray, eps: float = 1e-7) -> float:
    p = np.clip(p, eps, 1 - eps)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))


def node_sums(tree: dict, Xr: np.ndarray, y: np.ndarray,
              bag: np.ndarray) -> np.ndarray:
    """[N, 2] float64 (sum of y, count) of the bagged rows that REACH
    each node of ``tree``."""
    N = len(tree["feat"])
    idx = np.flatnonzero(bag)
    leaf = descend(tree, Xr[idx])
    resting = np.stack([np.bincount(leaf, weights=y[idx], minlength=N),
                        np.bincount(leaf, minlength=N).astype(np.float64)],
                       axis=1)
    return reaching_sums(resting)


def forest_prob(trees: list, Xr: np.ndarray) -> np.ndarray:
    """The forest's probability of class 1 for every row: the mean of
    the trees' leaf values, clipped to [0, 1]."""
    total = np.zeros(len(Xr))
    for tree in trees:
        total += tree["value"][descend(tree, Xr)]
    return np.clip(total / len(trees), 0.0, 1.0)


def level_hist(bins, rel, idx, y, n_nodes: int, nbins: int) -> np.ndarray:
    """[n_nodes, F, nbins, 2] (sum of y, count) of the rows ``idx``,
    each at node ``rel`` of the level."""
    F = bins.shape[1]
    hist = np.zeros((n_nodes, F, nbins, 2))
    seg0 = rel * nbins
    for f in range(F):
        seg = seg0 + bins[idx, f]
        hist[:, f, :, 0] = np.bincount(
            seg, weights=y, minlength=n_nodes * nbins).reshape(
            n_nodes, nbins)
        hist[:, f, :, 1] = np.bincount(
            seg, minlength=n_nodes * nbins).reshape(n_nodes, nbins)
    return hist


def split_gains(hist: np.ndarray, nbins: int, min_rows: float):
    """From one level's histograms [n, F, nbins, 2] the gain of every
    candidate cut, [n, F, nbins-2] (cut after bin b; -inf where a side
    would hold under ``min_rows`` rows), and the nodes' sums [n, 2]."""
    cum = np.cumsum(hist[:, :, : nbins - 1, :], axis=2)
    tot = cum[:, 0, -1, :] + hist[:, 0, nbins - 1, :]
    left = cum[:, :, : nbins - 2, :]
    right = tot[:, None, None, :] - left
    gains = (gain_term(left[..., 0], left[..., 1])
             + gain_term(right[..., 0], right[..., 1])
             - gain_term(tot[:, 0], tot[:, 1])[:, None, None])
    ok = (left[..., 1] >= min_rows) & (right[..., 1] >= min_rows)
    return np.where(ok, gains, -np.inf), tot


def may_split(best_gain, count, min_rows: float, gamma: float):
    return (best_gain > gamma) & (count >= 2 * min_rows) & \
        np.isfinite(best_gain)


def best_gains(tree: dict, Xr: np.ndarray, bins: np.ndarray,
               y: np.ndarray, bag: np.ndarray, cand: np.ndarray,
               nbins: int, min_rows: float, gamma: float) -> np.ndarray:
    """[N] for every node of ``tree`` that bagged rows reach, the best
    gain over the reference's own cuts (``bins``: the rows under them)
    among the node's candidate features ``cand`` [N, F]; 0 where no
    cut may be taken. The rows are routed by the tree's own splits, in
    value space; the cuts are this file's."""
    N = len(tree["feat"])
    depth = int(np.log2(N + 1)) - 1
    idx = np.flatnonzero(bag)
    yb = y[idx]
    best = np.zeros(N)
    rel = np.zeros(len(idx), dtype=np.int64)
    for d in range(depth):
        n_nodes, off = 2 ** d, 2 ** d - 1
        gains, tot = split_gains(
            level_hist(bins, rel, idx, yb, n_nodes, nbins), nbins, min_rows)
        gains = np.where(cand[off: off + n_nodes, :, None], gains, -np.inf)
        bg = gains.reshape(n_nodes, -1).max(axis=1)
        ok = may_split(bg, tot[:, 1], min_rows, gamma)
        best[off: off + n_nodes] = np.where(ok, bg, 0.0)
        # on to the next level by the tree's own splits; rows at a
        # node that is not split stay out of the deeper levels
        node = off + rel
        moved = tree["is_split"][node]
        idx, yb, node = idx[moved], yb[moved], node[moved]
        go_right = Xr[idx, tree["feat"][node]] >= tree["thr"][node]
        rel = 2 * (node - off) + go_right
    return best


def draw_bags(rows: int, ntrees: int, rate: float, seed: int) -> np.ndarray:
    """[ntrees, rows] bool: a bag a tree, each row kept with
    probability ``rate``, from the reference's own generator."""
    rng = np.random.default_rng([int(seed), 0xBA6])
    return rng.random((ntrees, rows)) < rate


def draw_candidates(ntrees: int, depth: int, F: int, mtries: int,
                    seed: int) -> np.ndarray:
    """[ntrees, N, F] bool: exactly ``mtries`` features a node above
    the deepest level (all of them where ``mtries`` is not in (0, F))."""
    N = 2 ** (depth + 1) - 1
    inner = 2 ** depth - 1
    cand = np.zeros((ntrees, N, F), dtype=bool)
    if not 0 < mtries < F:
        cand[:, :inner] = True
        return cand
    rng = np.random.default_rng([int(seed), 0xCA9D])
    order = np.argsort(rng.random((ntrees, inner, F)), axis=2)
    np.put_along_axis(cand[:, :inner], order[:, :, :mtries], True, axis=2)
    return cand


def train(Xr: np.ndarray, y: np.ndarray, params: dict, ntrees: int,
          seed: int, precision: str = "float64",
          fault: str | None = None, edges: np.ndarray | None = None
          ) -> dict:
    """Grow ``ntrees`` bagged trees; returns the forest in the neutral
    form `compare/drf_bagged.py` reads: ``trees`` (list of dicts of heap
    arrays feat, thr, is_split, value, gain, cover), what the grower
    hands out about them (``bags`` [ntrees, rows], ``candidates``
    [ntrees, N, F], ``sample_rate``, ``mtries``) and the final
    ``train_logloss`` / ``train_auc`` it reports.

    ``precision`` rounds every histogram sum before it is used (the
    control). ``fault`` plants one of `FAULTS`. ``edges`` are the cuts
    (by default this file's quantiles of the whole table)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    depth, nbins = int(params["max_depth"]), int(params["nbins"])
    min_rows = float(params.get("min_rows", 1.0))
    gamma = float(params.get("min_split_improvement", 1e-5))
    rate = float(params.get("sample_rate", 0.632))
    n, F = Xr.shape
    mtries = int(params.get("mtries", -1))
    if mtries == -1:
        mtries = max(int(np.sqrt(F)), 1)
    y = y.astype(np.float64)
    if edges is None:
        edges = quantile_edges(Xr, nbins)
    bins = bin_rows(Xr, edges)
    N = 2 ** (depth + 1) - 1
    bags = draw_bags(n, ntrees, rate, seed)
    cands = draw_candidates(ntrees, depth, F, mtries, seed)
    if fault == "unbagged":
        bags[:] = True
    elif fault == "shared_bag":
        bags[:] = bags[0]
    elif fault == "all_features":
        cands[:, : 2 ** depth - 1] = True
    trees = []
    for t in range(ntrees):
        used = bags[t]
        scale = 1.0
        if fault == "stale_bag":        # grown on another bag than told
            used = np.roll(bags[t], 1)
        elif fault == "half_batch":     # half the bag, sums doubled
            used = bags[t].copy()
            used[n // 2:] = False
            scale = 2.0
        tree = {"feat": np.zeros(N, dtype=np.int64),
                "thr": np.zeros(N, dtype=np.float32),
                "is_split": np.zeros(N, dtype=bool),
                "value": np.zeros(N), "gain": np.zeros(N),
                "cover": np.zeros(N)}
        idx = np.flatnonzero(used)
        yb = y[idx]
        rel = np.zeros(len(idx), dtype=np.int64)
        for d in range(depth + 1):
            n_nodes, off = 2 ** d, 2 ** d - 1
            ids = off + np.arange(n_nodes)
            if d == depth:
                tot = scale * np.stack(
                    [np.bincount(rel, weights=yb, minlength=n_nodes),
                     np.bincount(rel, minlength=n_nodes)], axis=1)
                tot = _round(tot, precision)
                tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1])
                tree["cover"][ids] = tot[:, 1]
                break
            hist = _round(scale * level_hist(bins, rel, idx, yb, n_nodes,
                                             nbins), precision)
            gains, tot = split_gains(hist, nbins, min_rows)
            gains = np.where(cands[t, ids][:, :, None], gains, -np.inf)
            if fault == "second_best":
                # every node takes the best cut of its second-best
                # candidate: a valid split, recorded as it is
                first = gains.max(axis=2).argmax(axis=1)
                gains[np.arange(n_nodes), first] = -np.inf
            flat = gains.reshape(n_nodes, -1)
            best = flat.argmax(axis=1)
            bg = flat[np.arange(n_nodes), best]
            bf, bb = best // (nbins - 2), best % (nbins - 2)
            can = may_split(bg, tot[:, 1], min_rows, gamma)
            tree["feat"][ids] = np.where(can, bf, 0)
            tree["thr"][ids] = edges[bf, np.minimum(bb, edges.shape[1] - 1)]
            tree["is_split"][ids] = can
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1])
            tree["gain"][ids] = np.where(can, bg, 0.0)
            tree["cover"][ids] = tot[:, 1]
            moved = can[rel]
            idx, yb, rel = idx[moved], yb[moved], rel[moved]
            go_right = bins[idx, bf[rel]] > bb[rel]
            rel = 2 * rel + go_right
        trees.append(tree)
    # the metric a sound grower reports is the whole forest's over the
    # whole table; the two metric faults grow sound trees and report
    # half the forest's, or the metric over the first tree's bag alone
    scored = trees[: max(ntrees // 2, 1)] \
        if fault == "half_forest_metric" else trees
    p, ym = forest_prob(scored, Xr), y
    if fault == "bag_metric":
        p, ym = p[bags[0]], y[bags[0]]
    return {"trees": trees, "bags": bags, "candidates": cands,
            "sample_rate": rate, "mtries": mtries,
            "train_logloss": logloss(p, ym), "train_auc": auc(p, ym)}
