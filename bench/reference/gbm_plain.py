"""Plain reference for the histogram GBM (binomial), in numpy float64.

It imports nothing of the program and takes nothing the program made
except the answer under test (a model's trees). Two uses:

- `descend`, `resting_sums` and `reaching_sums` follow a given tree
  over the table, row by row, from the reference's own margins: the
  (G, H, count) that really reach every node. The comparison
  (`compare/gbm_bernoulli.py`) holds a trained model's `value`, `gain`
  and `cover` against them, and the gain its splits took against the
  best that `split_gains` finds over this file's own quantile cuts.
- `train` grows a model of its own, level by level, as the
  configuration states it (H2O-3 GBM semantics: quantile bins, Newton
  leaves -G/H, gain GL²/HL + GR²/HR - G²/H, `min_rows`,
  `min_split_improvement`). Put in the program's place it is the
  control (gradients rounded to bfloat16) and carries the planted
  faults; at small sizes it is what the tests compare with.

Trees are dense heaps: node i has children 2i+1 and 2i+2, a row goes
right when `x[feat] >= thr`.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

FAULTS = ("stale_state", "half_batch", "no_exchange", "altered_answer",
          "second_best")


def sigmoid(m):
    return 1.0 / (1.0 + np.exp(-m))


def init_margin(y: np.ndarray) -> float:
    p1 = float(np.clip(y.mean(dtype=np.float64), 1e-6, 1 - 1e-6))
    return float(np.log(p1 / (1 - p1)))


def grad_hess(margin: np.ndarray, y: np.ndarray):
    p = sigmoid(margin)
    return p - y, p * (1.0 - p)


def logloss(margin: np.ndarray, y: np.ndarray, eps: float = 1e-7) -> float:
    p = np.clip(sigmoid(margin), eps, 1 - eps)
    return float(-np.mean(y * np.log(p) + (1 - y) * np.log1p(-p)))


def auc(margin: np.ndarray, y: np.ndarray) -> float:
    """Exact AUC (Mann-Whitney U, average ranks for ties)."""
    order = np.argsort(margin, kind="stable")
    s = margin[order]
    ranks = np.empty(len(s), dtype=np.float64)
    # average rank within runs of equal scores
    edge = np.flatnonzero(np.concatenate(([True], s[1:] != s[:-1], [True])))
    for_run = (edge[:-1] + edge[1:] + 1) / 2.0
    ranks[order] = np.repeat(for_run, np.diff(edge))
    pos = y > 0.5
    n1 = int(pos.sum())
    n0 = len(y) - n1
    return float((ranks[pos].sum() - n1 * (n1 + 1) / 2.0) / (n1 * n0))


def resting_table(is_split: np.ndarray, depth: int) -> np.ndarray:
    """For each position of the deepest level, the node where a row on
    its way there comes to rest: the first node of the path from the
    root that is not split."""
    pos = np.arange(2 ** depth) + 2 ** depth - 1
    rest = pos.copy()
    for up in range(1, depth + 1):            # ancestors, nearest first
        anc = ((pos + 1) >> up) - 1
        rest = np.where(is_split[anc], rest, anc)
    return rest


def descend(tree: dict, Xr: np.ndarray) -> np.ndarray:
    """Each row's resting heap node. ``Xr`` is [rows, F]. Every row
    walks the whole depth — at a node that is not split (or whose cut
    lies past the last bin: a NaN threshold) all go left — and is then
    taken back to where it came to rest."""
    n, F = Xr.shape
    depth = int(np.log2(len(tree["feat"]) + 1)) - 1
    feat = tree["feat"].astype(np.int64)
    thr = np.where(tree["is_split"], tree["thr"], np.inf).astype(np.float32)
    flat = Xr.reshape(-1)
    base = np.arange(n, dtype=np.int64) * F
    node = np.zeros(n, dtype=np.int64)
    for _ in range(depth):
        node = 2 * node + 1 + (flat[base + feat[node]] >= thr[node])
    return resting_table(tree["is_split"], depth)[node - (2 ** depth - 1)]


def resting_sums(leaf: np.ndarray, g, h, N: int) -> np.ndarray:
    """[N, 3] float64 (G, H, count) of the rows that REST at each node;
    sums over several blocks of rows add."""
    return np.stack([np.bincount(leaf, weights=g, minlength=N),
                     np.bincount(leaf, weights=h, minlength=N),
                     np.bincount(leaf, minlength=N).astype(np.float64)],
                    axis=1)


def reaching_sums(resting: np.ndarray) -> np.ndarray:
    """From the rows that rest at each node to the rows that reach it:
    a node's rows are its own and its children's."""
    out = resting.copy()
    for i in range(len(out) - 1, 0, -1):
        out[(i - 1) // 2] += out[i]
    return out


def leaf_value(G, H, learn_rate: float):
    return -learn_rate * G / (H + 1e-10)


def gain_term(G, H):
    return G * G / (H + 1e-10)


def quantile_edges(Xr: np.ndarray, nbins: int) -> np.ndarray:
    """[F, nbins-3] interior quantiles of every column: the candidate
    cuts (bin nbins-1 is the NA bin, as the configuration states). A
    thread a column: numpy drops the interpreter lock while it sorts."""
    qs = np.linspace(0.0, 1.0, nbins - 1)[1:-1]
    with ThreadPoolExecutor(8) as pool:
        return np.stack(list(pool.map(
            lambda f: np.quantile(Xr[:, f], qs),
            range(Xr.shape[1])))).astype(np.float32)


def bin_rows(Xr: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """[rows, F] int16 bin of every value under ``edges``."""
    bins = np.empty(Xr.shape, dtype=np.int16)
    for f in range(Xr.shape[1]):
        bins[:, f] = np.searchsorted(edges[f], Xr[:, f], side="right")
    return bins


def _round(a: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return a
    if precision == "float32":
        return a.astype(np.float32).astype(np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return a.astype(np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def level_hist(bins, rel, live, vals, n_nodes: int, nbins: int):
    """[n_nodes, F, nbins, 3] sums of ``vals`` columns (g, h, 1)."""
    F = bins.shape[1]
    hist = np.zeros((n_nodes, F, nbins, 3))
    idx = np.flatnonzero(live)
    seg0 = rel[idx] * nbins
    for f in range(F):
        seg = seg0 + bins[idx, f]
        for c in range(3):
            hist[:, f, :, c] = np.bincount(
                seg, weights=None if vals[c] is None else vals[c][idx],
                minlength=n_nodes * nbins).reshape(n_nodes, nbins)
    return hist


def split_gains(hist: np.ndarray, nbins: int, min_rows: float):
    """From one level's histograms [n, F, nbins, 3] the gain of every
    candidate cut, [n, F, nbins-2] (cut after bin b; -inf where a side
    would hold under ``min_rows`` rows), and the nodes' sums [n, 3]."""
    cum = np.cumsum(hist[:, :, : nbins - 1, :], axis=2)
    tot = cum[:, 0, -1, :] + hist[:, 0, nbins - 1, :]
    left = cum[:, :, : nbins - 2, :]
    right = tot[:, None, None, :] - left
    gains = (gain_term(left[..., 0], left[..., 1])
             + gain_term(right[..., 0], right[..., 1])
             - gain_term(tot[:, 0], tot[:, 1])[:, None, None])
    ok = (left[..., 2] >= min_rows) & (right[..., 2] >= min_rows)
    return np.where(ok, gains, -np.inf), tot


def may_split(best_gain, count, min_rows: float, gamma: float):
    return (best_gain > gamma) & (count >= 2 * min_rows) & \
        np.isfinite(best_gain)


def train(Xr: np.ndarray, y: np.ndarray, params: dict, ntrees: int,
          precision: str = "float64", fault: str | None = None,
          shards: int = 1) -> dict:
    """Grow ``ntrees`` trees; returns the model in the neutral form
    `bench/check.py` reads: ``init``, ``learn_rate``, ``trees`` (list of
    dicts of heap arrays feat, thr, is_split, value, gain, cover) and
    the final ``train_logloss`` / ``train_auc`` it reports.

    ``precision`` rounds every row's gradient and hessian before they
    are summed (the control). ``fault`` plants one of `FAULTS`."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    depth, nbins = int(params["max_depth"]), int(params["nbins"])
    lr = float(params["learn_rate"])
    min_rows = float(params.get("min_rows", 10.0))
    gamma = float(params.get("min_split_improvement", 1e-5))
    n, F = Xr.shape
    y = y.astype(np.float64)
    edges = quantile_edges(Xr, nbins)
    bins = bin_rows(Xr, edges)
    N = 2 ** (depth + 1) - 1
    init = init_margin(y)
    margin = np.full(n, init)
    # rows a faulty grower sums over, and what it scales the sums by
    use = np.ones(n, dtype=bool)
    scale = 1.0
    if fault == "half_batch":        # the mean over the kept half
        use[n // 2:] = False
        scale = 2.0
    elif fault == "no_exchange":     # one shard's histograms, no psum
        use[n // max(shards, 2):] = False
    trees = []
    for t in range(ntrees):
        g, h = grad_hess(margin, y)
        g, h = _round(g, precision), _round(h, precision)
        tree = {"feat": np.zeros(N, dtype=np.int64),
                "thr": np.zeros(N, dtype=np.float32),
                "is_split": np.zeros(N, dtype=bool),
                "value": np.zeros(N), "gain": np.zeros(N),
                "cover": np.zeros(N)}
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
                tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr)
                tree["cover"][ids] = tot[:, 2]
                break
            gains, tot = split_gains(
                scale * level_hist(bins, rel, live & use, (g, h, None),
                                   n_nodes, nbins), nbins, min_rows)
            if fault == "second_best":
                # every node takes the best cut of its second-best
                # feature: a valid split, recorded as it is, not the best
                first = gains.max(axis=2).argmax(axis=1)
                gains[np.arange(n_nodes), first] = -np.inf
            flat = gains.reshape(n_nodes, -1)
            best = flat.argmax(axis=1)
            bg = flat[np.arange(n_nodes), best]
            bf, bb = best // (nbins - 2), best % (nbins - 2)
            can = may_split(bg, tot[:, 2], min_rows, gamma)
            if fault == "altered_answer" and d == 1 and can[0]:
                # the split of one node moved after it was found: the
                # rows follow the new cut, the recorded stats the old
                bb[0] = (bb[0] + 8) % (nbins - 2)
            tree["feat"][ids] = np.where(can, bf, 0)
            tree["thr"][ids] = edges[bf, np.minimum(bb, edges.shape[1] - 1)]
            tree["is_split"][ids] = can
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr)
            tree["gain"][ids] = np.where(can, bg, 0.0)
            tree["cover"][ids] = tot[:, 2]
            idx = np.flatnonzero(live)
            r = rel[idx]
            moved = can[r]
            go_right = bins[idx, bf[r]] > bb[r]
            rel[idx] = np.where(moved, 2 * r + go_right, r)
            live[idx] = moved
        trees.append(tree)
        if fault != "stale_state":
            margin = margin + tree["value"][descend(tree, Xr)]
    return {"init": init, "learn_rate": lr, "trees": trees,
            "train_logloss": logloss(margin, y),
            "train_auc": auc(margin, y)}
