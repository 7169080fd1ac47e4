"""Plain reference for LambdaMART on the histogram tree learner
(XGBoost `rank:ndcg` / `rank:pairwise` as configuration `xgb-mslr`
states them), in numpy float64.

It imports nothing of the program and takes nothing the program made
except the answer under test (a model's trees); the tree-following
helpers are `gbm_plain`'s. THE SEMANTICS:

- A query q (the rows of one `qid`, contiguous) holds documents with
  margins s and labels y in 0..4. Its pairs are EVERY (i, j) of q with
  y_i > y_j; no pair crosses a query; no query is truncated and no pair
  is sampled.
- r_i: the 1-based rank of i in q by s descending, ties by row order (a
  stable sort). maxDCG_q = sum_k (2^y(k) - 1) / log2(1 + k) over the
  whole list by label descending.
- rho_ij = 1 / (1 + exp(s_i - s_j));
  w_ij = |2^y_i - 2^y_j| * |1/log2(1 + r_i) - 1/log2(1 + r_j)| / maxDCG_q
  for rank:ndcg, 1 for rank:pairwise.
- g_i = -sum_{j: y_i > y_j} w_ij rho_ij + sum_{j: y_j > y_i} w_ji rho_ji;
  h_i = the same pairs' w rho (1 - rho), summed. A query whose labels
  are all equal gives zeros.
- Trees: gain GL²/(HL+lambda) + GR²/(HR+lambda) - G²/(H+lambda),
  `min_child_weight` on a child's sum of h, leaf -eta G / (H + lambda),
  depth-wise to `max_depth`, `nbins` global quantile bins, initial
  score 0.

DEPARTURES FROM XGBOOST, noted: XGBoost samples or truncates a query's
pairs (one partner a document before 2.0; top-k and
`lambdarank_num_pair_per_sample` since) and normalises by the DCG at
its truncation level; this takes every pair (Burges 2010, "From RankNet
to LambdaRank to LambdaMART") and the whole list's maxDCG, which is
deterministic and so can be held to. Depth-wise growth to depth 8
stands for the source's `max_depth 8`.

`train` grows a model of its own; put in the program's place it is the
control (gradients rounded to bfloat16) and carries the planted faults.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import gbm_plain
from reference.gbm_plain import (bin_rows, descend, level_hist,  # noqa: F401
                                 quantile_edges, reaching_sums,
                                 resting_sums)

FAULTS = ("pointwise", "no_delta_ndcg", "cross_query", "truncated_query",
          "stale_rank", "unstable_ties", "stale_state", "half_batch",
          "second_best", "altered_answer")
TRUNCATE_AT = 256        # `truncated_query`: a layout of this fixed length


def query_bounds(qid: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(starts, sizes) of the queries: the runs of equal ``qid``."""
    starts = np.concatenate([[0], np.flatnonzero(qid[1:] != qid[:-1]) + 1])
    return starts, np.diff(np.concatenate([starts, [len(qid)]]))


def ranks_desc(s: np.ndarray, reverse_ties: bool = False) -> np.ndarray:
    """1-based rank of each document by ``s`` descending, ties by row
    order (``reverse_ties``: by reverse row order — a planted fault)."""
    n = len(s)
    if reverse_ties:
        order = (n - 1 - np.argsort(-s[::-1], kind="stable"))
    else:
        order = np.argsort(-s, kind="stable")
    r = np.empty(n, dtype=np.int64)
    r[order] = np.arange(1, n + 1)
    return r


def max_dcg(y: np.ndarray, k: int | None = None) -> float:
    gains = np.sort(2.0 ** y - 1.0)[::-1][:k]
    return float(np.sum(gains / np.log2(np.arange(2, len(gains) + 2))))


def query_grads(s, y, ndcg: bool, ranks=None):
    """(g, h) of one query's documents, every pair taken."""
    up = y[:, None] > y[None, :]                   # i above j
    if not up.any():
        return np.zeros(len(s)), np.zeros(len(s))
    rho = 1.0 / (1.0 + np.exp(s[:, None] - s[None, :]))
    w = 1.0
    if ndcg:
        r = ranks_desc(s) if ranks is None else ranks
        disc = 1.0 / np.log2(1.0 + r)
        gain = 2.0 ** y
        w = np.abs(gain[:, None] - gain[None, :]) \
            * np.abs(disc[:, None] - disc[None, :]) / max_dcg(y)
    a = np.where(up, w * rho, 0.0)
    hh = a * (1.0 - rho)
    return -a.sum(axis=1) + a.sum(axis=0), hh.sum(axis=1) + hh.sum(axis=0)


def lambda_grads(margin, y, starts, sizes, objective: str,
                 fault: str | None = None, first_ranks=None,
                 threads: int = 8):
    """Float64 (g, h) of every row, a query at a time. ``fault`` plants
    one of the gradient faults of `FAULTS`."""
    if fault == "pointwise":                 # squared error on the labels
        return margin - y, np.ones(len(y))
    ndcg = objective == "rank:ndcg" and fault != "no_delta_ndcg"
    if fault == "cross_query":               # queries merged two by two
        starts = starts[::2]
        sizes = np.diff(np.concatenate([starts, [len(y)]]))
    g = np.zeros(len(y))
    h = np.zeros(len(y))

    def some(lo_hi):
        for q in range(*lo_hi):
            a = starts[q]
            n = sizes[q]
            if fault == "truncated_query":
                n = min(n, TRUNCATE_AT)
            sl = slice(a, a + n)
            ranks = None
            if fault == "stale_rank" and first_ranks is not None:
                ranks = first_ranks[sl]
            elif fault == "unstable_ties" and ndcg:
                ranks = ranks_desc(margin[sl], reverse_ties=True)
            g[sl], h[sl] = query_grads(margin[sl], y[sl], ndcg, ranks)

    cuts = np.linspace(0, len(starts), threads + 1).astype(int)
    with ThreadPoolExecutor(threads) as pool:
        list(pool.map(some, zip(cuts[:-1], cuts[1:])))
    return g, h


def all_ranks(margin, starts, sizes) -> np.ndarray:
    r = np.empty(len(margin), dtype=np.int64)
    for a, n in zip(starts, sizes):
        r[a:a + n] = ranks_desc(margin[a:a + n])
    return r


def ndcg_at(margin, y, starts, sizes, k: int = 10) -> float:
    """Exact mean NDCG@k over the queries whose ideal DCG is positive;
    ties in the margin by row order."""
    total, n = 0.0, 0
    disc = 1.0 / np.log2(np.arange(2, k + 2))
    for a, m in zip(starts, sizes):
        yy = y[a:a + m]
        ideal = max_dcg(yy, k)
        if ideal > 0:
            top = np.argsort(-margin[a:a + m], kind="stable")[:k]
            total += float(np.sum((2.0 ** yy[top] - 1.0)
                                  * disc[:len(top)])) / ideal
            n += 1
    return total / max(n, 1)


def gain_term(G, H, lam: float):
    return G * G / (H + lam)


def leaf_value(G, H, learn_rate: float, lam: float):
    return -learn_rate * G / (H + lam)


def split_gains(hist: np.ndarray, nbins: int, lam: float, mcw: float,
                min_rows: float = 1.0):
    """From one level's histograms [n, F, nbins, 3] the regularised gain
    of every candidate cut, [n, F, nbins-2] (-inf where a child would
    hold under ``mcw`` of hessian or ``min_rows`` rows), and the nodes'
    sums [n, 3]."""
    cum = np.cumsum(hist[:, :, : nbins - 1, :], axis=2)
    tot = cum[:, 0, -1, :] + hist[:, 0, nbins - 1, :]
    left = cum[:, :, : nbins - 2, :]
    right = tot[:, None, None, :] - left
    gains = (gain_term(left[..., 0], left[..., 1], lam)
             + gain_term(right[..., 0], right[..., 1], lam)
             - gain_term(tot[:, 0], tot[:, 1], lam)[:, None, None])
    ok = (left[..., 2] >= min_rows) & (right[..., 2] >= min_rows) \
        & (left[..., 1] >= mcw) & (right[..., 1] >= mcw)
    return np.where(ok, gains, -np.inf), tot


def may_split(best_gain, count, gamma: float, min_rows: float = 1.0):
    return (best_gain > gamma) & (count >= 2 * min_rows) & \
        np.isfinite(best_gain)


def tree_params(params: dict) -> dict:
    """The tree learner's numbers from an XGBoost parameter dict."""
    return {"depth": int(params["max_depth"]),
            "nbins": int(params.get("nbins", 256)),
            "lr": float(params.get("eta", params.get("learn_rate", 0.3))),
            "lam": float(params.get("reg_lambda", 1.0)),
            "mcw": float(params.get("min_child_weight", 1.0)),
            "gamma": float(params.get("gamma", 0.0)),
            "objective": params.get("objective", "rank:ndcg")}


def train(Xr: np.ndarray, y: np.ndarray, qid: np.ndarray, params: dict,
          ntrees: int, precision: str = "float64",
          fault: str | None = None, edges: np.ndarray | None = None
          ) -> dict:
    """Grow ``ntrees`` trees; returns the model in the neutral form the
    comparison reads: ``init`` 0, ``learn_rate``, ``trees`` (dicts of
    heap arrays feat, thr, is_split, value, gain, cover) and the
    ``train_ndcg@10`` it reports. ``precision`` rounds every row's
    gradient and hessian before they are summed (the control);
    ``edges`` [F, nbins-2] are the cuts to bin by in place of this
    file's own quantiles (a test that holds a model's splits bin by
    bin hands over the model's)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    tp = tree_params(params)
    depth, nbins, lr, lam = tp["depth"], tp["nbins"], tp["lr"], tp["lam"]
    n, F = Xr.shape
    y = y.astype(np.float64)
    starts, sizes = query_bounds(qid)
    if edges is None:
        edges = quantile_edges(Xr, nbins)
    bins = bin_rows(Xr, edges)
    N = 2 ** (depth + 1) - 1
    margin = np.zeros(n)
    use = np.ones(n, dtype=bool)
    scale = 1.0
    if fault == "half_batch":
        use[n // 2:] = False
        scale = 2.0
    first_ranks = all_ranks(margin, starts, sizes) \
        if fault == "stale_rank" else None
    trees = []
    for t in range(ntrees):
        g, h = lambda_grads(margin, y, starts, sizes, tp["objective"],
                            fault, first_ranks)
        g = gbm_plain._round(g, precision)
        h = gbm_plain._round(h, precision)
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
                tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr,
                                                lam)
                tree["cover"][ids] = tot[:, 2]
                break
            gains, tot = split_gains(
                scale * level_hist(bins, rel, live & use, (g, h, None),
                                   n_nodes, nbins), nbins, lam, tp["mcw"])
            if fault == "second_best":
                first = gains.max(axis=2).argmax(axis=1)
                gains[np.arange(n_nodes), first] = -np.inf
            flat = gains.reshape(n_nodes, -1)
            best = flat.argmax(axis=1)
            bg = flat[np.arange(n_nodes), best]
            bf, bb = best // (nbins - 2), best % (nbins - 2)
            can = may_split(bg, tot[:, 2], tp["gamma"])
            if fault == "altered_answer" and d == 1 and can[0]:
                bb[0] = (bb[0] + nbins // 4) % (nbins - 2)
            tree["feat"][ids] = np.where(can, bf, 0)
            tree["thr"][ids] = edges[bf, np.minimum(bb, edges.shape[1] - 1)]
            tree["is_split"][ids] = can
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr, lam)
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
    return {"init": 0.0, "learn_rate": lr, "trees": trees,
            "train_ndcg@10": ndcg_at(margin, y, starts, sizes, 10)}
