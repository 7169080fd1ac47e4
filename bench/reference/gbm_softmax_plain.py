"""Plain reference for K-class softmax boosting on the histogram tree
learner (XGBoost `multi:softprob` / `multi:softmax` as configuration
`xgb-covtype` states them), in numpy float64.

It imports nothing of the program and takes nothing the program made
except the answer under test (a model's trees); the tree-following
helpers are `gbm_plain`'s, the regularised split search
`lambdamart_plain`'s. THE SEMANTICS:

- K classes; a row's margin is a vector m[0..K). The prior is the log
  of the classes' shares of the rows, clipped below at 1e-8 (the
  program's rule; XGBoost starts from a flat `base_score`, which
  softmax makes uniform: a departure the configuration states).
- p = softmax(m) over the K classes of a row; g_k = p_k - [y = k];
  h_k = p_k (1 - p_k) (XGBoost's softmax objective doubles it: stated
  under `assumed`).
- A round grows K trees, class k's from (g_k, h_k), ALL from the
  probabilities at the round's START: class k's tree never sees what
  class k-1's tree of the same round did to the margin. Then class k's
  tree is added to class k's margin.
- Trees: gain GL²/(HL+lambda) + GR²/(HR+lambda) - G²/(H+lambda),
  `min_child_weight` on a child's sum of h, leaf -eta G / (H + lambda),
  depth-wise to `max_depth`, `nbins` global quantile bins.
- The metric: mean multiclass logloss -log p_y of the final margin, p
  clipped below at 1e-7.

A model in the neutral form: ``init`` [K], ``learn_rate``,
``classes``, ``trees`` — a list of rounds, each a list of the K class
trees (dicts of heap arrays feat, thr, is_split, value, gain, cover) —
and the ``train_logloss`` it reports. `train` grows a model of its own;
put in the program's place it is the control (every row's g and h of
every class rounded to bfloat16) and carries the planted faults.
"""

from __future__ import annotations

import numpy as np

from reference import gbm_plain
from reference.gbm_plain import (bin_rows, descend, level_hist,  # noqa: F401
                                 quantile_edges, reaching_sums,
                                 resting_sums)
from reference.lambdamart_plain import (gain_term, leaf_value,  # noqa: F401
                                        may_split, split_gains,
                                        tree_params)

FAULTS = ("sequential_softmax", "one_vs_rest", "class_shift",
          "shared_gradient", "stale_state", "half_batch", "second_best",
          "altered_answer")


def init_margin(y: np.ndarray, K: int) -> np.ndarray:
    """[K] log class shares."""
    share = np.bincount(y, minlength=K)[:K] / float(len(y))
    return np.log(np.clip(share, 1e-8, None))


def softmax(margin: np.ndarray) -> np.ndarray:
    e = np.exp(margin - margin.max(axis=1, keepdims=True))
    return e / e.sum(axis=1, keepdims=True)


def grad_hess(margin: np.ndarray, y: np.ndarray,
              one_vs_rest: bool = False):
    """([rows, K] g, [rows, K] h) from the margins as they stand.
    ``one_vs_rest`` (a planted fault): a sigmoid a class, nothing
    shared across the classes."""
    p = 1.0 / (1.0 + np.exp(-margin)) if one_vs_rest else softmax(margin)
    g = p.copy()
    g[np.arange(len(y)), y] -= 1.0
    return g, p * (1.0 - p)


def logloss(margin: np.ndarray, y: np.ndarray, eps: float = 1e-7) -> float:
    p = softmax(margin)[np.arange(len(y)), y]
    return float(-np.mean(np.log(np.clip(p, eps, 1.0))))


def grow(bins, edges, g, h, tp: dict, use, scale: float,
         fault: str | None = None) -> dict:
    """One tree from one class's (g, h), level by level."""
    depth, nbins, lr, lam = tp["depth"], tp["nbins"], tp["lr"], tp["lam"]
    n = len(g)
    N = 2 ** (depth + 1) - 1
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
            tree["value"][ids] = leaf_value(tot[:, 0], tot[:, 1], lr, lam)
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
            # the split of one node moved after it was found: the rows
            # follow the new cut, the recorded stats the old
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
    return tree


def train(Xr: np.ndarray, y: np.ndarray, params: dict, rounds: int,
          K: int, precision: str = "float64", fault: str | None = None,
          edges: np.ndarray | None = None) -> dict:
    """Grow ``rounds`` rounds of ``K`` class trees. ``precision``
    rounds every row's gradient and hessian of every class before they
    are summed (the control); ``fault`` plants one of `FAULTS`;
    ``edges`` [F, nbins-2] are the cuts to bin by in place of this
    file's own quantiles (a test that holds a model's splits bin by bin
    hands over the model's)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    tp = tree_params(params)
    n = len(y)
    y = np.asarray(y).astype(np.int64)
    if edges is None:
        edges = quantile_edges(Xr, tp["nbins"])
    bins = bin_rows(Xr, edges)
    init = init_margin(y, K)
    margin = np.tile(init, (n, 1))
    use = np.ones(n, dtype=bool)
    scale = 1.0
    if fault == "half_batch":        # the mean over the kept half
        use[n // 2:] = False
        scale = 2.0
    out = []
    for _ in range(rounds):
        g, h = grad_hess(margin, y, fault == "one_vs_rest")
        trees = []
        for k in range(K):
            if fault == "sequential_softmax" and k:
                # class k's gradients taken after class k-1's tree of
                # this round moved the margin
                g, h = grad_hess(margin, y)
            src = 0 if fault == "shared_gradient" else k
            tree = grow(bins, edges,
                        gbm_plain._round(g[:, src], precision),
                        gbm_plain._round(h[:, src], precision), tp, use,
                        scale, fault)
            trees.append(tree)
            if fault != "stale_state":
                to = (k + 1) % K if fault == "class_shift" else k
                margin[:, to] += tree["value"][descend(tree, Xr)]
        out.append(trees)
    return {"init": init, "learn_rate": tp["lr"], "classes": K,
            "trees": out, "train_logloss": logloss(margin, y)}
