"""The comparison that decides `correct` for a trained K-class softmax
booster (XGBoost `multi:softprob` / `multi:softmax`; configuration
`xgb-covtype`).

`compare/gbm_bernoulli.py`'s numbers, carried over to K class trees a
round: the model's own trees, taken apart by class, are followed over
the raw table by the plain reference (`reference/gbm_softmax_plain.py`,
numpy float64), the `[rows, K]` margin goes forward from the
reference's own prior with the model's own leaves — class k's tree into
class k's column — and at every checked round's START the reference
takes its own float64 softmax gradients from that margin, so all K
trees of a round are judged on the gradients they should have seen.
Compared:

- `cover_gap`: every node's `cover` of every class tree against the
  rows that reach it (binning, routing, the count channel): exact;
- `value_gap`: `value` against -eta G / (H + lambda) at every reached
  node — THE number that holds the gradients: probabilities not
  normalised across the classes, a class's gradient taken after
  another's tree of the same round, another class's gradient, a tree
  added to another class's margin or a rounded gradient moves G and H
  at every node (`JUDGED`: the node it reads, as a quantile; the 99th
  and the worst are handed out beside it, unjudged);
- `gain_gap`: the recorded `gain` against GL²/(HL+lambda) +
  GR²/(HR+lambda) - G²/(H+lambda) from the children's true sums;
- `regret_gap`: for all K trees of the regret rounds, the gain the
  model's splits really took against the best of the reference's own
  regularised search (`min_child_weight` on a child's sum of h) over
  its own quantile bins, at every node rows reach: a tree's share of
  the gain to be had that it left, and of the trees' shares the MEDIAN
  — a fault of the search is in every tree; the worst tree is handed
  out unjudged, because a Newton step -G/(H + lambda) has no bound
  where H is next to nothing (rows of class k that the margin gives
  p_k ~ 0: g = -1, h ~ 0), the gain G²/(H + lambda) of a node that
  holds such rows leaps as a cut moves by one distinct value, and the
  program's cuts (quantiles of a 65,536-row sample) and the
  reference's (exact quantiles) lie a value apart: single trees read
  anywhere in -0.3 - 0.9 on sound runs (PERF.md section 2);
- `logloss_gap`: the reported `train_logloss` against the reference's
  over the final margin, relative.

Every number is a gap, lower is better; the limits are data, in the
cell's file."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import gbm_softmax_plain as ref

from gbm_bernoulli import _gaps, regret_trees
from xgb_rank import _blocks, _level_hists

# The node `value_gap` and the split `gain_gap` read, as a quantile
# (PERF.md section 2 has the readings that chose it)
JUDGED = 0.9


def neutral_model(m) -> dict:
    """The program's trained model as the comparison reads it: the
    class-interleaved stack taken apart into rounds of K dense heaps
    with value-space thresholds (a row goes right when x >= thr), host
    float64 — the model's answer, nothing of its tables kept."""
    t = m.trees
    K = int(m.nclasses)
    edges = np.asarray(m.bin_spec.edges_matrix())
    isp = np.asarray(t.is_split).astype(bool)
    feat = np.where(isp, np.asarray(t.split_feat), 0).astype(np.int64)
    sb = np.asarray(t.split_bin)
    width = edges.shape[1]
    thr = np.where(sb < width, edges[feat, np.minimum(sb, width - 1)],
                   np.nan).astype(np.float32)
    f64 = {k: np.asarray(getattr(t, k)).astype(np.float64)
           for k in ("value", "gain", "cover")}
    trees = [{"feat": feat[i], "thr": thr[i], "is_split": isp[i],
              **{k: v[i] for k, v in f64.items()}}
             for i in range(feat.shape[0])]
    return {"init": np.asarray(m.init_score, dtype=np.float64).tolist(),
            "learn_rate": float(m.params.learn_rate), "classes": K,
            "trees": [trees[r: r + K] for r in range(0, len(trees), K)],
            "train_logloss": float(
                m.scoring_history[-1]["train_logloss"])}


def compare(model: dict, Xr: np.ndarray, y: np.ndarray, config: dict,
            cell: dict, seed: int, blocks: int | None = None
            ) -> dict[str, float]:
    """``model`` in the neutral form (see `gbm_softmax_plain`), ``Xr``
    [rows, F], ``y`` the class of every row, 0..K-1. The configuration
    gives the parameters the trees were to be grown with; the cell how
    many rounds are checked node by node (``check_rounds``, the first
    ones) and how many of those have all K trees' splits held against
    the reference's best (``regret_rounds``: the first and others drawn
    from ``seed``). Every tree is followed for the reported metric."""
    tp = ref.tree_params(config["params"])
    lr, lam, nbins = float(model["learn_rate"]), tp["lam"], tp["nbins"]
    K = int(config["classes"])
    yi = np.asarray(y).astype(np.int64)
    rounds = model["trees"]
    check = min(int(cell["check_rounds"]), len(rounds))
    regret = regret_trees(check, int(cell.get("regret_rounds", 0)), seed)
    blocks = blocks or min(8, os.cpu_count() or 1)
    rows = _blocks(len(yi), blocks)
    N = len(rounds[0][0]["feat"])
    depth = int(np.log2(N + 1)) - 1
    out = {"cover_gap": 0.0, "value_gap": 0.0, "gain_gap": 0.0}
    if regret:
        out["regret_gap"] = 0.0
    value_gaps, gain_gaps, leaves = [], [], []
    node_losses, tree_losses = [], []
    margin = np.tile(ref.init_margin(yi, K), (len(yi), 1))
    with ThreadPoolExecutor(blocks) as pool:
        bins = None
        if regret:
            edges = ref.quantile_edges(Xr, nbins)
            bins = np.concatenate(list(pool.map(
                lambda b: ref.bin_rows(Xr[b[0]:b[1]], edges), rows)))
        for r, trees in enumerate(rounds):
            if len(trees) != K:
                raise ValueError(f"round {r} has {len(trees)} trees")
            if r < check:
                # all K trees of the round from the SAME probabilities
                g, h = ref.grad_hess(margin, yi)
            step = np.empty_like(margin)
            for k, tree in enumerate(trees):
                leaf = np.concatenate(list(pool.map(
                    lambda b: ref.descend(tree, Xr[b[0]:b[1]]), rows)))
                step[:, k] = tree["value"][leaf]
                leaves.append(int(np.sum(~tree["is_split"]
                                         & (tree["cover"] > 0))))
                if r >= check:
                    continue
                gk, hk = np.ascontiguousarray(g[:, k]), \
                    np.ascontiguousarray(h[:, k])
                G, H, C = ref.reaching_sums(
                    ref.resting_sums(leaf, gk, hk, N)).T
                reached = C > 0
                out["cover_gap"] = max(out["cover_gap"], float(np.max(
                    np.abs(tree["cover"] - C) / np.maximum(C, 1.0))))
                want = ref.leaf_value(G, H, lr, lam)
                value_gaps.append(_gaps(tree["value"] - want, want,
                                        reached))
                sp = tree["is_split"] & reached
                kids = 2 * np.flatnonzero(sp) + 1
                gain = np.zeros(N)
                gain[sp] = (ref.gain_term(G[kids], H[kids], lam)
                            + ref.gain_term(G[kids + 1], H[kids + 1], lam)
                            - ref.gain_term(G[sp], H[sp], lam))
                gain_gaps.append(_gaps(tree["gain"] - gain, gain, sp))
                if r in regret:
                    best = np.zeros(N)
                    for d, hist in enumerate(_level_hists(
                            bins, leaf, gk, hk, depth, nbins, pool, rows)):
                        gains, tot = ref.split_gains(hist, nbins, lam,
                                                     tp["mcw"])
                        bg = gains.reshape(len(tot), -1).max(axis=1)
                        ok = ref.may_split(bg, tot[:, 2], tp["gamma"])
                        best[2 ** d - 1: 2 ** (d + 1) - 1] = np.where(
                            ok, bg, 0.0)
                    at = best > 0
                    node_losses.append((best[at] - gain[at]) / best[at])
                    tree_losses.append(float(np.sum(best - gain)) / max(
                        float(best.sum()), 1e-300))
            margin += step
    for name, gaps in (("value_gap", np.concatenate(value_gaps)),
                       ("gain_gap", np.concatenate(gain_gaps))):
        if len(gaps):
            out[name] = float(np.quantile(gaps, JUDGED))
            out[name + "_99th"] = float(np.quantile(gaps, 0.99))
            out[name + "_worst"] = float(gaps.max())
    if regret:
        out["regret_gap"] = float(np.median(tree_losses))
        out["regret_gap_worst_tree"] = float(np.max(tree_losses))
        losses = np.concatenate(node_losses)
        for q in (75, 90, 99):
            out[f"regret_node_{q}th"] = float(np.quantile(losses, q / 100))
    ll = ref.logloss(margin, yi)
    out["logloss_gap"] = abs(float(model["train_logloss"]) - ll) / ll
    out["leaves_least"] = float(min(leaves))
    return out
