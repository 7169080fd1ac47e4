"""The comparison that decides `correct` for a trained LambdaMART
ranker (XGBoost `rank:ndcg` / `rank:pairwise`; configuration `xgb-mslr`).

`compare/gbm_bernoulli.py`'s numbers, carried over to a gradient that
hangs on a row's QUERY: the model's own trees are followed over the raw
table by the plain reference (`reference/lambdamart_plain.py`, numpy
float64), the margin goes forward from 0 with the model's own leaves,
and before every checked tree the reference takes its own float64
gradients from that margin — every pair of every query — so every tree
is judged on the gradients it should have seen. Compared:

- `cover_gap`: every node's `cover` against the rows that reach it
  (binning, routing, the count channel): exact;
- `value_gap`: `value` against -eta G / (H + lambda) at every reached
  node — THE number that holds the gradients: a wrong pair set, a wrong
  weight, a wrong rank or a rounded gradient moves G and H at every
  node (`JUDGED`: the node it reads, as a quantile; the 99th and the
  worst are handed out beside it, unjudged);
- `gain_gap`: the recorded `gain` against GL²/(HL+lambda) +
  GR²/(HR+lambda) - G²/(H+lambda) from the children's true sums;
- `regret_gap`: for the regret trees, the gain the model's splits really
  took against the best of the reference's own regularised search
  (`min_child_weight` on a child's sum of h) over its own quantile bins,
  at every node rows reach;
- `ndcg_gap`: the reported `train_ndcg@10` against the reference's exact
  one over the final margin.

Every number is a gap, lower is better; the limits are data, in the
cell's file."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import lambdamart_plain as ref

from gbm_bernoulli import _gaps, regret_trees

# The node `value_gap` and the split `gain_gap` read, as a quantile
# (PERF.md section 2 has the readings that chose it)
JUDGED = 0.9


def neutral_model(m) -> dict:
    """The program's trained model as the comparison reads it: dense
    heaps with value-space thresholds (a row goes right when
    x >= thr), host float64 — the model's answer, nothing of its tables
    kept."""
    t = m.trees
    edges = np.asarray(m.bin_spec.edges_matrix())
    isp = np.asarray(t.is_split).astype(bool)
    feat = np.where(isp, np.asarray(t.split_feat), 0).astype(np.int64)
    sb = np.asarray(t.split_bin)
    width = edges.shape[1]
    thr = np.where(sb < width, edges[feat, np.minimum(sb, width - 1)],
                   np.nan).astype(np.float32)
    f64 = {k: np.asarray(getattr(t, k)).astype(np.float64)
           for k in ("value", "gain", "cover")}
    last = m.scoring_history[-1]
    return {"init": float(m.init_score),
            "learn_rate": float(m.params.learn_rate),
            "trees": [{"feat": feat[i], "thr": thr[i], "is_split": isp[i],
                       **{k: v[i] for k, v in f64.items()}}
                      for i in range(feat.shape[0])],
            "train_ndcg@10": float(last["train_ndcg@10"])}


def _blocks(n: int, blocks: int):
    cuts = np.linspace(0, n, blocks + 1).astype(int)
    return list(zip(cuts[:-1], cuts[1:]))


def _level_hists(bins, leaf, g, h, depth: int, nbins: int, pool, blocks):
    """Every level's [2^d, F, nbins, 3] histograms of one tree, summed
    over row blocks (a thread a block)."""
    at = np.floor(np.log2(leaf + 1)).astype(np.int64)

    def part(lo_hi):
        lo, hi = lo_hi
        a, lf = at[lo:hi], leaf[lo:hi]
        return [ref.level_hist(
            bins[lo:hi], ((lf + 1) >> np.maximum(a - d, 0)) - 2 ** d,
            a >= d, (g[lo:hi], h[lo:hi], None), 2 ** d, nbins)
            for d in range(depth)]

    parts = list(pool.map(part, blocks))
    return [sum(p[d] for p in parts) for d in range(depth)]


def compare(model: dict, Xr: np.ndarray, y: np.ndarray, config: dict,
            cell: dict, seed: int, blocks: int | None = None
            ) -> dict[str, float]:
    """``model`` in the neutral form (see `lambdamart_plain.train`),
    ``Xr`` [rows, F], ``y`` the labels, ``config["qid"]`` the queries
    (contiguous). The configuration gives the parameters the trees were
    to be grown with; the cell how many trees are checked node by node
    (``check_trees``) and how many of those have their splits held
    against the reference's best (``regret_trees``, drawn from
    ``seed``)."""
    tp = ref.tree_params(config["params"])
    lr, lam, nbins = float(model["learn_rate"]), tp["lam"], tp["nbins"]
    yf = np.asarray(y).astype(np.float64)
    starts, sizes = ref.query_bounds(np.asarray(config["qid"]))
    check_trees = min(int(cell["check_trees"]), len(model["trees"]))
    regret = regret_trees(check_trees, int(cell.get("regret_trees", 0)),
                          seed)
    blocks = blocks or min(8, os.cpu_count() or 1)
    rows = _blocks(len(yf), blocks)
    N = len(model["trees"][0]["feat"])
    depth = int(np.log2(N + 1)) - 1
    out = {"cover_gap": 0.0, "value_gap": 0.0, "gain_gap": 0.0}
    if regret:
        out["regret_gap"] = 0.0
    value_gaps, gain_gaps, leaves = [], [], []
    margin = np.zeros(len(yf))
    with ThreadPoolExecutor(blocks) as pool:
        bins = None
        if regret:
            edges = ref.quantile_edges(Xr, nbins)
            bins = np.concatenate(list(pool.map(
                lambda b: ref.bin_rows(Xr[b[0]:b[1]], edges), rows)))
        for t, tree in enumerate(model["trees"]):
            leaf = np.concatenate(list(pool.map(
                lambda b: ref.descend(tree, Xr[b[0]:b[1]]), rows)))
            leaves.append(int(np.sum(~tree["is_split"]
                                     & (tree["cover"] > 0))))
            if t < check_trees:
                g, h = ref.lambda_grads(margin, yf, starts, sizes,
                                        tp["objective"], threads=blocks)
                G, H, C = ref.reaching_sums(
                    ref.resting_sums(leaf, g, h, N)).T
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
            if t in regret:
                best = np.zeros(N)
                for d, hist in enumerate(_level_hists(
                        bins, leaf, g, h, depth, nbins, pool, rows)):
                    gains, tot = ref.split_gains(hist, nbins, lam,
                                                 tp["mcw"])
                    bg = gains.reshape(len(tot), -1).max(axis=1)
                    ok = ref.may_split(bg, tot[:, 2], tp["gamma"])
                    best[2 ** d - 1: 2 ** (d + 1) - 1] = np.where(
                        ok, bg, 0.0)
                lost = float(np.sum(best - gain)) / max(
                    float(best.sum()), 1e-300)
                out["regret_gap"] = max(out["regret_gap"], lost)
            margin += tree["value"][leaf]
    for name, gaps in (("value_gap", np.concatenate(value_gaps)),
                       ("gain_gap", np.concatenate(gain_gaps))):
        if len(gaps):
            out[name] = float(np.quantile(gaps, JUDGED))
            out[name + "_99th"] = float(np.quantile(gaps, 0.99))
            out[name + "_worst"] = float(gaps.max())
    out["ndcg_gap"] = abs(float(model["train_ndcg@10"])
                          - ref.ndcg_at(margin, yf, starts, sizes, 10))
    out["leaves_least"] = float(min(leaves))
    return out
