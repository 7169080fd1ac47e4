"""The comparison that decides `correct` for a K-class softmax booster
whose categorical columns split on SETS of levels (H2O-3 GBM,
``distribution = "multinomial"``, ``categorical_encoding = "enum"``;
configuration `gbm-kddcup99`).

`compare/gbm_softmax.py`'s numbers with `compare/gbm_sets.py`'s descent:
the model's own trees, taken apart by class, are followed over the raw
table by the plain reference (`reference/gbm_softmax_sets_plain.py`,
numpy float64) — a set split by the row's level code among the levels
the model records, a numeric one by its threshold, a missing value by
`na_left` — the `[rows, K]` margin goes forward from the reference's
own prior with the model's own leaves, class k's tree into class k's
column, and at every checked round's START the reference takes its own
float64 softmax gradients from that margin, so all K trees of a round
are judged on the gradients they should have seen. Compared:

- `cover_gap`: every node's `cover` of every class tree against the
  rows that reach it (binning, one bin a level, routing by set, the
  count channel): exact;
- `value_gap`: `value` against -lr G / H at every reached node — THE
  number that holds the gradients (`JUDGED`: the node it reads, as a
  quantile; the 99th and the worst are handed out beside it, unjudged:
  a Newton step without a lambda has no bound where H is next to
  nothing, and float32's softmax keeps no h below 1e-38);
- `gain_gap`: the recorded `gain` against GL²/HL + GR²/HR - G²/H from
  the children's true sums (`JUDGED` as well), each against the larger
  of itself and the median gain of its tree, as `gbm_softmax` reads it;
- `split_gap`: whether each reached node splits, held to the rule the
  configuration states (a node splits where its best gain passes
  ``min_split_improvement`` and it holds 2 x ``min_rows`` rows), as the
  share of the nodes judged that break it: every split of every checked
  tree, whose own true gain has to pass ``min_split_improvement``
  whatever the best was, and in the searched trees (below) every
  reached node left a leaf, where the reference's `may_split` over the
  categorical columns has to be false — their bins, one a level, are
  the program's too, where a numeric column's cuts are the reference's
  own (a split the program's cuts do not have is `regret_gap`'s);
- `regret_gap`: for the class trees of the regret rounds (those of
  ``regret_classes`` classes, drawn from the seed: at the cell's rows a
  tree's search costs the host seconds), the gain the model's splits
  really took against the best of the reference's own
  search (levels ordered by G/H, every prefix, the missing-value bin on
  either side, `min_rows` on both children) over its own bins (one a
  level, its own quantile cuts of the numeric columns), at every node
  rows reach: a tree's share of the gain to be had that it left, and
  of the trees' shares the MEDIAN — a fault of the search is in every
  tree, and a single tree's regret leaps where a node's H is next to
  nothing (`gbm_softmax`'s reason; the worst tree is handed out,
  unjudged);
- `logloss_gap`: the reported `train_logloss` against the reference's
  over the final margin, relative.

Every number is a gap, lower is better; the limits are data, in the
cell's file."""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import gbm_softmax_sets_plain as ref

from gbm_bernoulli import _gaps, regret_trees
from xgb_rank import _blocks

# The node `value_gap` and the split `gain_gap` read, as a quantile: the
# 9th in ten, as `gbm_sets` and `gbm_softmax` read them
JUDGED = 0.9


def neutral_model(m) -> dict:
    """The program's trained model as the comparison reads it: the
    class-interleaved stack taken apart into rounds of K dense heaps,
    host float64, a numeric split as a value-space threshold (a row
    goes right when x >= thr), a set split as the level codes it sends
    left — the model's answer, nothing of its tables kept. Also the
    job's splits by kind, for `multi_set_split_share`."""
    t = m.trees
    K = int(m.nclasses)
    spec = m.bin_spec
    edges = np.asarray(spec.edges_matrix())
    isp = np.asarray(t.is_split).astype(bool)
    feat = np.where(isp, np.asarray(t.split_feat), 0).astype(np.int64)
    sb = np.asarray(t.split_bin)
    width = edges.shape[1]
    thr = np.where(sb < width, edges[feat, np.minimum(sb, width - 1)],
                   np.nan).astype(np.float32)
    is_set = (np.asarray(spec.set_feats, dtype=bool)[feat] & isp) \
        if spec.set_feats else np.zeros_like(isp)
    N = feat.shape[1]
    # code = bin for a set feature; leaves send nothing left. A model
    # without sets has only its numeric splits and their NA sides
    left = np.zeros((feat.shape[0], N, spec.n_bins - 1), dtype=bool)
    na_left = np.asarray(t.na_left).astype(bool)
    if t.left_bins is not None:
        lb = np.asarray(t.left_bins).astype(bool)    # [T·K, 2^d - 1, B]
        inner = lb.shape[1]
        left[:, :inner] = lb[:, :, :-1] & is_set[:, :inner, None]
        na_left[:, :inner] = lb[:, :, -1]
    f64 = {k: np.asarray(getattr(t, k)).astype(np.float64)
           for k in ("value", "gain", "cover")}
    trees = [{"feat": feat[i], "thr": thr[i], "is_split": isp[i],
              "is_set": is_set[i], "left": left[i],
              "na_left": na_left[i] & isp[i],
              **{k: v[i] for k, v in f64.items()}}
             for i in range(feat.shape[0])]
    return {"init": np.asarray(m.init_score, dtype=np.float64).tolist(),
            "learn_rate": float(m.params.learn_rate), "classes": K,
            "trees": [trees[r: r + K] for r in range(0, len(trees), K)],
            "splits": {"set": int(is_set.sum()),
                       "numeric": int(isp.sum() - is_set.sum())},
            "train_logloss": float(
                m.scoring_history[-1]["train_logloss"])}


def _level_hists(bins, leaf, g, h, depth: int, B: int, pool, blocks):
    """Every level's [2^d, F, B, 3] histograms of one tree over the
    reference's own bins, summed over row blocks (a thread a block)."""
    at = np.floor(np.log2(leaf + 1)).astype(np.int64)

    def part(lo_hi):
        lo, hi = lo_hi
        a, lf = at[lo:hi], leaf[lo:hi]
        return [ref.level_hist(
            bins[lo:hi], ((lf + 1) >> np.maximum(a - d, 0)) - 2 ** d,
            a >= d, (g[lo:hi], h[lo:hi], None), 2 ** d, B)
            for d in range(depth)]

    parts = list(pool.map(part, blocks))
    return [sum(p[d] for p in parts) for d in range(depth)]


def compare(model: dict, Xr: np.ndarray, y: np.ndarray, config: dict,
            cell: dict, seed: int, blocks: int | None = None
            ) -> dict[str, float]:
    """``model`` in the neutral form (see `gbm_softmax_sets_plain`),
    ``Xr`` [rows, F] with the categorical columns' level codes as
    numbers, ``y`` the class of every row, 0..K-1,
    ``config["levels"]`` [F] the columns' level counts (0: numeric).
    The cell gives how many rounds are checked node by node
    (``check_rounds``, the first ones), how many of those have their
    class trees' splits held against the reference's best
    (``regret_rounds``: the first and others drawn from ``seed``) and
    the trees of how many classes (``regret_classes``, all K where the
    cell does not say; drawn from ``seed``). Every tree is followed for
    the reported metric."""
    tp = ref.tree_params(config["params"])
    lr = float(model["learn_rate"])
    levels = np.asarray(config["levels"], dtype=np.int64)
    is_set = levels > 0
    B = ref.bins_of(levels, tp["nbins"])
    K = int(config["classes"])
    yi = np.asarray(y).astype(np.int64)
    rounds = model["trees"]
    check = min(int(cell["check_rounds"]), len(rounds))
    regret = regret_trees(check, int(cell.get("regret_rounds", 0)), seed)
    searched = set(np.random.default_rng(seed).permutation(K)[
        :int(cell.get("regret_classes", K))].tolist())
    blocks = blocks or min(8, os.cpu_count() or 1)
    rows = _blocks(len(yi), blocks)
    N = len(rounds[0][0]["feat"])
    depth = int(np.log2(N + 1)) - 1
    out = {"cover_gap": 0.0, "value_gap": 0.0, "gain_gap": 0.0,
           "split_gap": 0.0}
    if regret:
        out["regret_gap"] = 0.0
    value_gaps, gain_gaps, tree_losses = [], [], []
    broken = decided = 0
    margin = np.tile(ref.init_margin(yi, K), (len(yi), 1))
    with ThreadPoolExecutor(blocks) as pool:
        bins = None
        if regret:
            edges = ref.quantile_edges(Xr, tp["nbins"])
            bins = np.concatenate(list(pool.map(
                lambda b: ref.bin_rows(Xr[b[0]:b[1]], edges, levels, B),
                rows)))
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
                if r >= check:
                    continue
                gk, hk = np.ascontiguousarray(g[:, k]), \
                    np.ascontiguousarray(h[:, k])
                G, H, C = ref.reaching_sums(
                    ref.resting_sums(leaf, gk, hk, N)).T
                reached = C > 0
                out["cover_gap"] = max(out["cover_gap"], float(np.max(
                    np.abs(tree["cover"] - C) / np.maximum(C, 1.0))))
                want = ref.leaf_value(G, H, lr)
                value_gaps.append(_gaps(tree["value"] - want, want,
                                        reached))
                sp = tree["is_split"] & reached
                kids = 2 * np.flatnonzero(sp) + 1
                gain = np.zeros(N)
                gain[sp] = (ref.gain_term(G[kids], H[kids])
                            + ref.gain_term(G[kids + 1], H[kids + 1])
                            - ref.gain_term(G[sp], H[sp]))
                gain_gaps.append(_gaps(tree["gain"] - gain, gain, sp))
                if r in regret and k in searched:
                    best = np.zeros(N)
                    may_set = np.zeros(N, dtype=bool)
                    for d, hist in enumerate(_level_hists(
                            bins, leaf, gk, hk, depth, B, pool, rows)):
                        gains, _, _, tot = ref.split_gains(
                            hist, is_set, tp["min_rows"])
                        bg = gains.reshape(len(tot), -1).max(axis=1)
                        ok = ref.may_split(bg, tot[:, 2], tp["min_rows"],
                                           tp["gamma"])
                        at = slice(2 ** d - 1, 2 ** (d + 1) - 1)
                        best[at] = np.where(ok, bg, 0.0)
                        may_set[at] = ref.may_split(
                            gains[:, is_set].reshape(len(tot), -1).max(
                                axis=1), tot[:, 2], tp["min_rows"],
                            tp["gamma"])
                    tree_losses.append(float(np.sum(best - gain)) / max(
                        float(best.sum()), 1e-300))
                    left = reached & ~tree["is_split"]
                    left[2 ** depth - 1:] = False
                    broken += int(np.sum(left & may_set))
                    decided += int(left.sum())
                broken += int(np.sum(sp & (gain <= tp["gamma"])))
                decided += int(sp.sum())
            margin += step
    out["split_gap"] = broken / max(decided, 1)
    for name, gaps in (("value_gap", np.concatenate(value_gaps)),
                       ("gain_gap", np.concatenate(gain_gaps))):
        if len(gaps):
            out[name] = float(np.quantile(gaps, JUDGED))
            out[name + "_99th"] = float(np.quantile(gaps, 0.99))
            out[name + "_worst"] = float(gaps.max())
    if regret:
        out["regret_gap"] = float(np.median(tree_losses))
        out["regret_gap_worst_tree"] = float(np.max(tree_losses))
    ll = ref.logloss(margin, yi)
    out["logloss_gap"] = abs(float(model["train_logloss"]) - ll) / ll
    return out
