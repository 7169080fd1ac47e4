"""The comparison that decides `correct` for a bagged forest whose
categorical columns split on SETS of levels (H2O-3 DRF with
``categorical_encoding = "enum"``; configuration `drf-airline`).

`drf_bagged`'s numbers with `gbm_sets`' set descent, both imported and
neither edited: `neutral_model` is `gbm_sets.neutral_model` (a set
split as the level codes it sends left) with the record of the trees'
keys `drf_bagged` keeps; `compare`, after the window, asks the program
for every checked tree's bag and candidates (`drf_bagged.handed_out`)
and follows the model's own trees over the bagged rows with the plain
reference (`reference/drf_sets_plain.py`, numpy float64) — a set split
by the row's level code, a numeric one by its threshold, a missing
value by `na_left`. Held against it: every node's `cover` (exactly),
the worst node's `value` and the worst split's `gain` (the sums are
integers below 2^24, which float32 holds, so only the quotient and the
difference of the gain's terms round), the gain the regret trees' splits
took against the best of the reference's own search at every node rows
reach (levels in mean-response order, every prefix, over its own bins),
the bags' rate and independence, the candidates a node was offered, and
the metric the job reported against the reference's logloss and exact
AUC of the forest's probabilities. Every number is a gap, lower is
better; the limits are data, in the cell's file.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import drf_bagged
import gbm_sets
from gbm_bernoulli import regret_trees
from reference import drf_sets_plain as ref


def neutral_model(m) -> dict:
    """`gbm_sets.neutral_model` (dense heaps, value-space thresholds,
    the sets a model's splits send left, the splits by kind) and the
    model's record of its trees' keys, as plain numbers."""
    draws = getattr(m, "tree_draws", None)
    if draws is None:
        raise SystemExit("bench: this program's forest keeps no record of "
                         "what its trees saw (`tree_draws`), so the "
                         "drf_sets comparison cannot decide `correct`")
    out = gbm_sets.neutral_model(m)
    out["draws"] = dict(draws._asdict())
    return out


def _tree_numbers(tree, Xr, yf, bag, cand, mtries, regret):
    """One tree against its bagged rows: its gaps, and for a regret
    tree (``regret``: (bins, is_set, B, min_rows, gamma)) the share of
    the gain to be had that its splits left."""
    sums, leaf = ref.node_sums(tree, Xr, yf, bag)
    S, C = sums.T
    reached = C > 0
    out = {"cover_gap": float(np.max(
        np.abs(tree["cover"] - C) / np.maximum(C, 1.0)))}
    want = ref.leaf_value(S, C)
    out["value_gap"] = drf_bagged._worst(tree["value"] - want, want, reached)
    sp = tree["is_split"] & reached
    kids = 2 * np.flatnonzero(sp) + 1
    gain, terms = np.zeros(len(S)), np.zeros(len(S))
    terms[sp] = (ref.gain_term(S[kids], C[kids])
                 + ref.gain_term(S[kids + 1], C[kids + 1]))
    gain[sp] = terms[sp] - ref.gain_term(S[sp], C[sp])
    out["gain_gap"] = drf_bagged._worst(tree["gain"] - gain, terms, sp)
    inner = np.arange(len(S)) < len(S) // 2
    took_other = sp & ~cand[np.arange(len(S)), tree["feat"]]
    out["mtries_gap"] = float(np.max(
        np.abs(cand.sum(axis=1)[inner] - mtries))) + float(took_other.sum())
    if regret is not None:
        bins, is_set, B, min_rows, gamma = regret
        best = ref.best_gains(tree, leaf, bins[np.flatnonzero(bag)],
                              yf[bag], cand, is_set, B, min_rows, gamma)
        out["regret_gap"] = float(np.sum(best - gain)) / max(
            float(best.sum()), 1e-300)
    return out


def compare(model: dict, Xr: np.ndarray, y: np.ndarray, config: dict,
            cell: dict, seed: int, workers: int | None = None
            ) -> dict[str, float]:
    """``model`` in the neutral form (see `drf_sets_plain.train`),
    ``Xr`` [rows, F] with the categorical columns' level codes as
    numbers, ``config["levels"]`` [F] their level counts (0: numeric).
    Otherwise as `drf_bagged.compare`: the first ``check_trees`` trees
    node by node, ``regret_trees`` of them (drawn from ``seed``)
    against the reference's best, every tree for the reported metric;
    a thread a tree."""
    params = config["params"]
    levels = np.asarray(config["levels"], dtype=np.int64)
    yf = y.astype(np.float64)
    nbins = int(params["nbins"])
    rate = float(params["sample_rate"])
    mtries = ref.resolve_mtries(int(params.get("mtries", -1)), Xr.shape[1])
    check = min(int(cell["check_trees"]), len(model["trees"]))
    regret = regret_trees(check, int(cell.get("regret_trees", 0)), seed)
    bags, cands = drf_bagged.handed_out(model, check)
    how = None
    if regret:
        B = ref.bins_of(levels, nbins)
        how = (ref.bin_rows(Xr, ref.quantile_edges(Xr, nbins), levels, B),
               levels > 0, B, float(params.get("min_rows", 1.0)),
               float(params.get("min_split_improvement", 1e-5)))
    workers = workers or min(8, os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        prob = pool.submit(ref.forest_prob, model["trees"], Xr)
        per_tree = list(pool.map(
            lambda t: _tree_numbers(model["trees"][t], Xr, yf, bags[t],
                                    cands[t], mtries,
                                    how if t in regret else None),
            range(check)))
        p = prob.result()
    out = {k: max(n[k] for n in per_tree)
           for k in ("cover_gap", "value_gap", "gain_gap", "mtries_gap")}
    if regret:
        out["regret_gap"] = max(n["regret_gap"] for n in per_tree
                                if "regret_gap" in n)
    kept = bags.mean(axis=1)
    both = [float((bags[t] & bags[t + 1]).mean()) for t in range(check - 1)]
    out["bag_rate_gap"] = float(max(
        np.max(np.abs(kept - rate)),
        max((abs(b - rate * rate) for b in both), default=0.0)))
    ll = ref.logloss(p, yf)
    out["logloss_gap"] = abs(float(model["train_logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(model["train_auc"]) - ref.auc(p, yf))
    return out
