"""The comparison that decides `correct` for a trained bagged forest
(H2O-3 DRF on 0/1 targets).

A configuration names its comparison (`"comparison": "drf_bagged"`); the
harness finds this file by that name. `neutral_model` takes the
program's trained forest to the plain form the reference reads — the
trees in value space, as `gbm_bernoulli` does, and the record the model
keeps of its trees' keys (`tree_draws`: a few bytes a tree), NOT the
bags: drawing them again is device work no user's job does, and
`neutral_model` runs inside the timed job. `compare`, after the window,
asks the program what each tree saw (`TreeDraws.tree_bag`,
`.tree_candidates`) and follows the model's own trees over the table
with the plain reference (`reference/drf_plain.py`, numpy float64): for
every node of the checked trees the (sum of y, count) of the bagged
rows that reach it, against the model's `cover` (exactly), `value` and
`gain`; the gain its splits took against the best the reference finds
among the node's handed-out candidates; the bags' and the candidates'
own statistics; and the metric the job reported against the
reference's logloss and exact AUC of the forest's probabilities.

A forest that carries `bags` and `candidates` itself (the reference put
in the program's place: `control_drf.py`, the tests) is read from
those. Every number is a gap, lower is better, and is correct while it
is at most its limit. The limits are data: the cell's file carries them.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import gbm_bernoulli
from reference import drf_plain as ref
from reference.gbm_plain import auc, bin_rows, quantile_edges

regret_trees = gbm_bernoulli.regret_trees


def neutral_model(m) -> dict:
    """The trees as `gbm_bernoulli.neutral_model` gives them (dense
    heaps, value-space thresholds, host float64) and the model's record
    of its trees' keys, as plain numbers."""
    draws = getattr(m, "tree_draws", None)
    if draws is None:
        # a program from before the forest handed out its bags: there
        # is nothing to hold its trees against — no result
        raise SystemExit("bench: this program's forest keeps no record of "
                         "what its trees saw (`tree_draws`), so the "
                         "drf_bagged comparison cannot decide `correct`")
    out = gbm_bernoulli.neutral_model(m)
    out["draws"] = dict(draws._asdict())      # plain numbers and lists
    return out


def handed_out(model: dict, trees: int):
    """(bags [trees, rows] bool, candidates [trees, N, F] bool) of the
    first ``trees`` trees: the forest's own where it carries them, else
    drawn again by the program from the keys the model kept."""
    if "bags" in model:
        return (np.asarray(model["bags"][:trees]),
                np.asarray(model["candidates"][:trees]))
    from h2o_kubernetes_tpu.models.gbm import TreeDraws

    draws = TreeDraws(**model["draws"])
    return (np.stack([draws.tree_bag(t) for t in range(trees)]),
            np.stack([draws.tree_candidates(t) for t in range(trees)]))


def _worst(gap: np.ndarray, scale: np.ndarray, mask: np.ndarray) -> float:
    """Largest |gap| over ``mask``, each against its own scale or the
    median one, whichever is larger (some are all but zero)."""
    if not mask.any():
        return 0.0
    s = np.abs(scale[mask])
    return float(np.max(np.abs(gap[mask]) / np.maximum(s, np.median(s))))


def _tree_numbers(tree, Xr, yf, bag, cand, mtries, regret):
    """One tree against the bagged rows: its gaps, and for a regret
    tree (``regret``: (bins, nbins, min_rows, gamma)) the gain to be
    had and the gain taken."""
    S, C = ref.node_sums(tree, Xr, yf, bag).T
    reached = C > 0
    out = {"cover_gap": float(np.max(
        np.abs(tree["cover"] - C) / np.maximum(C, 1.0)))}
    want = ref.leaf_value(S, C)
    out["value_gap"] = _worst(tree["value"] - want, want, reached)
    sp = tree["is_split"] & reached
    kids = 2 * np.flatnonzero(sp) + 1
    gain, terms = np.zeros(len(S)), np.zeros(len(S))
    terms[sp] = (ref.gain_term(S[kids], C[kids])
                 + ref.gain_term(S[kids + 1], C[kids + 1]))
    gain[sp] = terms[sp] - ref.gain_term(S[sp], C[sp])
    # a gain is the difference of its terms and is rounded as they are:
    # measured against them
    out["gain_gap"] = _worst(tree["gain"] - gain, terms, sp)
    inner = np.arange(len(S)) < len(S) // 2
    offered = cand.sum(axis=1)
    took_other = sp & ~cand[np.arange(len(S)), tree["feat"]]
    out["mtries_gap"] = float(np.max(
        np.abs(offered[inner] - mtries))) + float(took_other.sum())
    if regret is not None:
        bins, nbins, min_rows, gamma = regret
        best = ref.best_gains(tree, Xr, bins, yf, bag, cand, nbins,
                              min_rows, gamma)
        out["regret_gap"] = float(np.sum(best - gain)) / max(
            float(best.sum()), 1e-300)
    return out


def compare(model: dict, Xr: np.ndarray, y: np.ndarray, config: dict,
            cell: dict, seed: int, workers: int | None = None
            ) -> dict[str, float]:
    """``model`` in the neutral form (see `drf_plain.train`), ``Xr``
    [rows, F]. The configuration gives the parameters the forest was to
    be grown with; the cell how many trees are checked node by node
    (``check_trees``, the first ones) and how many of those have their
    splits held against the reference's best (``regret_trees``, drawn
    from ``seed``). Every tree is followed for the reported metric. A
    thread a tree (numpy drops the interpreter lock in its loops)."""
    params = config["params"]
    yf = y.astype(np.float64)
    F = Xr.shape[1]
    nbins = int(params["nbins"])
    rate = float(params["sample_rate"])
    mtries = int(params.get("mtries", -1))
    if mtries == -1:
        mtries = max(int(np.sqrt(F)), 1)
    elif not 0 < mtries < F:
        mtries = F
    check = min(int(cell["check_trees"]), len(model["trees"]))
    regret = regret_trees(check, int(cell.get("regret_trees", 0)), seed)
    bags, cands = handed_out(model, check)
    how = None
    if regret:
        how = (bin_rows(Xr, quantile_edges(Xr, nbins)), nbins,
               float(params.get("min_rows", 1.0)),
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
    out = {k: max(n[k] for n in per_tree if k in n)
           for k in ("cover_gap", "value_gap", "gain_gap", "mtries_gap")}
    if regret:
        out["regret_gap"] = max(n["regret_gap"] for n in per_tree
                                if "regret_gap" in n)
    # each bag keeps its share of the rows, and two trees' bags share
    # what independent draws would: one bag handed to every tree, or
    # no bag at all, reads here
    kept = bags.mean(axis=1)
    both = [float((bags[t] & bags[t + 1]).mean()) for t in range(check - 1)]
    out["bag_rate_gap"] = float(max(
        np.max(np.abs(kept - rate)),
        max((abs(b - rate * rate) for b in both), default=0.0)))
    ll = ref.logloss(p, yf)
    out["logloss_gap"] = abs(float(model["train_logloss"]) - ll) / ll
    out["auc_gap"] = abs(float(model["train_auc"]) - auc(p, yf))
    return out
