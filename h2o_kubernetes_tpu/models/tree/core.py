"""Shared histogram tree-growing core for GBM / DRF / XGBoost-hist.

This is the TPU redesign of the reference's SharedTree driver +
ScoreBuildHistogram2 MRTask + DTree split finding (hex/tree/SharedTree,
DHistogram, ScoreBuildHistogram2 — SURVEY.md §3.4): per level, every
row's (grad, hess, count) is accumulated into a per-node per-feature
per-bin histogram, histograms are all-reduced across row shards, and the
best split per node is an argmax over (feature, bin).

TPU-first choices (SURVEY.md §7 "hard parts"):
- dense per-row relative node ids instead of dynamic row partitions;
  dead rows carry id -1 and are masked out of histograms;
- the whole tree builds inside ONE jitted shard_map: local segment-sum
  histograms + `lax.psum` over the ROWS axis per level (the MRTask
  reduce), split finding replicated on every shard;
- trees are dense heaps padded to max_depth — no recompilation as the
  tree grows.

Split semantics: `bin <= split_bin` goes left. The NA bin is the last
bin; `na_left` per node records the learned NA direction (both
directions are scored, XGBoost-style). A job with set features
(`TreeParams.set_feats`: H2O-3's ``categorical_encoding="enum"``) keeps
`Tree.left_bins` beside them, the bins that go left at every node, and
every descent of such a tree reads that table alone (`_goes_right`).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ...runtime.mesh import ROWS


class TreeParams(NamedTuple):
    max_depth: int = 5
    n_bins: int = 256
    min_rows: float = 10.0          # min rows per leaf (on weighted counts)
    reg_lambda: float = 0.0         # H2O GBM has no L2 penalty; XGB uses 1.0
    reg_alpha: float = 0.0
    gamma: float = 0.0              # min split gain improvement
    mtries: int = -1                # per-node feature subsampling (DRF); -1=all
    min_child_weight: float = 0.0   # min hessian mass per child (XGBoost)
    hist_impl: str = "auto"         # auto | segment | pallas (ops/histogram)
    unit_hess: bool = False         # h ≡ 1 loss: 2-channel histograms
    # per feature, whether it splits on a SET of its bins (an enum under
    # categorical_encoding="enum": BinSpec.set_feats); () = none does,
    # and the program is the one it was before sets existed
    set_feats: tuple = ()


class Tree(NamedTuple):
    """Dense heap tree: node i has children 2i+1, 2i+2. [N]=2^(d+1)-1."""

    split_feat: jax.Array   # int32 [N], -1 for leaves
    split_bin: jax.Array    # int32 [N]
    na_left: jax.Array      # bool  [N] NA direction
    is_split: jax.Array     # bool  [N]
    value: jax.Array        # f32   [N] leaf value (valid where not split)
    gain: jax.Array         # f32   [N] split gain (varimp attribution)
    # f32 [N] training weight mass reaching the node (global, psum'd) —
    # TreeSHAP's r_j. Defaulted so binary models pickled BEFORE this
    # field existed (6-tuple Trees) still unpickle; load_model backfills
    # the None (persist.py) and predict_contributions rejects it.
    cover: jax.Array = None
    # bool [2^max_depth - 1, B]: at inner node i, the bins that go LEFT
    # — THE description of a split in a job with set features (numeric
    # cuts and the NA bin are written into it too: `bin <= split_bin`,
    # `na_left`), read by the grower, `descend_tree` and so by every
    # scorer of such a tree. None where no feature takes set splits:
    # `split_bin` / `na_left` then say it all.
    left_bins: jax.Array = None


def set_split_reason(trees: "Tree", what: str) -> str | None:
    """Why ``what`` cannot take ``trees``, if they hold set splits:
    ``what`` reads `split_bin` as a threshold, and a split that sends
    a set of levels left has none. None for every other ensemble."""
    if getattr(trees, "left_bins", None) is None:
        return None
    return (f"{what} cannot carry a set split yet: this model was "
            "trained with categorical_encoding='enum' (a split sends a "
            "SET of an enum's levels left). Score it with predict(), "
            "or train with categorical_encoding='label_encoder'.")


def require_ordinal(trees: "Tree", what: str) -> None:
    """Raise `set_split_reason`, if there is one: a model trained with
    ``categorical_encoding="enum"`` must never be scored ordinally."""
    reason = set_split_reason(trees, what)
    if reason:
        raise ValueError(reason)


def _soft_thresh(g, alpha):
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - alpha, 0.0)


def _leaf_value(G, H, p: TreeParams):
    return -_soft_thresh(G, p.reg_alpha) / (H + p.reg_lambda + 1e-10)


def _gain_term(G, H, p: TreeParams):
    return _soft_thresh(G, p.reg_alpha) ** 2 / (H + p.reg_lambda + 1e-10)


# histogram accumulation lives in ops/histogram.py (segment_sum on CPU,
# the Pallas one-hot-matmul kernel on TPU)
from ...ops.histogram import block_columns as _block_columns
from ...ops.histogram import build_histogram as _build_histogram_op
from ...ops.histogram import expand_unit_hess as _expand_unit_hess
from ...ops.histogram import node_blocks as _node_blocks
from ...ops.histogram import resolve_impl as _resolve_impl
from .rank import groups_specs, rank_grad_hess


def _split_gains(left, tot4, p: TreeParams):
    """Split gain of every candidate's left stats [..., 3] against the
    node totals ``tot4`` [n, 1, 1, 3] — THE one gain formula, shared
    by `_find_splits` and `_find_splits_efb` so the EFB exactness
    contract (identical gains for identical left stats) cannot drift."""
    right = tot4 - left
    Gl, Hl, Cl = left[..., 0], left[..., 1], left[..., 2]
    Gr, Hr, Cr = right[..., 0], right[..., 1], right[..., 2]
    parent = _gain_term(tot4[..., 0], tot4[..., 1], p)
    raw = _gain_term(Gl, Hl, p) + _gain_term(Gr, Hr, p) - parent
    ok = (Cl >= p.min_rows) & (Cr >= p.min_rows)
    if p.min_child_weight > 0:
        ok &= (Hl >= p.min_child_weight) & (Hr >= p.min_child_weight)
    return jnp.where(ok, raw, -jnp.inf)


def _find_splits(hist, p: TreeParams, feat_ok=None, efb=None):
    """Best split per node from a [n_nodes, F, B, 3] histogram.

    With ``efb`` (an efb.EFBLuts pytree) the histogram is in BUNDLED
    column space and split finding dispatches to ``_find_splits_efb``,
    which decodes the winner back to the ORIGINAL (feature, bin) pair
    — downstream (tree emission, flattening, MOJO, serving) never sees
    a bundle.

    Scores every (feature, threshold-bin) cut with the NA bin (last)
    assigned to each side in turn, XGBoost-style learned NA direction.
    `feat_ok`: optional [n_nodes, F] bool mask of allowed features
    (per-tree column sampling and DRF per-node mtries) — always in
    ORIGINAL feature space, whatever the histogram width.
    Returns (feat, bin, na_left, can_split, node_value, best_gain,
    cover, left, right, left_bins) per node — cover is the node's total
    weight mass (TreeSHAP's r_j); left/right are the chosen split's side
    totals [n, 3] (== the children's node totals, NA side applied),
    which the grower uses as the final level's leaf stats.

    Set features (``p.set_feats``; Fisher 1958, CART §4.2.2: for a
    convex loss the best two-way partition of the levels is a prefix of
    their order by mean response): in every node the bins of a set
    feature are put in the order of `_set_order` before the prefixes
    are scanned, so candidate ``bin`` k of such a feature is "the first
    k+1 bins of that order go left"; a numeric feature's order is its
    codes' and nothing changes for it. ``left_bins`` [n, B] then says,
    for the winner of each node, which bins go left (the NA bin by
    ``na_left``); it is None without set features.
    """
    if efb is not None:
        return _find_splits_efb(hist, p, efb, feat_ok) + (None,)
    nb = hist.shape[2]
    na = hist[:, :, nb - 1, :]                 # [n, F, 3]
    body = hist[:, :, : nb - 1, :]
    order = None
    if any(p.set_feats):
        with jax.named_scope("set_order"):
            order = _set_order(body, p)
            body = jnp.take_along_axis(body, order[..., None], axis=2)
    cum = jnp.cumsum(body, axis=2)             # left stats, NA excluded
    tot = cum[:, :, -1, :] + na                # [n, F, 3] node totals
    totn = tot[:, 0:1, :]                      # same for every feature

    tot4 = totn[:, :, None, :]                 # [n, 1, 1, 3]

    gain_na_r = _split_gains(cum, tot4, p)              # NA goes right
    gain_na_l = _split_gains(cum + na[:, :, None, :], tot4, p)  # NA left
    na_left_better = gain_na_l > gain_na_r
    gain = jnp.maximum(gain_na_l, gain_na_r)            # [n, F, B-1]
    if feat_ok is not None:
        gain = jnp.where(feat_ok[:, :, None], gain, -jnp.inf)

    n_nodes, F = gain.shape[0], gain.shape[1]
    flat = gain.reshape(n_nodes, F * (nb - 1))
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], 1)[:, 0]
    feat = (best // (nb - 1)).astype(jnp.int32)
    bin_ = (best % (nb - 1)).astype(jnp.int32)
    na_l = jnp.take_along_axis(
        na_left_better.reshape(n_nodes, -1), best[:, None], 1)[:, 0]

    # (G, H, C) of the chosen split's LEFT side (NA routed per na_l):
    # these ARE the left child's node totals, and right = parent-left —
    # the grower derives the final level's leaf stats from them instead
    # of paying one more full-row histogram pass per tree
    def pick(left4):                                   # [n, F, B-1, 3]
        return jnp.take_along_axis(
            left4.reshape(n_nodes, F * (nb - 1), 3),
            best[:, None, None], 1)[:, 0]              # [n, 3]
    left = jnp.where(na_l[:, None], pick(cum + na[:, :, None, :]),
                     pick(cum))
    right = totn[:, 0, :] - left

    G, H, C = totn[:, 0, 0], totn[:, 0, 1], totn[:, 0, 2]
    can_split = (best_gain > p.gamma) & (C >= 2 * p.min_rows) & \
        jnp.isfinite(best_gain)
    value = _leaf_value(G, H, p)
    left_bins = None
    if order is not None:
        with jax.named_scope("set_order"):
            # the winner's order, inverted: a bin's place in it
            mine = jnp.take_along_axis(
                order, feat[:, None, None], axis=1)[:, 0]     # [n, B-1]
            place = jnp.argsort(mine, axis=1)
            left_bins = jnp.concatenate(
                [place <= bin_[:, None], na_l[:, None]], axis=1)
    return (feat, bin_, na_l, can_split, value, best_gain, C,
            left, right, left_bins)


def _set_order(body, p: TreeParams):
    """int32 [n, F, B-1]: for every node and feature, the body bins in
    the order their prefixes are scanned. A set feature's bins that
    hold rows (count > 0) come first, by G / (H + lambda) ascending —
    the Newton step's order, which for squared error is the order by
    mean response H2O-3 sorts by — equal ratios by code; its bins
    without rows in the node (levels absent from it, and the bins past
    its last level) come after them, by code, so a scanned prefix ends
    before them wherever it can and an absent level goes RIGHT. A
    numeric feature keeps the order of its codes."""
    G, H, C = body[..., 0], body[..., 1], body[..., 2]
    codes = jnp.arange(body.shape[2], dtype=jnp.float32)
    ratio = jnp.where(C > 0, G / (H + p.reg_lambda + 1e-10), jnp.inf)
    is_set = jnp.asarray(p.set_feats, dtype=bool)[None, :, None]
    return jnp.argsort(jnp.where(is_set, ratio, codes), axis=2,
                       stable=True).astype(jnp.int32)


def _goes_right(rowbin, b, nl, n_bins: int, left_bins=None, node=None,
                batched: bool = False):
    """Per row, whether it goes to the right child — THE one reading of
    a split, shared by the grower's descent and `descend_tree`. Without
    set features: the NA bin by ``nl``, else `rowbin > b`. With them:
    the row's bin is looked up among its node's ``left_bins`` ([nodes,
    B]; ``node`` is each row's row of that table), which holds numeric
    cuts and the NA direction as well. Under the class batch
    (``batched``: a `vmap` over K class trees, where a by-row gather is
    a batched gather) the table is read by `_set_word_lookup`."""
    if left_bins is None:
        is_na = rowbin == n_bins - 1
        return jnp.where(is_na, ~nl, rowbin > b)
    with jax.named_scope("set_descend"):
        if batched:
            return ~_set_word_lookup(left_bins, node, rowbin)
        return ~left_bins.reshape(-1)[node * n_bins + rowbin]


def _set_word_lookup(left_bins, node, rowbin):
    """``left_bins[node, rowbin]`` per row, with the table's bins packed
    32 to an int32 word ([nodes, B/32] words: 128 entries at 16 nodes
    of 256 bins) and each row's word read by `_node_lookup` — a select
    over the entries where they are few, so that the class batch keeps
    a class's table in its own slots and asks no batched gather — then
    the row's bit of it."""
    n, B = left_bins.shape
    W = -(-B // 32)
    bits = jnp.pad(left_bins, ((0, 0), (0, 32 * W - B))).reshape(n, W, 32)
    words = jnp.sum(bits.astype(jnp.uint32)
                    << jnp.arange(32, dtype=jnp.uint32), axis=-1)
    word = _node_lookup(lax.bitcast_convert_type(words, jnp.int32)
                        .reshape(-1), node * W + (rowbin >> 5))
    return ((word >> (rowbin & 31)) & 1) != 0


def _find_splits_efb(hist, p: TreeParams, efb, feat_ok):
    """EFB split finding: the histogram is [n_nodes, Fb, B, 3] in
    BUNDLED column space (models/tree/efb.py); every candidate slot is
    scored as the ORIGINAL (feature, threshold-bin) cut it encodes and
    the winner is returned decoded.

    Exactness contract (docs/SCALING.md "Wide sparse frames"): the
    candidate set and the tie-break order (original feat-major /
    bin-minor via ``efb.perm``) match `_find_splits` exactly;
    passthrough (dense) columns' gains are computed by the identical
    masked-cumsum program and are bitwise-equal; bundled members'
    default-bin mass is reconstructed as ``node_total - member_mass``
    — an exact set identity under zero conflicts whose f32
    reassociation is bitwise-neutral whenever the sums are exact
    (integer counts, dyadic gradients) and float-tolerance otherwise,
    the same caveat ooc.py documents for chunk-boundary sums."""
    nb = hist.shape[2]
    n, Fb = hist.shape[0], hist.shape[1]
    S = nb - 1
    sf = efb.slot_feat[:, :S]                    # [Fb, S]
    sb = efb.slot_bin[:, :S]
    body_mask = (efb.slot_feat >= 0) & (efb.slot_bin < nb - 1)  # [Fb, nb]
    body = hist[:, :, :S, :] * body_mask[None, :, :S, None]
    cum = jnp.cumsum(body, axis=2)               # [n, Fb, S, 3]
    # node totals from column 0: body cumsum tail + the non-body mass
    # (default slot, member NA slots; zeros only for a passthrough
    # column, where this reduces to the unbundled cum[-1] + na)
    nonbody0 = ~body_mask[0]
    totn = cum[:, 0, -1, :] + jnp.sum(
        hist[:, 0, :, :] * nonbody0[None, :, None], axis=1)     # [n, 3]
    tot4 = totn[:, None, None, :]
    # per-candidate member stats: NA mass, member-local prefix (left
    # stats excluding default/NA), member total (body + NA)
    na_idx = jnp.broadcast_to(efb.na_slot[None, :, :S, None],
                              (n, Fb, S, 3))
    na_c = jnp.take_along_axis(hist, na_idx, axis=2)            # [n,Fb,S,3]
    mstart = efb.mstart[:, :S]
    pre_idx = jnp.broadcast_to(
        jnp.maximum(mstart - 1, 0)[None, :, :, None], cum.shape)
    pre = jnp.take_along_axis(cum, pre_idx, axis=2)
    started = (mstart > 0)[None, :, :, None]
    mleft = jnp.where(started, cum - pre, cum)
    end_idx = jnp.broadcast_to(efb.mend[None, :, :S, None], cum.shape)
    mtot = jnp.take_along_axis(cum, end_idx, axis=2)
    mtot = jnp.where(started, mtot - pre, mtot)
    has_rem = efb.has_rem[:, :S]
    # default-bin remainder: every node row not in this member's own
    # slots sits at the member's default bin (zero-conflict identity)
    rem = jnp.where(has_rem[None, :, :, None],
                    tot4 - (mtot + na_c), 0.0)
    add_rem = has_rem & (sb >= efb.dbin[:, :S])
    left = mleft + jnp.where(add_rem[None, :, :, None], rem, 0.0)

    gain_na_r = _split_gains(left, tot4, p)          # NA goes right
    gain_na_l = _split_gains(left + na_c, tot4, p)   # NA goes left
    na_left_better = gain_na_l > gain_na_r
    gain = jnp.maximum(gain_na_l, gain_na_r)     # [n, Fb, S]
    cand = body_mask[:, :S]
    if feat_ok is None:
        feat_ok = jnp.ones((n, efb.feat_col.shape[0]), dtype=bool)
    fok = feat_ok[:, jnp.maximum(sf, 0).reshape(-1)].reshape(n, Fb, S)
    gain = jnp.where(cand[None, :, :] & fok, gain, -jnp.inf)
    flat = gain.reshape(n, Fb * S)[:, efb.perm]  # (feat, bin) order
    best_rank = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best_rank[:, None], 1)[:, 0]
    best = efb.perm[best_rank]                   # flat (col, slot) index
    feat = jnp.maximum(sf.reshape(-1)[best], 0).astype(jnp.int32)
    bin_ = jnp.clip(sb.reshape(-1)[best], 0, nb - 2).astype(jnp.int32)
    na_l = jnp.take_along_axis(
        na_left_better.reshape(n, -1), best[:, None], 1)[:, 0]

    def pick(l4):
        return jnp.take_along_axis(
            l4.reshape(n, Fb * S, 3), best[:, None, None], 1)[:, 0]
    left_w = jnp.where(na_l[:, None], pick(left + na_c), pick(left))
    right_w = totn - left_w

    G, H, C = totn[:, 0], totn[:, 1], totn[:, 2]
    can_split = (best_gain > p.gamma) & (C >= 2 * p.min_rows) & \
        jnp.isfinite(best_gain)
    value = _leaf_value(G, H, p)
    return (feat, bin_, na_l, can_split, value, best_gain, C,
            left_w, right_w)


def _row_column(binned, col):
    """``binned[r, col[r]]`` as int32, by a select over the columns
    and not a gather: one fused pass that streams the binned matrix
    once and keeps, for each row, the code of the column that equals
    ``col[r]`` (PERF.md section 6, PR 31: at 4,194,304 x 28 on the chip
    0.2 ms a call inside the boost program, what one read of the matrix
    takes, against the gather's 72-88 ms; alone 0.9 ms, 4.1 ms at 256
    columns). Bitwise the gather for every ``col`` in [0, F)."""
    cols = jnp.arange(binned.shape[1], dtype=jnp.int32)
    return jnp.sum(jnp.where(col[:, None] == cols,
                             binned.astype(jnp.int32), 0), axis=1)


# the most entries a node table may have for a by-row lookup into it to
# be a select over its entries; past it, a gather (PERF.md section 6:
# `tools/lookup_forms.py` on a v5e at 4,194,304 rows and under the
# class batch at 7 x 581,632: at 2,048 entries a select of one word
# takes 10.4-11.1 ms against the gather's 31-37, at 8,191 74-77)
_SELECT_MAX_ENTRIES = 2048


def node_lookup_form(entries: int) -> str:
    """How `_node_lookup` reads a node table of ``entries`` entries by
    row: ``select`` or ``gather``. THE rule, for the program as it is
    traced and for `node_lookup_forms`, which counts it."""
    return "select" if entries <= _SELECT_MAX_ENTRIES else "gather"


def node_lookup_forms(max_depth: int) -> list[str]:
    """The forms of one tree's by-row lookups in a boost scan: each
    level's descent (a table of 2^d nodes: `_split_of_rows`), then the
    margin's leaf value (the heap's 2^(max_depth+1) - 1 entries)."""
    return [node_lookup_form(2 ** d) for d in range(max_depth)] + \
        [node_lookup_form(2 ** (max_depth + 1) - 1)]


def _node_lookup(table, idx):
    """``table[..., idx]`` for a node table [..., entries] and each
    row's entry ``idx`` [..., rows] in [0, entries), leading batch axes
    alike. Under `node_lookup_form`'s rule either a gather or a select:
    every entry compared with ``idx`` and the one that matches kept, in
    one fused pass over [entries, rows] that a batch axis leaves a
    select (under `vmap` a gather is a batched gather, ~20 ns a row
    whatever the table: PERF.md section 6). The select
    sums the entries' 32 bits as integers, one of them non-zero, so it
    is bitwise the gather: a `-0.0` leaf stays `-0.0`."""
    if node_lookup_form(table.shape[-1]) == "gather":
        # `t[i]` a table: `take_along_axis`, or any select after the
        # gather, asks the chip's compiler for 137 MB more temporaries
        # in a depth-12 forest at 4,194,304 rows
        gather = lambda t, i: t[i]                      # noqa: E731
        for _ in range(idx.ndim - 1):
            gather = jax.vmap(gather)
        return gather(table, idx)
    bits = table.astype(jnp.int32) if table.dtype == jnp.bool_ \
        else lax.bitcast_convert_type(table, jnp.int32)
    hit = jnp.arange(table.shape[-1], dtype=idx.dtype)[:, None] == \
        idx[..., None, :]                                # [..., E, rows]
    got = jnp.sum(jnp.where(hit, bits[..., :, None], 0), axis=-2)
    return got.astype(jnp.bool_) if table.dtype == jnp.bool_ \
        else lax.bitcast_convert_type(got, table.dtype)


def _split_of_rows(idx, feat, bin_, na_l, can, n_feat: int, n_bins: int):
    """(feat, bin, na_left, can) of each row's node, ``idx`` its entry
    of the level's tables: the four packed into one int32 word a node
    and read by ONE `_node_lookup`, or two where a feature index and a
    bin do not fit in 29 bits together."""
    sb = max(n_bins - 1, 1).bit_length()
    low = (bin_ << 2) | (na_l.astype(jnp.int32) << 1) | can.astype(jnp.int32)
    if max(n_feat - 1, 1).bit_length() + sb + 2 <= 31:
        word = _node_lookup(low | (feat << (sb + 2)), idx)
        f = word >> (sb + 2)
    else:
        f, word = _node_lookup(feat, idx), _node_lookup(low, idx)
    return (f, (word >> 2) & ((1 << sb) - 1), (word & 2) != 0,
            (word & 1) != 0)


def row_orig_bins(binned, f, efb):
    """Per-row ORIGINAL-space bin of (per-row) feature ``f`` — the ONE
    decode both the fused grower and the out-of-core descent use.
    Unbundled: the row's code in column f (a select over the columns,
    `_row_column`). Bundled: select the row's bundle slot from feature
    f's column, then LUT-decode (rows whose slot belongs to another
    member sit at f's default bin; a member NA slot decodes to the NA
    bin, preserving learned NA routing)."""
    if efb is None:
        return _row_column(binned, f)
    col = efb.feat_col[f]
    s = _row_column(binned, col)
    sf = efb.slot_feat[col, s]
    sb = efb.slot_bin[col, s]
    return jnp.where(sf == f, sb, efb.feat_default[f]).astype(jnp.int32)


def level_candidates(key, d: int, col_mask, mtries: int):
    """[2^d, F] bool: the features each node of level ``d`` may split
    on, a pure function of the tree's key. Every node has the tree's
    column sample ``col_mask``; with ``mtries`` in (0, F) each node
    keeps exactly the ``mtries`` of them that draw lowest (DRF;
    reference: DTree per-split feature sampling, SURVEY.md §2b C10).
    Equal draws are told apart by the feature's index: two of a
    node's float32 draws are equal in about one node of 20,000 at 28
    features, and a tie at the ``mtries``-th place would offer that
    node one feature more. The grower calls this, and so does
    `tree_candidates` when a model is asked what a tree's nodes were
    offered."""
    F = col_mask.shape[0]
    feat_ok = jnp.broadcast_to(col_mask[None, :], (2 ** d, F))
    if 0 < mtries < F:
        r = jax.random.uniform(jax.random.fold_in(key, d), (2 ** d, F))
        r = jnp.where(feat_ok, r, jnp.inf)
        f = jnp.broadcast_to(jnp.arange(F), r.shape)
        r_sorted, f_sorted = lax.sort((r, f), dimension=1, num_keys=2)
        kth_r = r_sorted[:, mtries - 1: mtries]
        kth_f = f_sorted[:, mtries - 1: mtries]
        feat_ok = feat_ok & ((r < kth_r) | ((r == kth_r) & (f <= kth_f)))
    return feat_ok


# What ordering a tree's rows by node block saves and costs, in ns a
# row on a v5e (PERF.md section 6): a hi block of a blocked level
# costs `_BLOCK_NS` a row, a column it histograms (`block_columns`) and
# a channel (the ledger's one-block call at the cap), and the compacted
# level `_COMPACT_NS` whatever its blocks (`drf-airline.train`'s);
# the order costs `_ORDER_NS` a row (the sort and the scatter back) and
# `_ARRAY_NS` a row an array it gathers (`tools/hist_forms.py
# --compact`, each alone)
_BLOCK_NS = 1.095
_COMPACT_NS = 1.386
_ORDER_NS = 10.0
_ARRAY_NS = 15.6


def _level_blocks(p: TreeParams, d: int) -> tuple:
    """(n_ht, nodes a block) of level ``d``'s histogram call: the
    root's one node, else the 2^(d-1) left children (`node_blocks`)."""
    return _node_blocks(2 ** max(d - 1, 0), p.n_bins)


def compact_depth(p: TreeParams, F: int, batched: bool = False):
    """The depth at whose start one tree orders its rows by node block,
    or None where it keeps the caller's order — THE rule, for the
    program as it is traced and for `hist_level_forms`, which counts it.

    A level past one hi block histograms its left children in blocks of
    whole nodes, so a row's block is its ancestor at depth log2(n_ht),
    and rows ordered by their node at depth ``s`` are grouped by every
    shallower ancestor: one order at the deeper of the first blocked
    level and the deepest level's block depth serves every level from
    ``s`` on, each block over its own row tiles (`build_histogram`'s
    ``starts``). Engaged where every level past one hi block holds
    whole nodes a block, on the Pallas kernel and outside the class
    batch's `vmap` (an order a class would copy the shared codes K
    times), and where what the compacted levels spare (n_ht blocks a
    level for one compacted call, each every row's) costs more than
    the order and the arrays it gathers (a row gather costs the same
    at 8 columns of 16-bit codes and at 28 of 8-bit ones)."""
    if batched or _resolve_impl(p.hist_impl) != "pallas":
        return None
    blocked = [d for d in range(1, p.max_depth)
               if _level_blocks(p, d)[0] > 1]
    if not blocked or not all(_level_blocks(p, d)[1] for d in blocked):
        return None
    s = max(blocked[0], _level_blocks(p, blocked[-1])[0].bit_length() - 1)
    C = 2 if p.unit_hess else 3
    saved = sum(_level_blocks(p, d)[0] * _BLOCK_NS - _COMPACT_NS
                for d in blocked if d >= s) * _block_columns(F, C) * C
    # the codes, g, w, the node ids and the leaf (and h where it is not 1)
    return s if saved > _ORDER_NS + _ARRAY_NS * (C + 3) else None


def hist_level_forms(p: TreeParams, F: int,
                     batched: bool = False) -> list[str]:
    """The form of each histogram call of one tree, the root's first:
    ``fact`` (one hi block), ``blocked`` (several, every row tile
    against each) or ``compacted`` (several, over rows ordered by node
    block: `compact_depth`)."""
    s = compact_depth(p, F, batched)
    return ["fact" if _level_blocks(p, d)[0] == 1
            else "blocked" if s is None or d < s else "compacted"
            for d in range(p.max_depth)]


def _order_rows(s: int, rel, w, rows):
    """One shard's rows ordered by their node at depth ``s`` (``rel``),
    the dead ones (``rel`` < 0 or ``w`` 0) after every node, each node's
    in the caller's order → (order, bounds, ``rows`` ordered):
    ``order[i]`` is the caller's index of row i, ``bounds`` [2^s + 1]
    each node's first row (the last: the first row past every live
    one). A dead row never comes back, so the dead stay last."""
    key = jnp.where((rel >= 0) & (w > 0), rel, 2 ** s)
    key, order = lax.sort((key, jnp.arange(rel.shape[0], dtype=jnp.int32)),
                          num_keys=1, is_stable=True)
    bounds = jnp.searchsorted(
        key, jnp.arange(2 ** s + 1, dtype=jnp.int32)).astype(jnp.int32)
    return order, bounds, [x[order] for x in rows]


def _grow_tree_shard(binned, g, h, w, col_mask, key, p: TreeParams,
                     efb=None, batched: bool = False):
    """Per-shard tree build (runs under shard_map; histograms psum'd).

    Returns (Tree, leaf_node): `leaf_node` is each row's final absolute
    heap index — the grower already walks each row to its resting node,
    so the boost loop reads `tree.value[leaf_node]` instead of paying a
    second full heap descent per tree (predict_tree).

    ``efb``: optional bundle LUTs (models/tree/efb.py) — ``binned`` is
    then the BUNDLED matrix, histograms/psums run at bundled width,
    and splits/descents are decoded to original feature space.

    Each phase of a level traces under a `jax.named_scope` —
    `level_hist`, `hist_psum`, `sibling`, `split_find`, `descend`, and
    `leaves` for the last level (the words ooc.py's host spans use):
    metadata only, the operations' `op_name` in a compiled program and
    in a profile.

    Where `compact_depth` engages (``batched``: under the class batch's
    `vmap`, where it never does) the rows are ordered by node block
    once, under `row_order`, and every level from there on reads them
    in that order; the leaf a row is returned in the caller's order.
    """
    # col_mask is in ORIGINAL feature space (== binned width only when
    # efb is None)
    N = 2 ** (p.max_depth + 1) - 1
    split_feat = jnp.full(N, -1, dtype=jnp.int32)
    split_bin = jnp.zeros(N, dtype=jnp.int32)
    na_left = jnp.zeros(N, dtype=bool)
    is_split = jnp.zeros(N, dtype=bool)
    value = jnp.zeros(N, dtype=jnp.float32)
    gain = jnp.zeros(N, dtype=jnp.float32)
    cover = jnp.zeros(N, dtype=jnp.float32)
    left_bins = jnp.zeros((2 ** p.max_depth - 1, p.n_bins), dtype=bool) \
        if any(p.set_feats) else None

    rel = jnp.zeros(binned.shape[0], dtype=jnp.int32)   # relative node @ lvl
    abs_node = jnp.zeros(binned.shape[0], dtype=jnp.int32)

    hist_prev = None        # parent histograms for sibling subtraction
    can_prev = None
    order_at = compact_depth(p, binned.shape[1], batched)
    order = None
    for d in range(p.max_depth + 1):
        n_nodes = 2 ** d
        off = n_nodes - 1
        if d == p.max_depth:
            # final level: every node is a forced leaf, and its
            # (G, H, C) totals are EXACTLY the parent's chosen-split
            # side stats (same rows, NA routing included) — already in
            # hand from _find_splits at the previous level. Rounds 2-3
            # built a histogram here (full at first — half the tree's
            # matmul work — then single-bin); now it costs NOTHING:
            # no row-stream pass, no psum.
            with jax.named_scope("leaves"):
                if d == 0:
                    # depth-0 stump: no parent level exists — one
                    # single-bin pass for the root totals
                    zero_bin = jnp.zeros((binned.shape[0], 1),
                                         dtype=binned.dtype)
                    tot = _build_histogram_op(zero_bin, rel, g, h, w, 1,
                                              1, impl=p.hist_impl,
                                              unit_hess=p.unit_hess)
                    tot = lax.psum(tot, ROWS)
                    if p.unit_hess:
                        tot = _expand_unit_hess(tot)
                    tot = tot[:, 0, 0, :]
                else:
                    tot = jnp.where(can_prev[:, None, None],
                                    jnp.stack([left_prev, right_prev],
                                              axis=1),
                                    0.0).reshape(n_nodes, 3)  # child order
                idx = off + jnp.arange(n_nodes)
                value = value.at[idx].set(
                    _leaf_value(tot[:, 0], tot[:, 1], p))
                cover = cover.at[idx].set(tot[:, 2])
            break
        if d == order_at:
            with jax.named_scope("row_order"):
                order, bounds, (binned, g, h, w, rel, abs_node) = \
                    _order_rows(d, rel, w, (binned, g, h, w, rel, abs_node))
        if d == 0:
            with jax.named_scope("level_hist"):
                hist = _build_histogram_op(binned, rel, g, h, w, 1,
                                           p.n_bins, impl=p.hist_impl,
                                           unit_hess=p.unit_hess)
            with jax.named_scope("hist_psum"):
                hist = lax.psum(hist, ROWS)             # MRTask reduce
            if p.unit_hess:
                hist = _expand_unit_hess(hist)
        else:
            # sibling subtraction (the XGBoost/LightGBM trick): histogram
            # only LEFT children, derive right = parent - left. Halves
            # the hot-loop FLOPs and the psum payload at every level.
            # Valid because every live row of a split parent lands in
            # exactly one child; children of non-split parents are
            # zeroed so _find_splits can't fabricate splits from the
            # stale parent mass.
            with jax.named_scope("level_hist"):
                left_rel = jnp.where((rel >= 0) & (rel % 2 == 0),
                                     rel // 2, -1)
                # rows ordered at depth `order_at`: hi block b of this
                # level is their nodes [b, b+1) · 2^order_at / n_ht
                starts = None if order is None else \
                    bounds[::2 ** order_at // _level_blocks(p, d)[0]]
                hist_l = _build_histogram_op(binned, left_rel, g, h, w,
                                             n_nodes // 2, p.n_bins,
                                             impl=p.hist_impl,
                                             unit_hess=p.unit_hess,
                                             starts=starts)
            with jax.named_scope("hist_psum"):
                hist_l = lax.psum(hist_l, ROWS)
            if p.unit_hess:
                hist_l = _expand_unit_hess(hist_l)
            with jax.named_scope("sibling"):
                parent = jnp.where(can_prev[:, None, None, None],
                                   hist_prev, 0.0)
                hist_l = jnp.where(can_prev[:, None, None, None], hist_l,
                                   0.0)
                hist_r = parent - hist_l
                hist = jnp.stack([hist_l, hist_r], axis=1).reshape(
                    n_nodes, binned.shape[1], p.n_bins, 3)
        with jax.named_scope("split_find"):
            feat_ok = level_candidates(key, d, col_mask, p.mtries)
            (feat, bin_, na_l, can, val, g_best, cov, left_ch,
             right_ch, lb) = _find_splits(hist, p, feat_ok, efb)
            idx = off + jnp.arange(n_nodes)
            if lb is not None:
                left_bins = left_bins.at[idx].set(lb)
            split_feat = split_feat.at[idx].set(jnp.where(can, feat, -1))
            split_bin = split_bin.at[idx].set(bin_)
            na_left = na_left.at[idx].set(na_l)
            is_split = is_split.at[idx].set(can)
            value = value.at[idx].set(val)
            gain = gain.at[idx].set(jnp.where(can, g_best, 0.0))
            cover = cover.at[idx].set(cov)
        hist_prev, can_prev = hist, can
        left_prev, right_prev = left_ch, right_ch
        # descend rows: dead rows stay dead; rows in non-split nodes die
        with jax.named_scope("descend"):
            live = rel >= 0
            safe_rel = jnp.where(live, rel, 0)
            f, b, nl, c = _split_of_rows(safe_rel, feat, bin_, na_l, can,
                                         col_mask.shape[0], p.n_bins)
            rowbin = row_orig_bins(binned, f, efb)
            go_right = _goes_right(rowbin, b, nl, p.n_bins, lb, safe_rel,
                                   batched)
            child = 2 * rel + go_right.astype(jnp.int32)  # rel at d+1
            moved = live & c
            rel = jnp.where(moved, child, -1)
            abs_node = jnp.where(moved, (2 ** (d + 1) - 1) + child,
                                 abs_node)

    if order is not None:
        with jax.named_scope("row_order"):
            abs_node = jnp.zeros_like(abs_node).at[order].set(
                abs_node, unique_indices=True)
    return Tree(split_feat, split_bin, na_left, is_split, value, gain,
                cover, left_bins), abs_node


def _grad_hess(distribution: str, margin, y):
    """Gradient/hessian of the boosting loss at the current margin
    (hex/genmodel DistributionFamily analogs — see models/gbm.py)."""
    if distribution == "gaussian":
        return margin - y, jnp.ones_like(margin)
    if distribution == "bernoulli":
        p = jax.nn.sigmoid(margin)
        return p - y, p * (1.0 - p)
    if distribution == "poisson":
        mu = jnp.exp(margin)
        return mu - y, mu
    if distribution == "gamma":
        # gamma deviance, log link: g = 1 - y·e^{-f}, h = y·e^{-f}
        ye = y * jnp.exp(-margin)
        return 1.0 - ye, jnp.clip(ye, 1e-10, None)
    if distribution == "tweedie":
        pw = 1.5                      # variance power (fixed, like H2O's
        a = y * jnp.exp((1.0 - pw) * margin)      # default 1.5)
        b = jnp.exp((2.0 - pw) * margin)
        return b - a, jnp.clip((2.0 - pw) * b - (1.0 - pw) * a,
                               1e-10, None)
    if distribution == "laplace":
        return jnp.sign(margin - y), jnp.ones_like(margin)
    raise ValueError(distribution)


class BoostParams(NamedTuple):
    """Static config of the fused boosting loop (hashable for jit)."""

    distribution: str = "gaussian"
    learn_rate: float = 0.1
    sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    drf_mode: bool = False
    quantile_alpha: float = 0.5     # quantile distribution's τ
    huber_alpha: float = 0.9        # huber δ = this quantile of |resid|
    # GOSS (gradient-based one-side sampling, arXiv:1809.04559):
    # goss_b > 0 activates it — keep the top-`goss_a` fraction of rows
    # by |gradient| plus a seeded `goss_b` fraction of the rest,
    # amplified by (1-a)/b so split gains stay unbiased. 0.0 = off
    # (the H2O_TPU_GOSS kill-switch path traces byte-identically to a
    # build without the feature). models/gbm.goss_params is the ONE
    # env reader.
    goss_a: float = 0.0
    goss_b: float = 0.0
    # H2O-3 GBM's multinomial steps (models/gbm.py
    # `_MULTINOMIAL_STEP_CAP`; a multinomial GBM with set features): the
    # softmax keeps every 1 - p (`_softmax_grad_hess`), every node takes
    # its step and every split its gain from a pass over the rows that
    # reach it, and a split stands only where that gain passes
    # min_split_improvement (`_node_sums`); a step, after the learning
    # rate, is capped at this either way. None: the histograms' steps
    # and splits, uncapped (every other job)
    leaf_cap: float | None = None


def _boost_grad_hess(bp: BoostParams, margin, y, w):
    """Per-round (g, h) including the distributions whose gradients
    need BoostParams state (quantile's τ, huber's per-round δ); plain
    families delegate to _grad_hess.

    huber re-derives δ every round as the huber_alpha quantile of the
    CURRENT absolute residuals (hex/tree/gbm GBM.java recomputes δ per
    scoring pass [U3]); under shard_map the quantile is computed per
    shard and pmean'd over ROWS — a distributed approximation of the
    global order statistic (exact would need an all-gather sort).
    """
    if bp.distribution == "quantile":
        a = bp.quantile_alpha
        g = jnp.where(margin < y, -a, 1.0 - a)
        return g, jnp.ones_like(y)
    if bp.distribution == "huber":
        r = y - margin
        absr = jnp.where(w > 0, jnp.abs(r), jnp.nan)
        delta = lax.pmean(jnp.nanquantile(absr, bp.huber_alpha), ROWS)
        g = jnp.where(jnp.abs(r) <= delta, -r, -delta * jnp.sign(r))
        return g, jnp.ones_like(y)
    return _grad_hess(bp.distribution, margin, y)


def _round_grad_hess(bp: BoostParams, margin, y, w, groups, K: int):
    """(g, h) of one boosting round: [rows] for K = 1, [K, rows] for
    K > 1 — THE one place that says where a round's gradients come
    from. A forest's are -y (the class indicators for K classes) with
    h = 1, whatever the margin; a ranking objective's hang on a row's
    query (`rank_grad_hess`); K classes take the softmax of the
    [rows, K] margin; a pointwise objective `_boost_grad_hess`."""
    if K > 1:
        # NaN responses (w=0 pad rows) compare False for every class
        yk = (y[:, None] == jnp.arange(K, dtype=y.dtype)[None, :]
              ).astype(jnp.float32)                      # [rows, K]
        if bp.drf_mode:
            g = -yk.T
            return g, jnp.ones_like(g)
        if bp.leaf_cap is not None:
            return _softmax_grad_hess(margin, yk)
        probs = jax.nn.softmax(margin, axis=1)
        return (probs - yk).T, (probs * (1.0 - probs)).T
    if bp.drf_mode:
        return -y, jnp.ones_like(y)
    if groups is not None:
        return rank_grad_hess(bp.distribution, margin, groups)
    return _boost_grad_hess(bp, margin, y, w)


def _softmax_grad_hess(margin, yk):
    """Softmax (g, h), [K, rows], with every 1 - p kept to float32's
    relative precision: a row's likeliest class takes it as the other
    classes' share, summed apart, where ``1 - p`` would round it to a
    multiple of 2^-24 (a row the margin puts at p = 1 - 1e-9 for a
    class would read h = 0 there, or 6e-8)."""
    top = jnp.argmax(margin, axis=1)[:, None] == \
        jnp.arange(margin.shape[1])[None, :]                # [rows, K]
    e = jnp.exp(margin - jnp.max(margin, axis=1, keepdims=True))
    s = jnp.sum(e, axis=1, keepdims=True)
    rest = jnp.sum(jnp.where(top, 0.0, e), axis=1, keepdims=True)
    p = e / s
    q = jnp.where(top, rest, s - e) / s                     # 1 - p
    return jnp.where(yk > 0, -q, p).T, (p * q).T


def _round_sampling(bp: BoostParams, w, F: int, k_row, k_col):
    """Shard-level row/column sampling for one boosting round →
    (w_t, col_mask): THE one sampling scheme, for every job the boost
    scan (``_boost_shard``) serves."""
    w_t = w
    if bp.sample_rate < 1.0:
        w_t = w * row_keep(k_row, lax.axis_index(ROWS), w.shape[0],
                           bp.sample_rate)
    return w_t, tree_col_mask(k_col, F, bp.col_sample_rate_per_tree)


def row_keep(k_row, shard, rows: int, sample_rate: float):
    """[rows] bool: the rows of shard ``shard`` that one tree keeps
    (its bag). The shard index is folded in: every shard holds
    different rows and draws a keep-pattern of its own."""
    return jax.random.uniform(jax.random.fold_in(k_row, shard),
                              (rows,)) < sample_rate


def tree_col_mask(k_col, F: int, rate: float):
    """[F] bool: the tree's column sample — the same key on every
    shard, so the mask is replicated."""
    if rate < 1.0:
        return jax.random.uniform(k_col, (F,)) < rate
    return jnp.ones(F, dtype=bool)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def tree_bag(k_row, shards: int, rows_per_shard: int,
             sample_rate: float):
    """[shards * rows_per_shard] bool: the rows a tree kept, in the
    padded frame's row order — the draw `_round_sampling` makes on
    every shard, made again from the tree's row key."""
    if sample_rate >= 1.0:
        return jnp.ones(shards * rows_per_shard, dtype=bool)
    return jax.vmap(lambda s: row_keep(k_row, s, rows_per_shard,
                                       sample_rate))(
        jnp.arange(shards)).reshape(-1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def tree_candidates(k_col, k_tree, F: int, max_depth: int, mtries: int,
                    col_rate: float):
    """[2^(max_depth+1)-1, F] bool: the features each heap node of a
    tree was offered (the deepest level's nodes are leaves and were
    offered none), from the tree's column and node keys."""
    col_mask = tree_col_mask(k_col, F, col_rate)
    return jnp.concatenate(
        [level_candidates(k_tree, d, col_mask, mtries)
         for d in range(max_depth)]
        + [jnp.zeros((2 ** max_depth, F), dtype=bool)])


# ---------------------------------------------------------------------------
# GOSS — gradient-based one-side sampling (arXiv:1809.04559)
# ---------------------------------------------------------------------------
#
# Per boosting round, keep the top-`a` fraction of rows by |gradient|
# outright plus a seeded random draw of the rest, and amplify every
# sampled small-gradient row's (g·w, h·w, w) histogram contribution by
# (1-a)/b so split gains stay unbiased. Everything below is STATIC
# SHAPE: the selected rows are compacted per shard into a fixed-
# capacity buffer (goss_cap_rows) and only THAT buffer streams through
# the per-level histogram kernels — the 3-5× row reduction is real
# compute, not just masking; unfilled slots carry w=0 and contribute
# nothing (the same dead-row discipline as the rel == -1 mask).
#
# Layout invariance (the in-HBM mesh layout and the ooc chunk grid
# must select the SAME rows at the same seed, or the two paths would
# train different models): every per-row decision is a pure function
# of (a) GLOBAL ranking stats that are exactly associative — the max
# of |g| and an int32 count histogram of |g| bins, both order-
# independent under psum / cross-chunk adds — and (b) a per-row
# threefry hash of (round key, GLOBAL row id). No sort, no per-shard
# quantile, no draw whose value depends on how rows are sharded.
#
# Tie handling: the top set is "bins strictly above the threshold bin
# T" plus a per-row hash draw with probability frac_T inside bin T, so
# the kept-outright fraction hits `a` in expectation even when |g| is
# massively tied (round-1 bernoulli has exactly two |g| values). Rows
# that lose the bin-T draw fall through to the random-`b` rule, so
# every row's expected weight is exactly its true weight:
#   bin > T:   1
#   bin == T:  frac_T·1 + (1-frac_T)·q·amp = frac_T + (1-frac_T) = 1
#   bin < T:   q·amp = 1          (q = b/(1-a), amp = (1-a)/b = 1/q)

_GOSS_BINS = 2048       # |g|-ranking histogram resolution
_GOSS_SLACK = 1.25      # compaction capacity over the expected a+b rows
_GOSS_KEY_TAG = 0x9055  # fold_in tag of the path-invariant key stream


def goss_round_keys(key, n_trees: int):
    """Per-round GOSS key stream, derived from the estimator seed key
    OUTSIDE the per-dispatch key schedule — the fused in-HBM chunks
    and the ooc stream index it by global tree number, so both paths
    draw identical per-row keep patterns at the same seed."""
    return jax.random.split(jax.random.fold_in(key, _GOSS_KEY_TAG),
                            n_trees)


def goss_cap_rows(rows: int, a: float, b: float) -> int:
    """Static per-shard capacity of the compacted row buffer: the
    expected selected fraction is exactly a+b (see the tie-handling
    note above), so 1.25× slack + a 64-row floor absorbs the binomial
    fluctuation at any realistic shard size. Overflow (possible only
    far past the slack) drops the latest selected rows of the segment
    — a documented approximation, never an error."""
    cap = int(rows * (a + b) * _GOSS_SLACK) + 64
    cap = -(-cap // 8) * 8
    return min(rows, cap)


def goss_rank_stat(g, w):
    """Per-row |gradient| ranking stat masked to live (w>0) rows;
    multi-output [K, rows] gradients rank by the class L1 norm."""
    absg = jnp.abs(g) if g.ndim == 1 else jnp.sum(jnp.abs(g), axis=0)
    return jnp.where(w > 0, absg, 0.0)


def _goss_bin_ids(absg, m):
    scale = _GOSS_BINS / jnp.maximum(m, 1e-30)
    return jnp.clip((absg * scale).astype(jnp.int32), 0, _GOSS_BINS - 1)


def goss_local_counts(absg, live, m):
    """(int32 [GOSS_BINS] counts, int32 live count) for this segment —
    integer sums are exactly associative, so psum over shards and adds
    over ooc chunks give the SAME global histogram in any order."""
    bins = _goss_bin_ids(absg, m)
    counts = jnp.zeros(_GOSS_BINS, jnp.int32).at[bins].add(
        live.astype(jnp.int32))
    return counts, jnp.sum(live.astype(jnp.int32))


def goss_threshold(counts, total, a: float):
    """(T, frac_T) from the GLOBAL count histogram: rows in bins > T
    are kept outright; a row in bin T is kept outright when its hash
    draw lands under frac_T — together the top-`a` fraction in
    expectation, whatever the tie structure."""
    suffix = jnp.cumsum(counts[::-1])[::-1].astype(jnp.float32)
    k_top = jnp.float32(a) * total.astype(jnp.float32)
    T = jnp.sum((suffix >= k_top).astype(jnp.int32)) - 1
    T = jnp.clip(T, 0, _GOSS_BINS - 1)
    cnt_T = counts[T].astype(jnp.float32)
    above = suffix[T] - cnt_T                  # count(bin > T)
    frac = jnp.clip((k_top - above) / jnp.maximum(cnt_T, 1.0), 0.0, 1.0)
    return T, frac


def goss_row_factor(absg, live, m, T, frac_T, kg, row_ids,
                    a: float, b: float):
    """f32 per-row GOSS weight factor in {0, 1, (1-a)/b}. The two
    uniforms per row come from a threefry hash of (kg, global row id)
    — layout-invariant by construction."""
    q = b / (1.0 - a)              # rest-row keep probability
    amp = (1.0 - a) / b            # rest-row amplification = 1/q
    keys = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(kg, row_ids)
    u = jax.vmap(lambda k: jax.random.uniform(k, (2,)))(keys)
    bins = _goss_bin_ids(absg, m)
    top = (bins > T) | ((bins == T) & (u[:, 0] < frac_T))
    factor = jnp.where(top, jnp.float32(1.0),
                       jnp.where(u[:, 1] < q, jnp.float32(amp),
                                 jnp.float32(0.0)))
    return jnp.where(live, factor, 0.0)


def goss_amplified_w(g, w, kg, bp: BoostParams):
    """Runs UNDER shard_map (the in-HBM fused path): global ranking
    stats via pmax/psum over ROWS, then the per-row amplified weight
    w·factor for this shard's rows."""
    a, b = bp.goss_a, bp.goss_b
    absg = goss_rank_stat(g, w)
    live = w > 0
    m = lax.pmax(jnp.max(absg), ROWS)
    counts, nlive = goss_local_counts(absg, live, m)
    counts = lax.psum(counts, ROWS)
    total = lax.psum(nlive, ROWS)
    T, frac = goss_threshold(counts, total, a)
    rows_local = w.shape[0]
    row_ids = lax.axis_index(ROWS) * rows_local + \
        jnp.arange(rows_local, dtype=jnp.int32)
    return w * goss_row_factor(absg, live, m, T, frac, kg, row_ids,
                               a, b)


def goss_compact(binned, g, h, w_amp, cap: int):
    """Per-shard static-capacity compaction of the selected
    (w_amp > 0) rows, in ascending row order. Unfilled slots gather
    row 0 with w=0 — zero histogram contribution, exactly the dead-row
    semantics of the rel == -1 mask. g may be [rows] or [K, rows].

    Returns (binned, g, h, w, dropped): ``dropped`` is this segment's
    overflow count max(nsel - cap, 0) — the cap is sized for the
    EXPECTED a+b fraction, but the top-a set follows the data layout,
    so a frame whose row ORDER correlates with |gradient| (sorted by
    target/residual) can cluster far more than (a+b)·rows into one
    shard. The count is psum'd/summed by the callers and surfaced as
    a loud warning (models/gbm) — a silent drop of exactly the
    highest-gradient rows must never be silent."""
    sel = w_amp > 0
    idx = jnp.nonzero(sel, size=cap, fill_value=0)[0].astype(jnp.int32)
    nsel = jnp.sum(sel.astype(jnp.int32))
    valid = jnp.arange(cap, dtype=jnp.int32) < nsel
    wC = jnp.where(valid, w_amp[idx], 0.0)
    if g.ndim == 1:
        gC, hC = g[idx], h[idx]
    else:
        gC, hC = g[:, idx], h[:, idx]
    dropped = jnp.maximum(nsel - cap, 0)
    return binned[idx], gC, hC, wC, dropped


def round_keys(key, n_rounds: int):
    """[n_rounds] keys of one dispatch's boosting rounds. Everything a
    round draws — its row sample, its column sample, its nodes'
    candidate features — is a pure function of its key, so a model
    that keeps the keys can say what each tree saw (`tree_bag`,
    `tree_candidates`)."""
    return jax.random.split(key, n_rounds)


# live histogram bytes allowed for the vmapped K-class grow (per shard,
# deepest level) before `_boost_shard` drops to sequential lax.map
_MULTI_HIST_BUDGET = 2 ** 30


def level_hist_bytes(p: TreeParams, F: int) -> int:
    """Peak live histogram bytes for ONE tree's deepest level: the ×5
    covers hist_prev, hist_l, hist_r (2^(d-1) nodes each) and the
    stacked level (2^d nodes) live at once. THE single accounting used
    by the up-front budget validation (models/gbm.py), the multinomial
    vmap-vs-lax.map branch — one formula so the validator and the
    branch decision cannot drift."""
    C = 2 if p.unit_hess else 3
    return 5 * (2 ** max(p.max_depth - 1, 0)) * F * p.n_bins * C * 4


def multi_grow_vmapped(p: TreeParams, F: int, K: int) -> bool:
    """True when the K-class grow vmaps (K× histograms live); False
    when it falls to lax.map with one class's histograms live."""
    return K * level_hist_bytes(p, F) <= _MULTI_HIST_BUDGET


def _boost_shard(binned, y, w, margin, keys, efb=None, groups=None, *,
                 p: TreeParams, bp: BoostParams, K: int):
    """Scan over boosting rounds INSIDE one shard_map: sample →
    grad/hess → (GOSS) → grow → local margin update, with histograms
    psum'd per level. One tree a round for K = 1, K class trees a round
    for K > 1 (the margin is then [rows, K]). ``groups``: the query
    layout of a grouped objective (tree/rank.py), None for a pointwise
    one.

    This replaces the reference's per-tree driver round trips
    (SharedTree.Driver.computeImpl's outer loop, SURVEY.md §3.4) with a
    single compiled program — the margin never leaves the device and
    the host dispatches once per chunk of trees instead of ≥3 times per
    tree. The K class trees of a round grow from shared softmax probs,
    one row sample and one GOSS draw a round, as hex/tree/gbm/GBM.java
    grows an iteration's.

    A forest (``bp.drf_mode``) grows here too, a tree (K for K
    classes) a scan step, each on the bag and candidate features its own
    key draws; its gradients never read the carry. Its ``learn_rate``
    is 1, so the carry is the sum of its trees' leaf values for EVERY
    row (a row out of the bag has weight 0 and descends with the rest):
    the forest's train metric is read off it. Several forest trees a
    step under vmap never won on a v5e (PERF.md §6).
    """
    F = efb.feat_col.shape[0] if efb is not None else binned.shape[1]
    goss = bp.goss_b > 0.0

    def body(margin, kt):
        if goss:
            kt, kg = kt
        k_row, k_col, k_tree = jax.random.split(kt, 3)
        with jax.named_scope("sample"):
            w_t, col_mask = _round_sampling(bp, w, F, k_row, k_col)
        with jax.named_scope("grad_hess"):
            g, h = _round_grad_hess(bp, margin, y, w, groups, K)
        bC, gC, hC, wC = binned, g, h, w_t
        if goss:
            # GOSS: amplified weights → static-cap compaction → the
            # grower streams only the sampled rows (one draw a round,
            # ranked by the class-L1 gradient norm for K > 1). The
            # margin update re-descends the FULL binned matrix through
            # the grown trees (the grower's leaf walk only covers
            # sampled rows).
            with jax.named_scope("sample"):
                w_amp = goss_amplified_w(g, w_t, kg, bp)
                cap = goss_cap_rows(binned.shape[0], bp.goss_a,
                                    bp.goss_b)
                bC, gC, hC, wC, dropped = goss_compact(binned, g, h,
                                                       w_amp, cap)
        if K == 1:
            tree, leaf = _grow_tree_shard(bC, gC, hC, wC, col_mask,
                                          k_tree, p, efb)
        else:
            # vmap multiplies per-level histogram memory by K; past a
            # budget grow classes sequentially INSIDE the dispatch
            # (lax.map: 1/K the live histogram footprint, still one
            # compile). The decision uses the HISTOGRAM width (the
            # bundled width under EFB), matching gbm.py's validator
            batched = multi_grow_vmapped(p, binned.shape[1], K)

            def grow_one(gk, hk, kk):
                return _grow_tree_shard(bC, gk, hk, wC, col_mask, kk, p,
                                        efb, batched=batched)

            keys_k = jax.random.split(k_tree, K)
            if batched:
                tree, leaf = jax.vmap(grow_one)(gC, hC, keys_k)
            else:
                tree, leaf = lax.map(lambda a: grow_one(*a),
                                     (gC, hC, keys_k))
        if bp.leaf_cap is not None:
            with jax.named_scope("node_sums"):
                tree, rest = _node_sums(tree, leaf, gC, hC, wC, p)
        with jax.named_scope("margin"):
            # the grower already walked each row to its leaf: one
            # lookup replaces a full heap re-descent per tree. The rate
            # scales the rows' values after the lookup, and K classes'
            # [rows, K] margin meets their [K, rows] values in the
            # values' layout (a no-op for one tree): XLA:CPU folds the
            # multiply into the add's loop (a multiply-add), and only so
            # does it fold it alike after a gather and after a select.
            # A capped step (`bp.leaf_cap`) is stepped and capped on the
            # tree's values first, then looked up at the node a row's
            # leaf now rests at (`_node_sums`' ``rest``)
            if bp.leaf_cap is not None:
                tree = tree._replace(value=jnp.clip(
                    bp.learn_rate * tree.value, -bp.leaf_cap,
                    bp.leaf_cap))
                upd = _node_lookup(jnp.take_along_axis(
                    tree.value, rest, axis=-1), leaf)
                margin = (margin.T + upd).T
            elif goss:
                def upd_of(tr):
                    return _node_lookup(tr.value, descend_tree(
                        tr, binned, p.max_depth, p.n_bins, efb))
                upd = upd_of(tree) if K == 1 else jax.vmap(upd_of)(tree)
            else:
                upd = _node_lookup(tree.value, leaf)  # [(K,) rows]
            if bp.leaf_cap is None:
                margin = (margin.T + bp.learn_rate * upd).T
                tree = tree._replace(value=bp.learn_rate * tree.value)
        if goss:
            return margin, (tree, lax.psum(dropped, ROWS))
        return margin, tree

    if goss:
        margin, (trees, dropped) = lax.scan(body, margin, keys)
        return margin, trees, jnp.sum(dropped)
    margin, trees = lax.scan(body, margin, keys)
    return margin, trees


def _node_sums(tree: Tree, leaf, g, h, w, p: TreeParams):
    """(tree, rest): the tree with every node's value, and every
    split's gain, taken from the sums of g·w, h·w and rows of the rows
    that REACH it, found in one pass over the rows after the tree is
    grown (H2O-3's GammaPass, which finds a leaf's step so, carried to
    every node) where the grower took them from its level histograms.
    There a bin sums up to millions of rows in float32, and a right
    child's sums are its parent's less its sibling's: a child of a
    hundred rows under a parent of a million keeps little of its Σg and
    Σh, and with no lambda its Newton step, and the gain of the split
    above it, are mostly that rounding — enough to pass
    ``min_split_improvement`` where the true gain is 0. So a split
    stands only where its gain from the rows passes it; below a split
    that does not, the node is a leaf, its subtree goes, and ``rest``
    [(K,) N] says where each node's rows now rest (the node itself, or
    its deepest ancestor left in the tree). ``leaf`` is a row's heap
    node, [(K,) rows]; a node of the tree no row reaches keeps its
    value."""
    N = tree.value.shape[-1]
    n_levels = N.bit_length()
    live = (w > 0).astype(g.dtype)
    sums = lax.psum(_resting_sums(leaf, (g * w, h * w, live), N), ROWS)
    levels = [sums[..., 2 ** d - 1: 2 ** (d + 1) - 1, :]
              for d in range(n_levels)]
    for d in range(n_levels - 2, -1, -1):                  # [(K,) 2^d, 3]
        below = levels[d + 1]
        levels[d] = levels[d] + below.reshape(
            below.shape[:-2] + (2 ** d, 2, 3)).sum(axis=-2)
    G, H, C = jnp.moveaxis(jnp.concatenate(levels, axis=-2), -1, 0)
    inner = 2 ** (n_levels - 1) - 1
    kids = jnp.concatenate(levels[1:], axis=-2).reshape(
        sums.shape[:-2] + (inner, 2, 3))            # node 2i+1, 2i+2
    gain = _kid_gain(kids[..., 0, 0], kids[..., 0, 1], kids[..., 1, 0],
                     kids[..., 1, 1], p)
    stands = tree.is_split[..., :inner] & (gain > p.gamma)
    # top down: a node is in the tree where every split above it stands
    ins, rests = [jnp.ones_like(tree.is_split[..., :1])], \
        [jnp.zeros_like(leaf[..., :1])]
    for d in range(1, n_levels):
        up = stands[..., 2 ** (d - 1) - 1: 2 ** d - 1]
        ins.append(jnp.repeat(ins[-1] & up, 2, axis=-1))
        rests.append(jnp.where(
            ins[-1], jnp.arange(2 ** d - 1, 2 ** (d + 1) - 1,
                                dtype=leaf.dtype),
            jnp.repeat(rests[-1], 2, axis=-1)))
    inside = jnp.concatenate(ins, axis=-1)
    is_split = jnp.concatenate(
        [stands, jnp.zeros_like(tree.is_split[..., inner:])],
        axis=-1) & inside
    value = jnp.where(C > 0, _leaf_value(G, H, p), tree.value)
    return tree._replace(
        split_feat=jnp.where(is_split, tree.split_feat, -1),
        is_split=is_split,
        value=jnp.where(inside, value, 0.0),
        gain=jnp.where(is_split, jnp.concatenate(
            [gain, jnp.zeros_like(tree.gain[..., inner:])], axis=-1),
            0.0),
        cover=jnp.where(inside, tree.cover, 0.0)), \
        jnp.concatenate(rests, axis=-1)


def _resting_sums(leaf, vals, N: int):
    """[(K,) N, len(vals)]: each of ``vals`` ([(K,) rows], or [rows]
    alike for every tree) summed over the rows that rest at each of N
    nodes (``leaf`` [(K,) rows]). Under `node_lookup_form`'s rule, as a
    node table is read: where it would be a select, one fused
    compare-and-sum over [(K,) N, rows]; past it, a segment sum."""
    if node_lookup_form(N) == "select":
        hit = jnp.arange(N, dtype=leaf.dtype)[:, None] == \
            leaf[..., None, :]                            # [(K,) N, rows]
        return jnp.stack([jnp.sum(jnp.where(hit, v[..., None, :], 0.0),
                                  axis=-1) for v in vals], axis=-1)
    lead = leaf.shape[:-1]
    ids = (jnp.arange(math.prod(lead), dtype=leaf.dtype).reshape(
        lead + (1,)) * N + leaf).reshape(-1)
    return jnp.stack([jax.ops.segment_sum(
        jnp.broadcast_to(v, leaf.shape).reshape(-1), ids,
        math.prod(lead) * N).reshape(lead + (N,)) for v in vals], axis=-1)


def _kid_gain(Gl, Hl, Gr, Hr, p: TreeParams):
    """`_split_gains`' gain of a split from its children's sums, the
    parent's being theirs added up. Without an L1 term it is taken as
    (A B (a - b)^2 - lambda (a Gl + b Gr)) / (A + B - lambda), A and B
    the children's H + lambda and a, b their Gl / A and Gr / B — the
    same number, but a gain small beside the parent's G^2 / H (children
    of near steps) keeps its digits, where the three terms' difference
    keeps float32's rounding of the largest."""
    if p.reg_alpha:
        return (_gain_term(Gl, Hl, p) + _gain_term(Gr, Hr, p)
                - _gain_term(Gl + Gr, Hl + Hr, p))
    lam = p.reg_lambda + 1e-10
    A, B = Hl + lam, Hr + lam
    a, b = Gl / A, Gr / B
    return (A * B * (a - b) ** 2 - lam * (a * Gl + b * Gr)) / (A + B - lam)


def _boost_program(binned, y, w, margin, keys, efb, p, bp, K, mesh,
                   groups):
    """`_boost_shard` under one shard_map: rows sharded, keys and LUTs
    replicated, a query layout as `groups_specs` lays it; out the
    margin by rows and the trees (and the GOSS overflow) replicated."""
    out_specs = (P(ROWS), P(), P()) if bp.goss_b > 0 \
        else (P(ROWS), P())
    in_specs = (P(ROWS), P(ROWS), P(ROWS), P(ROWS), P(), P())
    args = (binned, y, w, margin, keys, efb)
    if groups is not None:
        in_specs += (groups_specs(groups),)
        args += (groups,)
    fn = jax.shard_map(
        functools.partial(_boost_shard, p=p, bp=bp, K=K),
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        # pallas_call's interpret mode can't thread vma through its
        # internal slices (jax 0.9 limitation) — disable the check there
        check_vma=_resolve_impl(p.hist_impl) == "segment")
    return fn(*args)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _boost_jit(binned, y, w, margin, keys, efb, p: TreeParams,
               bp: BoostParams, K: int, mesh, groups=None):
    """Fused boosting, one tree a round (boosted, ranked or a forest):
    len(keys) rounds in ONE dispatch → (margin, trees [T, N]). With
    GOSS (``bp.goss_b > 0``) ``keys`` is the pair (round keys, rows of
    the path-invariant `goss_round_keys` stream) and a third output
    counts the rows compaction dropped (`goss_compact`). A grouped
    objective's query layout is the last operand (``groups``:
    `rank.RankGroups`), its classes replicated and its rows' slots
    sharded as the rows are. models/gbm.BoostPlan builds the operands."""
    assert K == 1, "K class trees a round run in _boost_multi_jit"
    return _boost_program(binned, y, w, margin, keys, efb, p, bp, K,
                          mesh, groups)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _boost_multi_jit(binned, y, w, margin, keys, efb, p: TreeParams,
                     bp: BoostParams, K: int, mesh, groups=None):
    """`_boost_jit` with K > 1 class trees a round → (margin [rows, K],
    trees [T, K, N]), plus the GOSS overflow scalar when sampling is
    active."""
    assert K > 1, "one tree a round runs in _boost_jit"
    return _boost_program(binned, y, w, margin, keys, efb, p, bp, K,
                          mesh, groups)


def descend_tree(tree: Tree, binned, max_depth: int, n_bins: int,
                 efb=None):
    """Per-row resting heap node by iterative descent (jittable) — the
    ONE implementation of split semantics at scoring time (NA bin
    routing via na_left, `bin > split_bin` goes right; a tree with
    set splits by its `left_bins`: `_goes_right`). With ``efb``
    the binned matrix is in BUNDLED column space and per-row bins
    decode through the shared row_orig_bins (a select over the
    columns, then the bundle's LUTs)."""
    node = jnp.zeros(binned.shape[0], dtype=jnp.int32)
    for _ in range(max_depth):
        f = tree.split_feat[node]
        b = tree.split_bin[node]
        nl = tree.na_left[node]
        sp = tree.is_split[node]
        rowbin = row_orig_bins(binned, jnp.maximum(f, 0), efb)
        go_right = _goes_right(rowbin, b, nl, n_bins, tree.left_bins,
                               node)
        child = 2 * node + 1 + go_right.astype(jnp.int32)
        node = jnp.where(sp, child, node)
    return node


def predict_tree(tree: Tree, binned, max_depth: int, n_bins: int,
                 efb=None):
    """Per-row leaf value (descend + gather)."""
    return tree.value[descend_tree(tree, binned, max_depth, n_bins,
                                   efb)]


# ---------------------------------------------------------------------------
# Compiled serving fast path: flattened ensemble scorer
# ---------------------------------------------------------------------------
#
# The MOJO idea (h2o-genmodel SharedTreeMojoModel [U3]): scoring needs
# none of the training structures.  flatten_trees packs the dense heap
# into compact per-tree node arrays — only REACHABLE nodes, explicit
# left-child slots — and converts every split's bin id into a RAW
# FEATURE threshold, so serving never re-bins: with right-searchsorted
# binning, `bin(x) <= b  <=>  x < edges[b]`, hence descending right on
# `x >= thresh` reproduces the heap descent decision bitwise.  These
# arrays are the single flattening shared by the in-process scorer
# (flat_margin) and the MOJO artifact (mojo.py serializes them).

class FlatTrees(NamedTuple):
    """Compact serving ensemble: [T, M] node arrays, M = max reachable
    nodes per tree (BFS slot order, root = slot 0, right = left + 1)."""

    split_feat: jax.Array   # int32 [T, M]; -1 marks a leaf
    thresh: jax.Array       # f32   [T, M]; go RIGHT iff x >= thresh
    left: jax.Array         # int32 [T, M]; left-child slot
    na_left: jax.Array      # bool  [T, M]; NaN feature goes left
    value: jax.Array        # f32   [T, M]; leaf value (0 on splits)


def _reach_slots(isp: np.ndarray, max_depth: int
                 ) -> tuple[np.ndarray, np.ndarray, int]:
    """(reach [T, N] bool, slot [T, N] int, M) — the reachable-node set
    and its BFS slot assignment, shared by ``flatten_trees`` and
    ``flatten_cover`` so every per-node companion array (cover, for the
    TreeSHAP path tables) lands on exactly the slots the serving
    descent reads."""
    T, N = isp.shape
    reach = np.zeros((T, N), dtype=bool)
    reach[:, 0] = True
    for d in range(max_depth):
        lo, hi = 2 ** d - 1, 2 ** (d + 1) - 1
        if hi > N:
            break
        par = reach[:, lo:hi] & isp[:, lo:hi]
        idx = np.arange(lo, hi)
        reach[:, 2 * idx + 1] |= par
        reach[:, 2 * idx + 2] |= par
    # BFS slot order == heap-index order among reachable nodes (FIFO
    # BFS emits each level in parent order, i.e. ascending heap index)
    slot = reach.cumsum(axis=1) - 1                       # [T, N]
    M = int(reach.sum(axis=1).max())
    return reach, slot, M


def flatten_cover(trees: Tree, max_depth: int) -> np.ndarray:
    """[T, M] per-FLAT-NODE training weight mass (TreeSHAP's r_j),
    slot-aligned with ``flatten_trees``' arrays — the optional MOJO-v2
    ``flat_cover`` part and the input to the per-leaf path tables
    (models/tree/shap.py::build_shap_tables)."""
    isp = np.asarray(trees.is_split).astype(bool)
    cov = np.asarray(trees.cover).astype(np.float32)
    reach, slot, M = _reach_slots(isp, max_depth)
    out = np.zeros((isp.shape[0], M), dtype=np.float32)
    tt, hh = np.nonzero(reach)
    out[tt, slot[tt, hh]] = cov[tt, hh]
    return out


def flatten_trees(trees: Tree, edges_matrix: np.ndarray,
                  enum_mask: np.ndarray, max_depth: int) -> FlatTrees:
    """Host-side flattening of a stacked [T, N] heap Tree pytree.

    Threshold semantics (bitwise-equal to the binned heap descent,
    models/tree/binning.py `apply_bins`):
      numeric feature, split_bin b < n_edges: thresh = edges[f, b]
        (searchsorted(e, x, "right") > b  <=>  x >= e[b], +inf pads
        included — a padded edge sends every finite x left both ways);
      numeric, b == n_edges (cut past the last body bin): thresh = NaN
        — `x >= NaN` is False, so every non-NA row goes left, exactly
        like `bin <= b` when b is the max body bin;
      categorical (code IS the bin): thresh = b + 1, since
        `clip(code) > b  <=>  code >= b + 1` for integer codes.
    NA routing stays explicit via na_left (callers canonicalize
    negative enum codes to NaN before descending — apply_bins sends
    those to the NA bin)."""
    require_ordinal(trees, "The flat scorer (flatten_trees / flat_margin)")
    sf = np.asarray(trees.split_feat)
    sb = np.asarray(trees.split_bin)
    nl = np.asarray(trees.na_left).astype(bool)
    isp = np.asarray(trees.is_split).astype(bool)
    val = np.asarray(trees.value).astype(np.float32)
    edges_matrix = np.asarray(edges_matrix)
    enum_mask = np.asarray(enum_mask).astype(bool)
    T, N = sf.shape
    # reachable set + BFS slots (shared with flatten_cover)
    reach, slot, M = _reach_slots(isp, max_depth)
    out_feat = np.full((T, M), -1, dtype=np.int32)
    out_thresh = np.zeros((T, M), dtype=np.float32)
    out_left = np.zeros((T, M), dtype=np.int32)
    out_nal = np.zeros((T, M), dtype=bool)
    out_val = np.zeros((T, M), dtype=np.float32)
    tt, hh = np.nonzero(reach)
    ss = slot[tt, hh]
    sm = isp[tt, hh]                                      # split mask
    f = np.where(sm, sf[tt, hh], 0)
    b = sb[tt, hh]
    width = edges_matrix.shape[1]
    b_safe = np.minimum(b, width - 1)
    with np.errstate(invalid="ignore"):
        th = np.where(
            enum_mask[f], (b + 1).astype(np.float32),
            np.where(b < width, edges_matrix[f, b_safe].astype(np.float32),
                     np.float32(np.nan)))
    lh = np.minimum(2 * hh + 1, N - 1)                    # guarded gather
    out_feat[tt, ss] = np.where(sm, sf[tt, hh], -1)
    out_thresh[tt, ss] = np.where(sm, th, 0.0)
    out_left[tt, ss] = np.where(sm, slot[tt, lh], 0)
    out_nal[tt, ss] = nl[tt, hh] & sm
    out_val[tt, ss] = np.where(sm, 0.0, val[tt, hh])
    return FlatTrees(out_feat, out_thresh, out_left, out_nal, out_val)


@functools.partial(jax.jit, static_argnums=(3, 4))
def flat_margin(flat: FlatTrees, X, enum_mask, levels: int, K: int):
    """[K, rows] per-class leaf-value sums over an interleaved [T*K]
    flat ensemble, scored on RAW float features (no binning).

    Accumulation is an ordered scan over boosting rounds — the same
    per-class f32 addition order as the binned `_stack_predict` path,
    so predictions are bitwise-identical, not merely close."""
    # negative enum codes are NA (apply_bins sends them to the NA bin);
    # canonicalize to NaN once so the descent needs only isnan
    Xc = jnp.where(enum_mask[None, :] & (X < 0), jnp.float32(jnp.nan), X)
    TK = flat.split_feat.shape[0]
    per_round = jax.tree.map(
        lambda a: a.reshape((TK // K, K) + a.shape[1:]), flat)

    def descend(sf, th, lf, nl, val):
        node = jnp.zeros(Xc.shape[0], dtype=jnp.int32)
        for _ in range(levels):
            f = sf[node]
            x = jnp.take_along_axis(
                Xc, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
            go_r = jnp.where(jnp.isnan(x), ~nl[node], x >= th[node])
            node = jnp.where(f >= 0, lf[node] + go_r.astype(jnp.int32),
                             node)
        return val[node]

    def body(acc, tr):
        return acc + jax.vmap(descend)(*tr), None

    init = jnp.zeros((K, Xc.shape[0]), dtype=jnp.float32)
    total, _ = lax.scan(body, init, tuple(per_round))
    return total
