"""Histogram accumulation kernels for the tree learners' hot loop.

This is THE hot op of the framework (SURVEY.md §3.4: the reference's
ScoreBuildHistogram2 row×column binning loop; BASELINE.json names a
Pallas histogram kernel as the TPU answer). Per tree level every live
row contributes (g·w, h·w, w) to histogram cell [node, feature, bin].

Two implementations:

- `segment`: jax.ops.segment_sum per feature — XLA lowers this to
  scatter-add, which is fine on CPU but serializes on TPU.
- `pallas`: scatter-free MXU formulation, ONE kernel for every depth.
  A row's cell seg = node·B + bin is factorized as seg = hi·128 + lo;
  for a row tile the value channels are packed against the hi one-hot
  (A[c·ht + hi, t], ht hi slots a block) and multiplied with the exact
  lo one-hot, so the whole histogram build rides the systolic
  array with full MXU rows and one lane pass (the GPU literature's
  shared-memory atomics have no TPU analog; matmul inflation is the
  right trade — see PAPERS.md GBDT-on-accelerator entries). A level
  with more than `_FACT_MAX_NHI` hi slots is served in blocks of hi
  slots along one grid axis: a row whose slot lies in another block
  matches nothing there, as a dead row does — so a row tile met every
  block, and at 16 blocks 15/16 of the products were zeros. Where the
  grower has ordered a tree's rows by node block
  (`models/tree/core.compact_depth`: a block holds whole nodes, so a
  row's block is an ancestor of its node), the call takes each block's
  first row (``starts``) and runs each block over its own row tiles
  alone (`_hist_compact_kernel`: grid (feature groups, steps), each
  step's block and tile scalar-prefetched), skipping the dead rows
  sorted last — still one call a level, named `hist_blocked`, bitwise
  the blocked call's sums over the same rows.

  BOTH one-hots are built rows-on-lanes — `iota[·, T] == x[None, :]`, a
  sublane broadcast of a lane vector, the layout the bin codes arrive
  in — and the product contracts the row axis of both operands. Until
  PR 35 the lo one-hot was `[T, 128]`, `iota == lo[:, None]`: laying
  each row's `lo` across a sublane row cost 896 lane permutes a
  (column, 4,096-row tile), and THOSE bound every shallow call — 2.4 µs
  a (column, tile) whatever the level computed, against 0.7 µs without
  them, bitwise the same sums (PERF.md section 3 has the op-alone
  table; `tools/hist_forms.py` reruns it, with the older form and a
  node-stationary one that lost beside the shipped kernel). What binds
  a call now: up to 8 hi slots the 256 weight pushes and the compares a
  (column, tile); past that the A operand's build and the MXU, as at
  the deep levels.

  K trees grown at once over one stored frame (the K class trees of a
  boosting round, under `vmap`) go through ONE call a level whose row
  tile carries the K classes' node ids and values
  (`_hist_class_kernel`): a column's codes are read once, each class's
  rows meet that class's hi slots only, and the K classes are packed
  on the sublanes of ONE A operand for one product against the
  column's lo one-hot. That takes a node of whole 128-lane rows
  (lo = bin mod 128 whatever the node); where a node takes part of
  one, the classes go a class at a time through the unbatched call
  (PERF.md section 6, PR 39).

`build_histogram(..., impl="auto")` picks pallas on TPU, segment
elsewhere. Both run under shard_map (per-shard rows); callers psum the
result across the ROWS mesh axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Precision: a plain bf16 multiply loses ~0.4% on the gradient sums, so
# the kernel reproduces f32 products with THREE explicit bf16 mantissa
# terms of the values against the exactly-representable 0/1 one-hot —
# the same arithmetic HIGHEST would emulate, minus the wasted passes on
# the one-hot operand (it is already bf16-exact). Two terms (~2^-16)
# were measured on a v5e and were no faster (PERF.md §6, PR 25), so
# every caller gets the stated float32. `terms` stays a static argument
# of the kernel: a caller-stated contract like `unit_hess` (exact
# small-integer values need one term) would pass it from there.


def _interpret() -> bool:
    """Pallas interpret mode off-chip — the CPU-test path of every
    kernel in ops/. Read at TRACE time; tests/test_chip_compile.py
    patches it to compile the kernels for a described TPU."""
    return jax.default_backend() != "tpu"


def _fact_row_tile(ht: int, rows: int) -> int:
    """Row tile for a hi block of ``ht`` slots. A wider tile shares the
    row-stream operands (rel, vals, the mantissa split) and the grid
    step's sequencing among four times the rows — what a (column, tile)
    costs on top is in the module docstring — but the [3·C·ht, T] A
    operand scales with T: stay at 1024 when the block is large (VMEM
    ~16 MB/core) or the rows wouldn't fill a wide tile anyway."""
    return 4096 if ht <= 64 and rows >= 8192 else 1024


# out-block VMEM budget for the fused-feature kernel: features are
# processed in groups of `fg` per grid step so [fg, C·n_hi, 128] f32
# stays resident; past this budget F is split into 8-aligned groups.
# A wider group shares the row-stream operands among more columns and
# wins nothing else: a column's own work (its two one-hots, its A
# operand, its 256 weight pushes a 4,096-row tile) is what a step costs
_OUT_BUDGET = 3 << 20

def _dimsem(*sems):
    return pltpu.CompilerParams(dimension_semantics=sems)


def _hist_segment(binned, rel, vals, n_nodes: int, n_bins: int):
    """[r,F] bins + [r] rel + [r,C] vals -> [n_nodes, F, B, C]."""
    live = rel >= 0
    seg_node = jnp.where(live, rel, n_nodes)
    C = vals.shape[1]

    def per_feature(bins_f):
        seg = seg_node * n_bins + bins_f.astype(jnp.int32)
        out = jax.ops.segment_sum(
            vals, seg, num_segments=(n_nodes + 1) * n_bins)
        return out[: n_nodes * n_bins].reshape(n_nodes, n_bins, C)

    return jax.vmap(per_feature, in_axes=1, out_axes=1)(binned)


def _mantissa_terms(vals_t, terms: int):
    """Split [n_ch, T] f32 values into `terms` stacked bf16 mantissa
    terms whose products against a 0/1 operand sum back to the f32
    product (to ~2^-8·8·terms relative)."""
    v1 = vals_t.astype(jnp.bfloat16)
    if terms == 1:
        return v1
    r1 = vals_t - v1.astype(jnp.float32)
    v2 = r1.astype(jnp.bfloat16)
    if terms == 2:
        return jnp.concatenate([v1, v2], axis=0)
    v3 = (r1 - v2.astype(jnp.float32)).astype(jnp.bfloat16)
    return jnp.concatenate([v1, v2, v3], axis=0)


# contract the ROW axis of both operands: `lo` stays on the lanes it
# arrives on (`lo[:, None]` against a [T, 128] iota relaid every row's
# value across a sublane row: what bound a shallow call)
_ROW_AXES = (((1,), (1,)), ((), ()))


def _term_products(a, Bt, terms: int):
    """ONE matmul with all mantissa terms stacked into M — the MXU's
    row occupancy multiplies (terms·n_ch·ht rows instead of `terms`
    passes of n_ch·ht); the per-term partial sums `[terms, M/terms,
    128]` recombine with one cheap VPU add. Same bf16 products, same
    f32 accumulation."""
    acc = lax.dot_general(a, Bt, dimension_numbers=_ROW_AXES,
                          preferred_element_type=jnp.float32)
    return acc.reshape(terms, a.shape[0] // terms, 128)


def _hist_fact_kernel(binned_ref, rel_ref, vals_ref, out_ref, *, n_bins,
                      ht, n_ht, n_ch, fg, terms):
    """Factorized one-hot histogram matmul, one block of ``ht`` hi slots.

    seg = rel·B + bin is split as seg = hi·128 + lo.  The LHS packs the
    weighted value channels against the hi one-hot —
    A[c·ht + hi, t] = v_c[t]·1[hi_t = hi] — and the RHS is the exact
    lo one-hot, held transposed: Bt[lo, t] = 1[lo_t = lo], so
    hist[c, seg] = (A @ Btᵀ)[c·ht + hi, lo]: the MXU sees
    [3·C·ht, T]x[T, 128] (full rows for ht ≥ 43, ONE lane pass), where
    a one-hot over the cells themselves would fill C of its 128 rows.
    A is split into three bf16 terms (hi/mid/lo mantissa) so the f32
    products match the segment path to ~2^-24; B is 0/1 and thus exact
    in bf16.
    """
    # grid (feature_groups, hi_blocks, 1, row_blocks): one step
    # covers a whole FEATURE GROUP of fg features for its row block —
    # the row-stream operands (rel, vals, mantissa split) load and
    # compute ONCE per row block instead of once per (feature, row
    # block), and the grid shrinks F×.
    first = (pl.program_id(2) == 0) & (pl.program_id(3) == 0)

    @pl.when(first)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    _accumulate(binned_ref, rel_ref, vals_ref, out_ref,
                lambda: pl.program_id(1), n_bins=n_bins, ht=ht, n_ht=n_ht,
                n_ch=n_ch, fg=fg, terms=terms)


def _hist_compact_kernel(block_ref, tile_ref, binned_ref, rel_ref, vals_ref,
                         out_ref, *, n_bins, ht, n_ht, n_ch, fg, terms):
    """`_hist_fact_kernel` over rows ordered by hi block: grid step s
    serves hi block ``block_ref[s] >> 1`` over row tile ``tile_ref[s]``
    (scalar-prefetched, `_compact_steps`), so a block meets its own row
    tiles and no others. The out block follows the step's hi block and
    is zeroed at that block's first step; a step whose low bit is 0 (a
    block without rows, or padding past the last block) adds nothing.
    The same arithmetic a (row tile, block) as the blocked call: a tile
    it skips holds no row of the block, and would have added zeros."""
    # grid (feature_groups, steps)
    s = pl.program_id(1)
    word = block_ref[s]
    first = (s == 0) | (block_ref[jnp.maximum(s - 1, 0)] >> 1 != word >> 1)

    @pl.when(first)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(word & 1 == 1)
    def _():
        _accumulate(binned_ref, rel_ref, vals_ref, out_ref,
                    lambda: word >> 1, n_bins=n_bins, ht=ht, n_ht=n_ht,
                    n_ch=n_ch, fg=fg, terms=terms)


def _accumulate(binned_ref, rel_ref, vals_ref, out_ref, block, *, n_bins,
                ht, n_ht, n_ch, fg, terms):
    """One row tile's products added into the out block, for the hi
    block ``block()`` (read only where ``n_ht > 1``)."""
    rel = rel_ref[:]                                 # [T]
    rel_base = rel * n_bins
    if n_ht > 1:
        # this step's hi block starts at slot block·ht: shift the
        # cell index so the block's slots read 0..ht-1 below
        rel_base = rel_base - block() * (ht * 128)
    vals_t = vals_ref[:].T                           # [n_ch, T]
    # f32-precision via `terms` bf16 mantissa terms, split on the TINY
    # [n_ch, T] values and masked by the 0/1 one-hot IN bf16 —
    # bit-identical to splitting the big masked A (0/1 masking commutes
    # with rounding) but skips materializing a [n_ch*ht, T] f32 A
    # plus two subtract passes over it: the A-build drops from ~6
    # f32-width VPU passes to `terms` bf16-width multiplies.
    V = _mantissa_terms(vals_t, terms)               # [terms·n_ch, T]
    T = rel.shape[0]
    iota_hi = lax.broadcasted_iota(jnp.int32, (ht, T), 0)
    iota_lo = lax.broadcasted_iota(jnp.int32, (128, T), 0)

    # REAL loop over the feature group, not a static unroll: Mosaic
    # stack-allocates every unrolled iteration's [3·n_ch·ht, T] A
    # operand separately (fg=10 at T=4096 → 22 MB, past the 16 MB
    # scoped-vmem limit — caught by the on-chip gate), while a
    # fori_loop body's buffers are reused across iterations. The
    # feature index is a LEADING dim of the binned/out blocks so the
    # dynamic index never touches the tiled (sublane, lane) pair.
    def _feature(j, carry):
        bins = binned_ref[j, 0, 0, :]                # [T]
        seg = rel_base + bins
        hi = lax.shift_right_arithmetic(seg, 7)      # floor(seg/128)
        lo = seg - hi * 128                          # seg mod 128, >= 0
        # hi one-hot, transposed [ht, T]. Dead rows (rel=-1) have
        # hi < 0 and match no slot, and neither does a row whose slot
        # lies in another hi block (hi < 0 or hi >= ht); dead rows'
        # vals are zeroed upstream.
        oh_hi = (iota_hi == hi[None, :]).astype(jnp.bfloat16)
        Bt = (iota_lo == lo[None, :]).astype(jnp.bfloat16)
        a = jnp.concatenate(
            [oh_hi * V[k][None, :] for k in range(terms * n_ch)],
            axis=0)                             # [terms·n_ch·ht, T]
        acc = _term_products(a, Bt, terms)
        out_ref[0, 0, j] += acc.sum(axis=0)          # [n_ch·ht, 128]
        return carry

    lax.fori_loop(0, fg, _feature, 0)


def _hist_class_kernel(binned_ref, rel_ref, vals_ref, out_ref, *, n_bins,
                       ht, n_ht, n_ch, fg, terms):
    """`_hist_fact_kernel` for a block of classes that share one stored
    `binned` (the K class trees of a boosting round): ``rel_ref``
    `[classes, T]`, ``vals_ref`` `[classes, n_ch, T]` (rows on lanes
    already: a `[T, n_ch]` block a class would pad each to 128 lanes).
    A node takes whole 128-lane rows (``n_bins`` a multiple of 128), so
    lo = bin mod 128 whatever the class and the node: a column's codes
    are read once, and ONE lo one-hot, one set of weight pushes and ONE
    product serve the block's classes, each class's hi one-hot over ITS
    ``ht`` slots. The classes are packed on the sublanes — row (class,
    slot) of the operands below, built ONCE a row tile — so a column
    costs one add, one shift and one compare over `[classes·ht, T]` and
    `terms·n_ch` multiplies, where a class at a time spends a whole
    vreg row on each of its 2- or 4-row pieces (25.8 against 10.1 ms
    for the root of seven class trees: PERF.md section 3). The same
    bf16 products and K-tiles a cell as `_hist_fact_kernel`'s: bitwise
    its sums. Out rows `[channel][class][slot]`."""
    # grid (feature_groups, hi_blocks, class_blocks, row_blocks)
    @pl.when(pl.program_id(3) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    classes, T = rel_ref.shape
    rel_bases = [rel_ref[k, :] * n_bins for k in range(classes)]
    if n_ht > 1:
        # this step's hi block starts at slot program_id·ht
        rel_bases = [b - pl.program_id(1) * (ht * 128) for b in rel_bases]
    Vs = [_mantissa_terms(vals_ref[k], terms) for k in range(classes)]
    first_cell = lax.broadcasted_iota(jnp.int32, (ht, T), 0) * 128
    # a class's cell base less its slot's first cell: the row's one-hot
    # is `0 <= base + bin < 128` (dead rows and rows of another hi
    # block fall outside, as in `_hist_fact_kernel`)
    base = jnp.concatenate([b[None, :] - first_cell for b in rel_bases],
                           axis=0)                   # [classes·ht, T]
    V_rows = [jnp.concatenate(
        [jnp.broadcast_to(V[k][None, :], (ht, T)) for V in Vs], axis=0)
        for k in range(terms * n_ch)]                # each [classes·ht, T]
    iota_lo = lax.broadcasted_iota(jnp.int32, (128, T), 0)

    def _feature(j, carry):
        bins = binned_ref[j, 0, 0, :]                # [T]
        oh = (lax.shift_right_arithmetic(base + bins[None, :], 7) == 0
              ).astype(jnp.bfloat16)
        Bt = (iota_lo == lax.bitwise_and(bins, 127)[None, :]).astype(
            jnp.bfloat16)
        a = jnp.concatenate([oh * v for v in V_rows], axis=0)
        out_ref[0, 0, j] += _term_products(a, Bt, terms).sum(axis=0)
        return carry

    lax.fori_loop(0, fg, _feature, 0)


# Most hi slots ONE grid step holds: the VMEM cap of the kernel's
# working set, not its reach. With the stacked-term matmul the peak is
# the bf16 A [3·n_ch·ht, T] (4.7 MB at ht=256, C=3, T=1024 —
# _fact_row_tile drops to 1024 past ht=64) plus the [ht, T] hi one-hot,
# the [128, T] lo one-hot, the f32 [3·n_ch·ht, 128] dot result (1.2 MB)
# and the resident out block (_OUT_BUDGET) — ~10 MB worst case against
# ~16 MB/core VMEM. TIGHT: the on-chip kernel gate compiles exactly
# this cap shape as `fact_kernel_cap`; if it fails there, lower this
# cap. Deeper levels (n_nodes·n_bins > 2^15) run as several hi blocks
# of at most this many slots, each at the cap shape's VMEM. A class
# batch stacks whole classes up to the same cap (`_class_blocks`) and
# holds its value operands beside the A they become: 4.7 MB more at
# the cap.
_FACT_MAX_NHI = 256

# Most classes ONE grid step holds, whatever their slots: a class's own
# operands — its `[C, T]` values block (double-buffered), its ids, its
# mantissa stack — grow with classes x row tile, which the cap on the
# stacked slots does not see. 32 classes of 2 slots at a 4,096-row tile
# ask 19.6 MB of the 16 MB scoped limit (24 still fit, and 16 of 4);
# 8 fills the sublanes of the ids block, with that room to spare.
# `tests/test_chip_compile.py` compiles 8 classes x 32 slots, 2 x 128,
# and the roots of 32 and 128 classes.
_CLASS_BLOCK_MAX = 8


def _hi_blocks(n_cells: int) -> tuple:
    """(n_ht, ht): the hi blocks that serve ``n_cells`` = nodes·bins
    histogram cells, and the slots in each — the fewest blocks the cap
    allows, evenly filled (the last may hold junk slots)."""
    n_hi = -(-n_cells // 128)                        # ceil
    n_ht = -(-n_hi // _FACT_MAX_NHI)
    return n_ht, -(-n_hi // n_ht)


def node_blocks(n_nodes: int, n_bins: int) -> tuple:
    """(n_ht, nodes a block): the hi blocks a level of ``n_nodes`` nodes
    of ``n_bins`` bins takes, and the whole nodes each holds — node i
    in block i // nodes a block. 0 nodes where a block's slots hold no
    whole count of nodes: no order of the rows then groups them by
    block."""
    n_ht, ht = _hi_blocks(n_nodes * n_bins)
    per = ht * 128 // n_bins
    whole = per * n_bins == ht * 128 and per * n_ht == n_nodes
    return n_ht, per if whole else 0


def block_columns(F: int, C: int) -> int:
    """The columns a hi block at the cap histograms for a frame of
    ``F`` columns and ``C`` channels: ``F`` padded to whole feature
    groups (`_feature_groups`)."""
    return _feature_groups(F, C, _FACT_MAX_NHI)[1]


def _compact_steps(starts, rt_size: int, n_tiles: int):
    """The compacted call's two step tables from ``starts`` ([n_ht + 1]
    rows: hi block b's rows are [starts[b], starts[b+1]), blocks in
    order, every row past the last dead): (block word, row tile) a grid
    step, ``n_tiles + n_ht - 1`` steps. A block takes the tiles its rows
    lie in (a tile two blocks share is visited by each), a block
    without rows one step that adds nothing; the steps past the last
    block repeat its last tile and add nothing. The word is
    ``block << 1 | adds``."""
    n_ht = starts.shape[0] - 1
    lo, hi = starts[:-1], starts[1:]
    first = lo // rt_size
    last = jnp.maximum(hi - 1, lo) // rt_size
    count = last - first + 1                          # >= 1 a block
    ends = jnp.cumsum(count)
    step = jnp.arange(n_tiles + n_ht - 1, dtype=jnp.int32)
    block = jnp.minimum(jnp.searchsorted(ends, step, side="right"),
                        n_ht - 1).astype(jnp.int32)
    live = step < ends[-1]
    tile = jnp.where(live, first[block] + step - (ends - count)[block],
                     last[-1])
    adds = live & (hi > lo)[block]
    return (block << 1 | adds.astype(jnp.int32),
            jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32))


def _feature_groups(F: int, C: int, ht: int) -> tuple[int, int]:
    """(fg, F_pad): the features one grid step of the kernel holds and
    the width the frame is padded to, a multiple of it. Each step keeps
    [fg, C·ht, 128] f32 of output resident (`_OUT_BUDGET`), and fg is
    capped at 64 outright. A frame within the caps goes as one group,
    unpadded; one past them in 8-aligned groups — the widest the caps
    allow while it has at most 64 columns, and past 64 the width that
    pads least (the widest of those): 136 columns go as 17 groups of 8,
    where three groups of 64 would histogram 192."""
    fg_cap = min(F, 64, max(1, _OUT_BUDGET // (C * ht * 128 * 4)))
    if fg_cap >= F:
        return F, F
    fg = max(8, fg_cap // 8 * 8)
    if F > 64:
        fg = min(range(fg, 7, -8), key=lambda w: -(-F // w) * w)
    return fg, -(-F // fg) * fg


def _class_blocks(K: int, ht: int) -> tuple:
    """(n_cb, kb): the blocks of whole classes that serve a batch of K
    classes of ``ht`` hi slots each, and the classes in each — the
    fewest blocks of at most `_CLASS_BLOCK_MAX` classes whose stacked
    slots stay within `_FACT_MAX_NHI`, evenly filled (the last may hold
    dead classes). A class that alone passes the cap is a block of its
    own, served in hi blocks."""
    n_cb = -(-K // min(_CLASS_BLOCK_MAX, max(1, _FACT_MAX_NHI // ht)))
    return n_cb, -(-K // n_cb)


def _hist_pallas(binned, rel, vals, n_nodes: int, n_bins: int,
                 starts=None):
    """[r, F] codes + [r] rel + [r, C] vals -> [n_nodes, F, B, C]; or,
    for K classes over the one stored ``binned`` (the batching rule of
    `_hist_vmappable`), [K, r] rel + [K, r, C] vals ->
    [K, n_nodes, F, B, C] from the one call (``n_bins`` a multiple of
    128; a class a call otherwise). ``starts`` ([n_ht + 1], unbatched,
    a level of several hi blocks): the rows are ordered by hi block,
    block b's in [starts[b], starts[b+1]) and every row past the last
    dead, and each block is served over its own row tiles alone
    (`_hist_compact_kernel`)."""
    batched = rel.ndim == 2
    if batched and n_bins % 128:
        # a node takes part of a 128-lane row: lo = seg mod 128 follows
        # each class's own nodes, so the classes share no lo one-hot —
        # a class at a time through the unbatched kernel (`binned`
        # re-read a class: PERF.md section 3 has it beside the fold)
        return lax.map(lambda a: _hist_pallas(binned, *a, n_nodes, n_bins),
                       (rel, vals))
    r, F = binned.shape
    C = vals.shape[-1]
    K = rel.shape[0] if batched else 1
    nB = n_nodes * n_bins
    n_ht, ht = _hi_blocks(nB)
    # classes a grid step holds on its A operand: what sizes the row
    # tile and the resident out block is their slots together, kb·ht
    n_cb, kb = _class_blocks(K, ht) if batched else (1, 1)
    rt_size = _fact_row_tile(kb * ht, r)
    pad = (-r) % rt_size
    if pad:
        lead = ((0, 0),) * batched
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        rel = jnp.pad(rel, lead + ((0, pad),), constant_values=-1)
        vals = jnp.pad(vals, lead + ((0, pad), (0, 0)))
    rp = r + pad
    rbb = rp // rt_size                 # row blocks
    # feature grouping: each grid step holds [fg, kb·C·ht, 128] f32 of
    # output resident; wide tables split into 8-aligned groups (padded
    # feature columns histogram into junk rows that are sliced away).
    # fg is also capped at 64 outright: the row-stream-reuse win
    # saturates long before that, and the resident out block is the
    # only cost that grows with fg (the kernel's fori_loop reuses one
    # iteration's buffers)
    fg, F_pad = _feature_groups(F, kb * C, ht)
    if F_pad > F:
        binned = jnp.pad(binned, ((0, 0), (0, F_pad - F)))
    n_fg = F_pad // fg
    # [rp, F_pad] -> [F_pad, row_block, 1, rt]: a (fg, 1, 1, rt) block
    # is a row block's bins for one feature group, with the feature on
    # a LEADING dim — the kernel's fori_loop indexes it dynamically,
    # which is only legal off the tiled (sublane, lane) pair
    binned4 = binned.astype(jnp.int32).T.reshape(
        F_pad, rbb, 1, rt_size)
    rel32 = rel.astype(jnp.int32)
    # the instruction's name in the compiled program and in a profile
    # (`hist_fact.N custom-call`). A level past the cap, blocked over
    # its hi slots, is `hist_blocked`: a trace's count of those calls
    # is how often the blocking engages
    name = "hist_fact" if n_ht == 1 else "hist_blocked"
    # under shard_map the output varies per shard: propagate the input's
    # varying-mesh-axes set or jax's vma check rejects the call
    vma = jax.typeof(vals).vma
    params = dict(n_bins=n_bins, ht=ht, n_ht=n_ht, n_ch=C, fg=fg, terms=3)
    binned_spec = pl.BlockSpec((fg, 1, 1, rt_size),
                               lambda g, b, k, rt: (g, rt, 0, 0))
    if batched:
        if n_cb * kb > K:               # dead classes fill the last block
            grow = ((0, n_cb * kb - K), (0, 0))
            rel32 = jnp.pad(rel32, grow, constant_values=-1)
            vals = jnp.pad(vals, grow + ((0, 0),))
        out = pl.pallas_call(
            functools.partial(_hist_class_kernel, **params),
            out_shape=jax.ShapeDtypeStruct(
                (n_fg, n_ht, n_cb, fg, C * kb * ht, 128), jnp.float32,
                vma=vma),
            grid=(n_fg, n_ht, n_cb, rbb),
            in_specs=[
                binned_spec,
                # blocks whose class dim is the array's own: the (8,
                # 128) rule that refuses `vmap`'s squeezed (1, T) block
                # over [K, rows] has nothing to say
                pl.BlockSpec((None, kb, rt_size),
                             lambda g, b, k, rt: (k, 0, rt)),
                pl.BlockSpec((None, kb, C, rt_size),
                             lambda g, b, k, rt: (k, 0, 0, rt)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, None, fg, C * kb * ht, 128),
                lambda g, b, k, rt: (g, b, k, 0, 0, 0)),
            # row blocks alone accumulate into an out block
            compiler_params=_dimsem("parallel", "parallel", "parallel",
                                    "arbitrary"),
            interpret=_interpret(),
            name=name, metadata={"kernel": name},
        )(binned4, rel32.reshape(n_cb, kb, rp),
          vals.transpose(0, 2, 1).reshape(n_cb, kb, C, rp))
        # [n_fg, n_ht, n_cb, fg, C·kb·ht, 128] -> [K, F, C, n_ht·ht·128]
        out = out.reshape(n_fg, n_ht, n_cb, fg, C, kb, ht * 128
                          ).transpose(2, 5, 0, 3, 4, 1, 6).reshape(
            n_cb * kb, F_pad, C, n_ht * ht * 128)[:K, :F, :, :nB]
        return out.reshape(K, F, C, n_nodes, n_bins).transpose(
            0, 3, 1, 4, 2)
    if starts is not None and n_ht > 1:
        out = _hist_compact_call(binned4, rel32, vals, starts, params,
                                 rt_size, n_fg, name, vma)
    else:
        out = pl.pallas_call(
            functools.partial(_hist_fact_kernel, **params),
            # one (fg, C·ht, 128) block per (feature group, hi block),
            # contiguous
            out_shape=jax.ShapeDtypeStruct((n_fg, n_ht, fg, C * ht, 128),
                                           jnp.float32, vma=vma),
            grid=(n_fg, n_ht, 1, rbb),
            in_specs=[
                binned_spec,
                # (the third grid axis is 1: `k·rb` is 0, and stays in the
                # index maps so that the accepted cells' kernel is, to the
                # letter, the one their ledger lines were measured with)
                pl.BlockSpec((rt_size,),
                             lambda g, b, k, rt, rb=rbb: (k * rb + rt,)),
                pl.BlockSpec((rt_size, C),
                             lambda g, b, k, rt, rb=rbb: (k * rb + rt, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, fg, C * ht, 128),
                                   lambda g, b, k, rt: (g, b, 0, 0, 0)),
            # feature groups and hi blocks write DISTINCT out blocks
            # (parallel — Mosaic may pipeline them); row blocks ACCUMULATE
            # into the same block (arbitrary = sequential)
            compiler_params=_dimsem("parallel", "parallel", "arbitrary",
                                    "arbitrary"),
            interpret=_interpret(),
            name=name, metadata={"kernel": name},
        )(binned4, rel32, vals)
    # [n_fg, n_ht, fg, C·ht, 128] -> [F, C, n_ht·ht·128] -> [n, F, B, C]
    out = out.reshape(n_fg, n_ht, fg, C, ht * 128).transpose(
        0, 2, 3, 1, 4).reshape(F_pad, C, n_ht * ht * 128)[:F, :, :nB]
    return out.reshape(F, C, n_nodes, n_bins).transpose(2, 0, 3, 1)


def _hist_compact_call(binned4, rel32, vals, starts, params, rt_size: int,
                       n_fg: int, name: str, vma):
    """The unbatched call over rows ordered by hi block (``starts``:
    `_hist_pallas`): grid (feature groups, steps), each step's hi block
    and row tile scalar-prefetched from `_compact_steps`, the out block
    the step's hi block's. Its out is the blocked call's."""
    fg, C, ht, n_ht = (params[k] for k in ("fg", "n_ch", "ht", "n_ht"))
    block, tile = _compact_steps(starts.astype(jnp.int32), rt_size,
                                 rel32.shape[0] // rt_size)
    return pl.pallas_call(
        functools.partial(_hist_compact_kernel, **params),
        out_shape=jax.ShapeDtypeStruct((n_fg, n_ht, fg, C * ht, 128),
                                       jnp.float32, vma=vma),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_fg, block.shape[0]),
            in_specs=[
                pl.BlockSpec((fg, 1, 1, rt_size),
                             lambda g, s, b, t: (g, t[s], 0, 0)),
                pl.BlockSpec((rt_size,), lambda g, s, b, t: (t[s],)),
                pl.BlockSpec((rt_size, C), lambda g, s, b, t: (t[s], 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, fg, C * ht, 128),
                lambda g, s, b, t: (g, b[s] >> 1, 0, 0, 0))),
        # a block's steps follow each other and accumulate into its out
        # block (arbitrary = sequential)
        compiler_params=_dimsem("parallel", "arbitrary"),
        interpret=_interpret(),
        name=name, metadata={"kernel": name},
    )(block, tile, binned4, rel32, vals)


def _hist_call(binned, rel, vals, n_nodes: int, n_bins: int, impl: str,
               starts=None):
    if impl == "pallas":
        return _hist_pallas(binned, rel, vals, n_nodes, n_bins, starts)
    # a sum by segment is the same in any order of the rows
    return _hist_segment(binned, rel, vals, n_nodes, n_bins)


def _hist_vmappable(binned, rel, vals, n_nodes: int, n_bins: int,
                    impl: str):
    """Histogram build with a class-batching rule that never vmaps the
    Pallas kernel.

    ``jax.vmap`` of a pallas_call prepends a squeezed batch dim to
    every block spec, and Mosaic rejects that for the rank-1 row-stream
    operands (block (1, T) over a [K, rows] array fails the (8, 128)
    divisibility rule) — the round-4 on-chip kernel gate caught exactly
    this in the fused multinomial boost scan, which grows its K class
    trees under vmap. Instead the rule hands the kernel the batch
    itself: ONE call whose row tile carries the K classes' node ids and
    values, in which a column's codes are read once and each class's
    rows are multiplied against that class's hi slots alone
    (`_hist_class_kernel`; PERF.md section 6, PR 39).
    """
    fn = functools.partial(_hist_call, n_nodes=n_nodes, n_bins=n_bins,
                           impl=impl)
    cv = custom_vmap(fn)

    @cv.def_vmap
    def _rule(axis_size, in_batched, binned_b, rel_b, vals_b):
        K = axis_size
        bb, rb, vb = in_batched
        if impl != "pallas":
            # segment_sum vmaps fine as-is — no kernel, no flattening
            out = jax.vmap(fn, in_axes=(0 if bb else None,
                                        0 if rb else None,
                                        0 if vb else None))(
                binned_b, rel_b, vals_b)
            return out, True
        rel2 = rel_b if rb else jnp.broadcast_to(
            rel_b[None], (K,) + rel_b.shape)
        vals2 = vals_b if vb else jnp.broadcast_to(
            vals_b[None], (K,) + vals_b.shape)
        if bb:
            # every class its own codes: nothing to share, so a class
            # at a time through the unbatched call
            return lax.map(lambda a: fn(*a), (binned_b, rel2, vals2)), True
        return fn(binned_b, rel2, vals2), True

    return cv(binned, rel, vals)


def resolve_impl(impl: str) -> str:
    if impl == "auto":
        from ..config import get_config

        cfg = get_config("hist_impl")     # env/programmatic tier
        if cfg != "auto":
            if cfg not in ("segment", "pallas"):
                # the env tier (H2O_TPU_HIST_IMPL) is unvalidated at
                # load — a typo must not silently demote the kernel
                raise ValueError(
                    f"H2O_TPU_HIST_IMPL/config hist_impl '{cfg}' is not "
                    "one of auto/segment/pallas")
            return cfg
        return "pallas" if jax.default_backend() == "tpu" else "segment"
    if impl not in ("segment", "pallas"):
        raise ValueError(f"unknown histogram impl '{impl}'")
    return impl


def build_histogram(binned, rel, g, h, w, n_nodes: int, n_bins: int,
                    impl: str = "auto", unit_hess: bool = False,
                    starts=None):
    """Per-shard histogram [n_nodes, F, B, 3] of (Σgw, Σhw, Σw).

    binned: [r, F] uint8 bin codes; rel: [r] int32 node id (-1 dead);
    w: [r] row weight (0 for padding/unsampled rows).

    ``starts`` ([n_ht + 1] int32; `node_blocks` gives n_ht): the caller
    has ordered the rows by hi block — block b's live rows in
    [starts[b], starts[b+1]), none past the last — and the kernel runs
    each block over its own row tiles alone. Never under `vmap`.

    ``unit_hess``: the caller asserts h ≡ 1 (gaussian/laplace/quantile/
    huber losses and DRF), so Σhw == Σw and the kernels accumulate TWO
    channels [Σgw, Σw] instead of three — 1/3 fewer MXU passes and a
    1/3 smaller psum payload at every tree level. The result is then
    [..., 2]; callers expand back to [..., 3] AFTER their psum with
    ``expand_unit_hess`` (expanding earlier would forfeit the psum
    saving).
    """
    live = (rel >= 0) & (w > 0)
    rel = jnp.where(live, rel, -1)
    impl = resolve_impl(impl)
    # where() (not just *w) so NaN g/h in dead rows can't poison sums
    if unit_hess:
        vals = jnp.where(live[:, None],
                         jnp.stack([g * w, w], axis=1), 0.0)
    else:
        vals = jnp.where(live[:, None],
                         jnp.stack([g * w, h * w, w], axis=1), 0.0)
    if starts is not None:
        return _hist_call(binned, rel, vals, n_nodes, n_bins, impl, starts)
    return _hist_vmappable(binned, rel, vals, n_nodes, n_bins, impl)


def expand_unit_hess(hist2):
    """[..., 2] (Σgw, Σw) → [..., 3] (Σgw, Σhw=Σw, Σw) — the H channel
    of a unit-hessian histogram IS the weight channel."""
    return jnp.concatenate(
        [hist2[..., 0:1], hist2[..., 1:2], hist2[..., 1:2]], axis=-1)
