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
  matches nothing there, as a dead row does.

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
    # grid (feature_groups, hi_blocks, n_copies, row_blocks): one step
    # covers a whole FEATURE GROUP of fg features for its row block —
    # the row-stream operands (rel, vals, mantissa split) load and
    # compute ONCE per row block instead of once per (feature, row
    # block), and the grid shrinks F×.
    first = (pl.program_id(2) == 0) & (pl.program_id(3) == 0)

    @pl.when(first)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    rel = rel_ref[:]                                 # [T]
    rel_base = rel * n_bins
    if n_ht > 1:
        # this step's hi block starts at slot program_id·ht: shift the
        # cell index so the block's slots read 0..ht-1 below
        rel_base = rel_base - pl.program_id(1) * (ht * 128)
    T = rel.shape[0]
    vals_t = vals_ref[:].T                           # [n_ch, T]
    # f32-precision via `terms` bf16 mantissa terms, split on the TINY
    # [n_ch, T] values and masked by the 0/1 one-hot IN bf16 —
    # bit-identical to splitting the big masked A (0/1 masking commutes
    # with rounding) but skips materializing a [n_ch*ht, T] f32 A
    # plus two subtract passes over it: the A-build drops from ~6
    # f32-width VPU passes to `terms` bf16-width multiplies.
    V = _mantissa_terms(vals_t, terms)               # [terms·n_ch, T]
    iota_hi = lax.broadcasted_iota(jnp.int32, (ht, T), 0)
    iota_lo = lax.broadcasted_iota(jnp.int32, (128, T), 0)
    # contract the ROW axis of both operands: `lo` stays on the lanes
    # it arrives on (`lo[:, None]` against a [T, 128] iota relaid every
    # row's value across a sublane row: what bound a shallow call)
    dn = (((1,), (1,)), ((), ()))

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
        # ONE matmul with all mantissa terms stacked into M — the
        # MXU's row occupancy multiplies (terms·n_ch·ht rows instead
        # of `terms` passes of n_ch·ht); the per-term partial sums
        # recombine with one cheap VPU add over [n_ch·ht, 128]. Same
        # bf16 products, same f32 accumulation.
        a = jnp.concatenate(
            [oh_hi * V[k][None, :] for k in range(terms * n_ch)],
            axis=0)                             # [terms·n_ch·ht, T]
        acc = lax.dot_general(a, Bt, dimension_numbers=dn,
                              preferred_element_type=jnp.float32)
        acc = acc.reshape(terms, n_ch * ht, 128)
        out_ref[0, 0, j] += acc.sum(axis=0)          # [n_ch·ht, 128]
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
# of at most this many slots, each at the cap shape's VMEM.
_FACT_MAX_NHI = 256


def _hi_blocks(n_cells: int) -> tuple:
    """(n_ht, ht): the hi blocks that serve ``n_cells`` = nodes·bins
    histogram cells, and the slots in each — the fewest blocks the cap
    allows, evenly filled (the last may hold junk slots)."""
    n_hi = -(-n_cells // 128)                        # ceil
    n_ht = -(-n_hi // _FACT_MAX_NHI)
    return n_ht, -(-n_hi // n_ht)


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


def _hist_pallas(binned, rel, vals, n_nodes: int, n_bins: int,
                 binned_tile: int = 1, row_tile: int | None = None):
    """``binned_tile`` > 1: rel/vals carry ``binned_tile`` consecutive
    copies of the row range (the flattened class batch) while binned is
    stored ONCE — the grid index map re-reads the same bin blocks per
    copy instead of materializing K copies in HBM. Such callers must
    pre-align each copy's rows and pass the ``row_tile`` they aligned
    to (one decision, not two that must agree)."""
    r, F = binned.shape
    C = vals.shape[1]
    nB = n_nodes * n_bins
    n_ht, ht = _hi_blocks(nB)
    rt_size = row_tile or _fact_row_tile(ht, r)
    pad = (-r) % rt_size
    if pad:
        assert binned_tile == 1     # tiled callers pre-align rows
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        rel = jnp.pad(rel, (0, pad), constant_values=-1)
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    rp = r + pad
    rbb = rp // rt_size                 # row blocks per binned copy
    # feature grouping: each grid step holds [fg, C·ht, 128] f32 of
    # output resident; wide tables split into 8-aligned groups (padded
    # feature columns histogram into junk rows that are sliced away).
    # fg is also capped at 64 outright: the row-stream-reuse win
    # saturates long before that, and the resident out block is the
    # only cost that grows with fg (the kernel's fori_loop reuses one
    # iteration's buffers)
    fg, F_pad = _feature_groups(F, C, ht)
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
    out = pl.pallas_call(
        functools.partial(_hist_fact_kernel, n_bins=n_bins, ht=ht,
                          n_ht=n_ht, n_ch=C, fg=fg, terms=3),
        # one (fg, C·ht, 128) block per (feature group, hi block),
        # contiguous
        out_shape=jax.ShapeDtypeStruct((n_fg, n_ht, fg, C * ht, 128),
                                       jnp.float32, vma=vma),
        grid=(n_fg, n_ht, binned_tile, rbb),
        in_specs=[
            pl.BlockSpec((fg, 1, 1, rt_size),
                         lambda g, b, k, rt: (g, rt, 0, 0)),
            pl.BlockSpec((rt_size,),
                         lambda g, b, k, rt, rb=rbb: (k * rb + rt,)),
            pl.BlockSpec((rt_size, C),
                         lambda g, b, k, rt, rb=rbb: (k * rb + rt, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, fg, C * ht, 128),
                               lambda g, b, k, rt: (g, b, 0, 0, 0)),
        # feature groups and hi blocks write DISTINCT out blocks
        # (parallel — Mosaic may pipeline them); copies and row blocks
        # ACCUMULATE into the same block (arbitrary = sequential)
        compiler_params=_dimsem("parallel", "parallel", "arbitrary",
                                "arbitrary"),
        interpret=_interpret(),
        name=name, metadata={"kernel": name},
    )(binned4, rel32, vals)
    # [n_fg, n_ht, fg, C·ht, 128] -> [F, C, n_ht·ht·128] -> [n, F, B, C]
    out = out.reshape(n_fg, n_ht, fg, C, ht * 128).transpose(
        0, 2, 3, 1, 4).reshape(F_pad, C, n_ht * ht * 128)[:F, :, :nB]
    return out.reshape(F, C, n_nodes, n_bins).transpose(2, 0, 3, 1)


def _hist_call(binned, rel, vals, n_nodes: int, n_bins: int, impl: str):
    fn = _hist_pallas if impl == "pallas" else _hist_segment
    return fn(binned, rel, vals, n_nodes, n_bins)


def _hist_vmappable(binned, rel, vals, n_nodes: int, n_bins: int,
                    impl: str):
    """Histogram build with a class-batching rule that never vmaps the
    Pallas kernel.

    ``jax.vmap`` of a pallas_call prepends a squeezed batch dim to
    every block spec, and Mosaic rejects that for the rank-1 row-stream
    operands (block (1, T) over a [K, rows] array fails the (8, 128)
    divisibility rule) — the round-4 on-chip kernel gate caught exactly
    this in the fused multinomial boost scan, which grows its K class
    trees under vmap. Instead of batching the kernel, the batch is
    LOWERED AWAY: class k's rows are relabeled to nodes
    [k·n_nodes, (k+1)·n_nodes) and the SAME flat kernel runs once over
    the concatenated row stream. Identical sums, and the MXU M
    dimension (channels × hi-slots) gets K× fuller than K separate
    passes would — batching IMPROVES systolic occupancy here.
    """
    cv = custom_vmap(
        functools.partial(_hist_call, n_nodes=n_nodes, n_bins=n_bins,
                          impl=impl))

    @cv.def_vmap
    def _rule(axis_size, in_batched, binned_b, rel_b, vals_b):
        K = axis_size
        bb, rb, vb = in_batched
        if impl != "pallas":
            # segment_sum vmaps fine as-is — no kernel, no flattening
            fn = functools.partial(_hist_call, n_nodes=n_nodes,
                                   n_bins=n_bins, impl=impl)
            out = jax.vmap(fn, in_axes=(0 if bb else None,
                                        0 if rb else None,
                                        0 if vb else None))(
                binned_b, rel_b, vals_b)
            return out, True

        r = rel_b.shape[1] if rb else rel_b.shape[0]
        # pad each class's rows to the row tile the flat kernel will
        # pick for the MERGED node count
        rt = _fact_row_tile(_hi_blocks(K * n_nodes * n_bins)[1], r)
        pad = (-r) % rt
        C = vals_b.shape[-1]
        F = binned_b.shape[-1]
        # per-class row padding BEFORE flattening so each class's rows
        # stay aligned with the (re-read) binned row blocks
        if bb:
            binned_f = jnp.pad(binned_b, ((0, 0), (0, pad), (0, 0))
                               ).reshape(K * (r + pad), F)
            tile = 1
        else:
            binned_f = jnp.pad(binned_b, ((0, pad), (0, 0)))
            tile = K        # binned stored once; grid re-reads it K×
        rel2 = rel_b if rb else jnp.broadcast_to(rel_b[None], (K, r))
        rel2 = jnp.pad(rel2, ((0, 0), (0, pad)), constant_values=-1)
        # class k's rows land in nodes [k·n_nodes, (k+1)·n_nodes)
        rel2 = jnp.where(rel2 >= 0,
                         rel2 + (jnp.arange(K, dtype=jnp.int32)
                                 * n_nodes)[:, None], -1)
        vals2 = vals_b if vb else jnp.broadcast_to(
            vals_b[None], (K, r, C))
        vals2 = jnp.pad(vals2, ((0, 0), (0, pad), (0, 0)))
        out = _hist_pallas(binned_f, rel2.reshape(K * (r + pad)),
                           vals2.reshape(K * (r + pad), C),
                           K * n_nodes, n_bins, binned_tile=tile,
                           row_tile=rt)
        return out.reshape((K, n_nodes) + out.shape[1:]), True

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
                    impl: str = "auto", unit_hess: bool = False):
    """Per-shard histogram [n_nodes, F, B, 3] of (Σgw, Σhw, Σw).

    binned: [r, F] uint8 bin codes; rel: [r] int32 node id (-1 dead);
    w: [r] row weight (0 for padding/unsampled rows).

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
    return _hist_vmappable(binned, rel, vals, n_nodes, n_bins, impl)


def expand_unit_hess(hist2):
    """[..., 2] (Σgw, Σw) → [..., 3] (Σgw, Σhw=Σw, Σw) — the H channel
    of a unit-hessian histogram IS the weight channel."""
    return jnp.concatenate(
        [hist2[..., 0:1], hist2[..., 1:2], hist2[..., 1:2]], axis=-1)
