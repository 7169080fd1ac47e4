"""The histogram kernel's forms, each alone on one chip: seconds a call.

Run on the chip (`python tools/hist_forms.py`) before a PR changes the
kernel's arithmetic or which form serves which level (ISSUE 35; the
table this wrote is in PERF.md section 3). A call is
`_hist_pallas`-shaped: [rows, F] bin codes, [rows] node ids, [rows, C]
values -> [n_nodes, F, B, C]; each (form, shape) is jitted alone, run
once to compile and `--calls` times more, and the least seconds kept
(a call includes the int32 transposition of the codes, ~8 ms at
4,194,304 x 28, which every form pays). Every form's result is compared
with `_hist_segment` (run on the host, a column at a time; largest
difference over the largest sum) and with the shipped kernel (bitwise,
or the largest difference):

  a  the kernel until PR 35: the lo one-hot `[T, 128]` built as
     `iota == lo[:, None]` and latched, a column's A operand streamed.
     896 lane permutes a (column, 4,096-row tile) lay `lo` across
     sublanes: 2.4 µs a (column, tile) whatever the level computes
  b  the SHIPPED kernel (`ops/histogram._hist_fact_kernel`): the lo
     one-hot built as `iota[128, T] == lo[None, :]` and contracted over
     the row axis of both operands — no relayout of `lo`, the same 256
     weight pushes a (column, tile), bitwise (a)'s sums
  c  node-stationary (ISSUE 35's proposal, measured and not shipped):
     W[t, (k, c, node)] = V_k,c[t] * 1[rel_t = node] built once a row
     tile and latched a K-tile for several columns, each column's bin
     one-hot `iota[n_bins, T] == bins[None, :]` streamed against it.
     Wins over (a), loses to (b) at 256 bins (1,024 result pops a
     (column, tile) are its floor) and ties it at 64

With `--classes K` (ISSUE 39; needs `--shape ROWSxCOLUMNS[xBINS[xC]]`)
the call is the K-class grower's: ONE stored `[rows, F]` of codes,
`[K, rows]` node ids and `[K, rows, C]` values ->
`[K, n_nodes, F, B, C]`, through

  fold  the class batch folded into the node axis, which B replaced:
        class k's rows relabelled to nodes [k·n, (k+1)·n), the flat
        kernel over the K concatenated row streams with K·n nodes —
        every copy multiplied against all K classes' hi slots
  A     the copy axis indexes the out block: a class a grid step
        against its own slots, the codes re-read K times (the shipped
        kernel with blocks of one class)
  B     the SHIPPED rule (`ops/histogram._hist_class_kernel`): the
        classes of one row tile in one grid step, a column's codes
        read once, the classes packed on the sublanes of ONE A operand
        against one lo one-hot. At a bin count that is no multiple of
        128 the shipped rule IS `map`, and so is A
  map   K unbatched calls under `lax.map`: the kernels of the grower's
        fallback past `_MULTI_HIST_BUDGET`

each compared with B (bitwise, or the largest difference) and with
`_hist_segment` a class. A form's first call compiles; `--limit` is
the seconds after which a (form, shape) is abandoned (its line says
`timeout`; the calls already queued on the chip still drain).

With `--compact` the levels past one hi block, their rows
ordered by hi block (dead rows, 36.8% as a bag leaves them, last):

  blocked    the shipped call where a tree keeps its rows' order: every
             row tile against every hi block
  compacted  the SHIPPED call where a tree orders its rows by node
             block (`build_histogram`'s ``starts``): each block over its
             own row tiles, bitwise `blocked`'s sums

and what ordering one tree's rows costs, at the cells' row counts and
code widths (a row's codes, g, w, its node and its leaf; h too at C 3):

  sort       the stable sort of the node keys that gives the order
  gather     each array gathered by the order, apart (the shipped form)
  packed     the arrays packed into one [rows, words] int32, ONE gather,
             unpacked
  columns    the codes gathered a column at a time into [F, rows] (a row
             of codes is not padded to 128 lanes), the rest apart
  carried    every column a payload of the sort: no gather
  back       the leaf put back in the caller's order by a scatter (the
             shipped form)
  back_sort  the same by sorting (order, leaf) on the order
  order      the grower's whole step (`core._order_rows` and `back`)

One JSON line a measurement on stdout and in
`chiprun_out/hist_forms.jsonl`. Exits non-zero without a TPU;
`tests/test_hist_forms.py` holds the three forms to `_hist_segment` on
the CPU in interpret mode.
"""

import argparse
import contextlib
import functools
import json
import os
import signal
import sys
import time
import unittest.mock as mock

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from h2o_kubernetes_tpu.models.tree import core
from h2o_kubernetes_tpu.ops import histogram as H


def _fact_kernel_a(binned_ref, rel_ref, vals_ref, out_ref, *, n_bins, ht,
                   n_ht, n_ch, fg, terms):
    """`_hist_fact_kernel` as it was until PR 35: lo one-hot [T, 128]."""
    first = (pl.program_id(2) == 0) & (pl.program_id(3) == 0)

    @pl.when(first)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    rel = rel_ref[:]
    rel_base = rel * n_bins
    if n_ht > 1:
        rel_base = rel_base - pl.program_id(1) * (ht * 128)
    T = rel.shape[0]
    V = H._mantissa_terms(vals_ref[:].T, terms)
    iota_hi = lax.broadcasted_iota(jnp.int32, (ht, T), 0)
    iota_lo = lax.broadcasted_iota(jnp.int32, (T, 128), 1)
    dn = (((1,), (0,)), ((), ()))

    def _feature(j, carry):
        seg = rel_base + binned_ref[j, 0, 0, :]
        hi = lax.shift_right_arithmetic(seg, 7)
        lo = seg - hi * 128
        oh_hi = (iota_hi == hi[None, :]).astype(jnp.bfloat16)
        B = (iota_lo == lo[:, None]).astype(jnp.bfloat16)
        a = jnp.concatenate(
            [oh_hi * V[k][None, :] for k in range(terms * n_ch)], axis=0)
        acc = lax.dot_general(a, B, dimension_numbers=dn,
                              preferred_element_type=jnp.float32)
        out_ref[0, 0, j] += acc.reshape(terms, n_ch * ht, 128).sum(axis=0)
        return carry

    lax.fori_loop(0, fg, _feature, 0)


def _form_a(binned, rel, vals, n_nodes, n_bins):
    # `_hist_pallas` reads its kernel body at trace time
    with mock.patch.object(H, "_hist_fact_kernel", _fact_kernel_a):
        return H._hist_pallas(binned, rel, vals, n_nodes, n_bins)


_NODE_OUT_BUDGET = 4 << 20      # resident [fg, bp, Mp] f32 out block
_NODE_VMEM_LIMIT = 48 << 20     # scoped VMEM asked for (v5e: 128 MiB)


def _hist_node_kernel(binned_ref, rel_ref, vals_ref, out_ref, w_ref, *,
                      n_nodes, bp, n_ch, fg, terms, wc, sg, kc):
    """Node-stationary one-hot histogram matmul, form (c).

    out[j, bin, (k, c, node)] = sum_t O_j[bin, t] * W[t, (k, c, node)]:
    W does not depend on the column, so it is built ONCE a row tile
    into `w_ref` (rows on sublanes, the layout `vals_ref` arrives in)
    and, a K-tile of `kc` rows at a time, latched for `sg` columns'
    bin one-hots stacked along the streamed axis. Same bf16 products
    against an exact 0/1 operand and the same float32 accumulation as
    the shipped kernel; the terms' lane groups are summed by the
    caller.
    """
    # grid (feature_groups, row_blocks)
    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    T = rel_ref.shape[0]
    Mp = w_ref.shape[1]
    # lane m = (k*C + c)*n_nodes + node
    lane = lax.broadcasted_iota(jnp.int32, (1, Mp), 1)
    kc_l = lane // n_nodes
    node_l = lane - kc_l * n_nodes
    k_l = kc_l // n_ch
    c_l = kc_l - k_l * n_ch

    # W in chunks of wc rows: the [wc, Mp] f32 temporaries of the
    # mantissa split stay small whatever the row tile
    def _w(i, carry):
        r0 = pl.multiple_of(i * wc, wc)
        rel = rel_ref[pl.ds(r0, wc)]                 # [wc]
        vals = vals_ref[pl.ds(r0, wc), :]            # [wc, C]
        r = jnp.zeros((wc, Mp), jnp.float32)
        for c in range(n_ch):
            r = jnp.where(c_l == c, vals[:, c:c + 1], r)
        # `_mantissa_terms` on the lane-broadcast values: lane group k
        # keeps term k
        w = None
        for k in range(terms):
            vk = r.astype(jnp.bfloat16).astype(jnp.float32)
            w = vk if w is None else jnp.where(k_l == k, vk, w)
            r = r - vk
        # dead rows (rel = -1) match no node; lanes past terms*C*n_nodes
        # stay zero
        hit = (rel[:, None] == node_l) & (k_l < terms)
        w_ref[pl.ds(r0, wc), :] = jnp.where(hit, w, 0.0).astype(
            jnp.bfloat16)
        return carry

    lax.fori_loop(0, T // wc, _w, 0)
    dn = (((1,), (0,)), ((), ()))
    iota_b = lax.broadcasted_iota(jnp.int32, (bp, kc), 0)

    def _ktile(kt, carry):
        k0 = pl.multiple_of(kt * kc, kc)
        wk = w_ref[pl.ds(k0, kc), :]                 # [kc, Mp]
        for j0 in range(0, fg, sg):
            j1 = min(j0 + sg, fg)
            # the columns' bins stacked as int32 (whole tiles: no data
            # moves), ONE compare, and the mask goes to the MXU as it is
            # packed — stacking the one-hots themselves builds each in
            # float32 first
            bins = jnp.concatenate(
                [jnp.broadcast_to(binned_ref[j, 0, :, pl.ds(k0, kc)],
                                  (bp, kc)) for j in range(j0, j1)],
                axis=0)
            iota = jnp.concatenate([iota_b] * (j1 - j0), axis=0)
            acc = lax.dot_general(
                (iota == bins).astype(jnp.bfloat16), wk,
                dimension_numbers=dn, preferred_element_type=jnp.float32)
            out_ref[0, j0:j1] += acc.reshape(j1 - j0, bp, Mp)
        return carry

    lax.fori_loop(0, T // kc, _ktile, 0)


def _form_c(binned, rel, vals, n_nodes, n_bins):
    """The level through `_hist_node_kernel`: [n_nodes, F, B, C]."""
    terms = 3
    r, F = binned.shape
    C = vals.shape[1]
    M = terms * C * n_nodes
    Mp = -(-M // 128) * 128
    bp = -(-n_bins // 16) * 16          # bf16 sublane tile
    rt_size = 4096 if r >= 8192 else 1024
    pad = (-r) % rt_size
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
        rel = jnp.pad(rel, (0, pad), constant_values=-1)
        vals = jnp.pad(vals, ((0, pad), (0, 0)))
    rbb = (r + pad) // rt_size
    # the widest group the budget holds among those that pad the frame
    # least: 28 columns go as one group, 136 as eight of 17
    cap = max(1, min(F, 64, _NODE_OUT_BUDGET // (bp * Mp * 4)))
    fg = min(range(cap, 0, -1), key=lambda w: -(-F // w) * w)
    F_pad = -(-F // fg) * fg
    if F_pad > F:
        binned = jnp.pad(binned, ((0, 0), (0, F_pad - F)))
    n_fg = F_pad // fg
    binned4 = binned.astype(jnp.int32).T.reshape(F_pad, rbb, 1, rt_size)
    out = pl.pallas_call(
        functools.partial(_hist_node_kernel, n_nodes=n_nodes, bp=bp,
                          n_ch=C, fg=fg, terms=terms, wc=1024,
                          sg=max(1, 2048 // bp), kc=256),
        out_shape=jax.ShapeDtypeStruct((n_fg, fg, bp, Mp), jnp.float32),
        grid=(n_fg, rbb),
        in_specs=[
            pl.BlockSpec((fg, 1, 1, rt_size),
                         lambda g, rt: (g, rt, 0, 0)),
            pl.BlockSpec((rt_size,), lambda g, rt: (rt,)),
            pl.BlockSpec((rt_size, C), lambda g, rt: (rt, 0)),
        ],
        out_specs=pl.BlockSpec((1, fg, bp, Mp),
                               lambda g, rt: (g, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rt_size, Mp), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_NODE_VMEM_LIMIT),
        interpret=H._interpret(),
        name="hist_node", metadata={"kernel": "hist_node"},
    )(binned4, rel.astype(jnp.int32), vals)
    # [n_fg, fg, bp, Mp] -> [F, B, terms, C, n]; the terms summed last
    out = out.reshape(F_pad, bp, Mp)[:F, :n_bins, :M].reshape(
        F, n_bins, terms, C, n_nodes)
    return out.sum(axis=2).transpose(3, 0, 1, 2)     # [n, F, B, C]


FORMS = {"a": _form_a, "b": H._hist_pallas, "c": _form_c}
SHIPPED = "b"

# (rows, F, C, n_bins, code dtype, node counts, forms): the cells' shapes
SHAPES = {
    "higgs256": (4_194_304, 28, 3, 256, "uint8", (1, 2, 4, 8, 16, 32, 64),
                 ("b", "a", "c")),
    "forest64": (4_194_304, 28, 2, 64, "uint8",
                 (1, 2, 4, 8, 16, 32, 64, 128), ("b", "a", "c")),
    "airline512": (8_388_608, 8, 3, 512, "uint16", (1, 8, 64),
                   ("b", "a", "c")),
    "mslr136": (2_270_296, 136, 3, 256, "uint8", (1, 16), ("b", "a", "c")),
}


def _case(rows, F, C, n_bins, dtype, n_nodes, seed, classes=None,
          ordered=False):
    """A level's operands; with ``classes`` the K-class grower's: one
    `binned`, `[K, rows]` node ids and `[K, rows, C]` values; with
    ``ordered`` a bagged tree's rows ordered by node, the dead last."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    binned = jax.random.randint(k1, (rows, F), 0, n_bins,
                                jnp.int32).astype(dtype)
    lead = () if classes is None else (classes,)
    rel = jax.random.randint(k2, lead + (rows,), 0, n_nodes, jnp.int32)
    dead = jax.random.uniform(k3, lead + (rows,)) < (
        0.368 if ordered else 0.1)
    rel = jnp.where(dead, -1, rel)
    if ordered:
        rel = jnp.sort(jnp.where(dead, n_nodes, rel))
        rel = jnp.where(rel == n_nodes, -1, rel)
        dead = rel < 0
    vals = jnp.where(dead[..., None], 0.0,
                     jax.random.normal(k4, lead + (rows, C), jnp.float32))
    return binned, rel, vals


def _fold(binned, rel, vals, n_nodes, n_bins):
    """The class batch as `_hist_vmappable`'s rule lowered it until
    PR 39: the flat kernel over K relabelled copies of the row stream,
    `binned` stored once and re-read a copy, all K·n_nodes nodes' hi
    slots in the one block every copy is multiplied against."""
    K, r = rel.shape
    F, C = binned.shape[1], vals.shape[-1]
    nB = K * n_nodes * n_bins
    n_ht, ht = H._hi_blocks(nB)
    rt_size = H._fact_row_tile(ht, r)
    pad = (-r) % rt_size
    binned = jnp.pad(binned, ((0, pad), (0, 0)))
    rel = jnp.pad(rel, ((0, 0), (0, pad)), constant_values=-1)
    rel = jnp.where(rel >= 0, rel + (jnp.arange(K, dtype=jnp.int32)
                                     * n_nodes)[:, None], -1)
    vals = jnp.pad(vals, ((0, 0), (0, pad), (0, 0)))
    rbb = (r + pad) // rt_size
    fg, F_pad = H._feature_groups(F, C, ht)
    binned = jnp.pad(binned, ((0, 0), (0, F_pad - F)))
    n_fg = F_pad // fg
    out = pl.pallas_call(
        functools.partial(H._hist_fact_kernel, n_bins=n_bins, ht=ht,
                          n_ht=n_ht, n_ch=C, fg=fg, terms=3),
        out_shape=jax.ShapeDtypeStruct((n_fg, n_ht, fg, C * ht, 128),
                                       jnp.float32),
        grid=(n_fg, n_ht, K, rbb),
        in_specs=[
            pl.BlockSpec((fg, 1, 1, rt_size),
                         lambda g, b, k, rt: (g, rt, 0, 0)),
            pl.BlockSpec((rt_size,),
                         lambda g, b, k, rt: (k * rbb + rt,)),
            pl.BlockSpec((rt_size, C),
                         lambda g, b, k, rt: (k * rbb + rt, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, fg, C * ht, 128),
                               lambda g, b, k, rt: (g, b, 0, 0, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=H._interpret(), name="hist_fold",
    )(binned.astype(jnp.int32).T.reshape(F_pad, rbb, 1, rt_size),
      rel.reshape(-1), vals.reshape(-1, C))
    out = out.reshape(n_fg, n_ht, fg, C, ht * 128).transpose(
        0, 2, 3, 1, 4).reshape(F_pad, C, n_ht * ht * 128)[:F, :, :nB]
    return out.reshape(F, C, K, n_nodes, n_bins).transpose(2, 3, 0, 4, 1)


def _class_form_a(binned, rel, vals, n_nodes, n_bins):
    # `_hist_pallas` sizes its class blocks at trace time
    with mock.patch.object(H, "_class_blocks", lambda K, ht: (K, 1)):
        return H._hist_pallas(binned, rel, vals, n_nodes, n_bins)


def _class_map(binned, rel, vals, n_nodes, n_bins):
    return lax.map(lambda a: H._hist_pallas(binned, *a, n_nodes, n_bins),
                   (rel, vals))


CLASS_FORMS = {"fold": _fold, "A": _class_form_a, "B": H._hist_pallas,
               "map": _class_map}
CLASS_SHIPPED = "B"


def _compacted(binned, rel, vals, n_nodes, n_bins):
    """The level over its rows ordered by hi block, as the grower hands
    it: each block's first row from the ordered node ids."""
    n_ht, per = H.node_blocks(n_nodes, n_bins)
    key = jnp.where(rel >= 0, rel // per, n_ht)
    starts = jnp.searchsorted(key, jnp.arange(n_ht + 1, dtype=jnp.int32))
    return H._hist_pallas(binned, rel, vals, n_nodes, n_bins,
                          starts.astype(jnp.int32))


COMPACT_FORMS = {"compacted": _compacted, "blocked": H._hist_pallas}
COMPACT_SHIPPED = "compacted"

# (rows, F, C, n_bins, code dtype, node counts, forms): the cells whose
# levels pass one hi block, at their widths
COMPACT_SHAPES = {
    "forest512": (8_388_608, 8, 2, 512, "uint16", (128, 256, 512, 1024),
                  tuple(COMPACT_FORMS)),
    "airline512": (8_388_608, 8, 3, 512, "uint16", (128, 256),
                   tuple(COMPACT_FORMS)),
    "forest64": (4_194_304, 28, 2, 64, "uint8", (1024,),
                 tuple(COMPACT_FORMS)),
}


def _pack(arrays):
    """[rows, words] int32 of ``arrays`` ([rows] or [rows, k] of 1, 2 or
    4 bytes an element, k·bytes a multiple of 4 a row), and the
    function that unpacks it."""
    parts, cuts = [], [0]
    for a in arrays:
        x = a if a.ndim == 2 else a[:, None]
        n = x.shape[1] * x.dtype.itemsize // 4
        parts.append(lax.bitcast_convert_type(
            x.reshape(x.shape[0], n, 4 // x.dtype.itemsize), jnp.int32)
            if x.dtype.itemsize < 4 else
            lax.bitcast_convert_type(x, jnp.int32))
        cuts.append(cuts[-1] + n)

    def unpack(words):
        out = []
        for a, lo, hi in zip(arrays, cuts, cuts[1:]):
            x = lax.bitcast_convert_type(words[:, lo:hi], a.dtype)
            out.append(x.reshape(a.shape))
        return out

    return jnp.concatenate(parts, axis=1), unpack


def _order_case(rows, F, C, dtype, depth, seed):
    """One tree's rows at the depth it orders them: codes, g (h), w,
    node ids at ``depth`` (36.8% dead) and leaves."""
    k1, k2, k3, k4 = jax.random.split(jax.random.key(seed), 4)
    binned = jax.random.randint(k1, (rows, F), 0, 1 << (8 * jnp.dtype(
        dtype).itemsize), jnp.int32).astype(dtype)
    rel = jax.random.randint(k2, (rows,), 0, 2 ** depth, jnp.int32)
    w = (jax.random.uniform(k3, (rows,)) >= 0.368).astype(jnp.float32)
    g = -(jax.random.uniform(k4, (rows,)) < 0.4).astype(jnp.float32)
    cols = (g,) + ((jnp.ones_like(g),) if C == 3 else ()) + (w,)
    return (binned,) + cols + (rel, rel + (2 ** depth - 1))


def _order_forms(depth):
    """The ordering's pieces, each ``fn(binned, *cols, rel, leaf)``."""
    def order_of(rel, w):
        key = jnp.where((rel >= 0) & (w > 0), rel, 2 ** depth)
        return lax.sort((key, jnp.arange(rel.shape[0], dtype=jnp.int32)),
                        num_keys=1, is_stable=True)[1]

    def sort(*a):
        return order_of(a[-2], a[-3])

    def gather(*a):
        order = order_of(a[-2], a[-3])
        return [x[order] for x in a]

    def packed(*a):
        order = order_of(a[-2], a[-3])
        words, unpack = _pack(a)
        return unpack(words[order])

    def columns(*a):
        # the codes a column at a time, stored [F, rows]: no row of
        # codes padded to 128 lanes
        order = order_of(a[-2], a[-3])
        codes = jnp.stack([a[0][:, j][order] for j in range(a[0].shape[1])])
        return [codes.T] + [x[order] for x in a[1:]]

    def carried(*a):
        # every column a payload of the sort itself: no gather at all
        rel, w = a[-2], a[-3]
        key = jnp.where((rel >= 0) & (w > 0), rel, 2 ** depth)
        F = a[0].shape[1]
        out = lax.sort((key,) + tuple(a[0][:, j] for j in range(F))
                       + a[1:], num_keys=1, is_stable=True)
        return [jnp.stack(out[1:F + 1]).T] + list(out[F + 1:])

    def back(*a):
        order = order_of(a[-2], a[-3])
        return jnp.zeros_like(a[-1]).at[order].set(a[-1],
                                                   unique_indices=True)

    def back_sort(*a):
        order = order_of(a[-2], a[-3])
        return lax.sort((order, a[-1]), num_keys=1)[1]

    def order(*a):
        o, bounds, rows = core._order_rows(depth, a[-2], a[-3], a)
        return rows, bounds, jnp.zeros_like(rows[-1]).at[o].set(
            rows[-1], unique_indices=True)

    return {"order": order, "sort": sort, "gather": gather,
            "packed": packed, "columns": columns, "carried": carried,
            "back": back, "back_sort": back_sort}


# (rows, F, C, code dtype, depth the tree orders its rows at): the cells
# whose levels pass one hi block
ORDER_SHAPES = {
    "forest512": (8_388_608, 8, 2, "uint16", 8),
    "airline512": (8_388_608, 8, 3, "uint16", 8),
    "forest64": (4_194_304, 28, 2, "uint8", 11),
}


def measure_order(name, shape, calls, seed, limit=0):
    """One line a piece of the ordering at one shape; ``packed`` and
    ``gather`` are compared (bitwise), and the whole step with them."""
    rows, F, C, dtype, depth = shape
    args = _order_case(rows, F, C, dtype, depth, seed)
    got = {}
    for form, fn in _order_forms(depth).items():
        line = {"shape": name, "rows": rows, "F": F, "C": C,
                "code_bytes": jnp.dtype(dtype).itemsize, "depth": depth,
                "form": form}
        try:
            with _limit(limit):
                fn = jax.jit(fn)
                t0 = time.perf_counter()
                got[form] = jax.block_until_ready(fn(*args))
                line["first_s"] = time.perf_counter() - t0
                secs = []
                for _ in range(calls):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*args))
                    secs.append(time.perf_counter() - t0)
            line["min_s"], line["max_s"] = min(secs), max(secs)
            line["ns_a_row"] = 1e9 * line["min_s"] / rows
            # the device memory the form asks for besides its operands
            line["temp_bytes"] = fn.lower(*args).compile(
            ).memory_analysis().temp_size_in_bytes
            if form in ("packed", "columns", "carried") and \
                    "gather" in got:
                line["bitwise_gather"] = all(
                    bool(jnp.array_equal(x, y))
                    for x, y in zip(got[form], got["gather"]))
            if form == "back_sort" and "back" in got:
                line["bitwise_back"] = bool(
                    jnp.array_equal(got["back_sort"], got["back"]))
        except TimeoutError as e:
            line["timeout"] = str(e)
        except Exception as e:      # this form failed here; go on
            line["error"] = repr(e)[:400]
        yield line


def _segment_on_host(binned, rel, vals, n_nodes, n_bins):
    """`_hist_segment` on the HOST, a column at a time: the chip
    serializes a scatter (seconds a column at these rows), and vmapped
    over 28 columns its operands are 60 GB."""
    cpu = jax.devices("cpu")[0]
    seg = jax.jit(functools.partial(H._hist_segment, n_nodes=n_nodes,
                                    n_bins=n_bins))     # follows its operands
    rel_h, vals_h = jax.device_put((rel, vals), cpu)
    return jax.device_put(jnp.concatenate(
        [seg(jax.device_put(binned[:, j:j + 1], cpu), rel_h, vals_h)
         for j in range(binned.shape[1])], axis=1), jax.devices()[0])


@contextlib.contextmanager
def _limit(seconds):
    """SIGALRM after ``seconds`` (0: never) of a (form, shape): a
    compile or a call that runs away costs its limit, not the chip
    call's."""
    def _raise(*_):
        raise TimeoutError(f"over {seconds} s")

    if seconds:
        signal.signal(signal.SIGALRM, _raise)
        signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)


def measure(name, shape, n_nodes, forms, calls, seed, segment=True,
            classes=None, limit=0, compact=False):
    """One line a form at one (shape, node count); the shipped form is
    measured first so that the others are compared with it."""
    rows, F, C, n_bins, dtype = shape[:5]
    table, first = (COMPACT_FORMS, COMPACT_SHIPPED) if compact else \
        (FORMS, SHIPPED) if classes is None else \
        (CLASS_FORMS, CLASS_SHIPPED)
    binned, rel, vals = _case(rows, F, C, n_bins, dtype, n_nodes, seed,
                              classes, ordered=compact)
    want = None
    if segment and classes is None:
        want = _segment_on_host(binned, rel, vals, n_nodes, n_bins)
    elif segment:
        want = jnp.stack([_segment_on_host(binned, rel[k], vals[k],
                                           n_nodes, n_bins)
                          for k in range(classes)])
    shipped = None
    for form in sorted(forms, key=lambda f: f != first):
        line = {"shape": name, "rows": rows, "F": F, "C": C,
                "n_bins": n_bins, "n_nodes": n_nodes, "form": form}
        if classes is not None:
            line["classes"] = classes
        try:
            with _limit(limit):
                fn = jax.jit(functools.partial(
                    table[form], n_nodes=n_nodes, n_bins=n_bins))
                t0 = time.perf_counter()
                got = fn(binned, rel, vals).block_until_ready()
                line["first_s"] = time.perf_counter() - t0
                secs = []
                for _ in range(calls):
                    t0 = time.perf_counter()
                    fn(binned, rel, vals).block_until_ready()
                    secs.append(time.perf_counter() - t0)
            line["min_s"], line["max_s"] = min(secs), max(secs)
            if want is not None:
                line["rel_err_segment"] = float(
                    jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            if form == first:
                shipped = got
            elif shipped is not None:
                line["bitwise_shipped"] = bool(
                    jnp.array_equal(got, shipped))
                line["rel_diff_shipped"] = float(
                    jnp.max(jnp.abs(got - shipped))
                    / jnp.max(jnp.abs(shipped)))
        except TimeoutError as e:
            line["timeout"] = str(e)
        except Exception as e:      # this form failed here; go on
            line["error"] = repr(e)[:400]
        yield line


def _custom_shape(text, classes):
    """`581632x54` -> a shape of that many rows and columns at the
    K-class cell's widths (256 bins, C 3, 8-bit codes);
    `581632x54x64x2` states the bins and the channels too."""
    given = [int(x) for x in text.lower().split("x")]
    rows, F, n_bins, C = given + [None, None, 256, 3][len(given):]
    return (rows, F, C, n_bins, "uint8", (1, 4, 16),
            tuple(CLASS_FORMS) if classes else ("b", "a", "c"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shape", action="append", default=None,
                    help=f"one of {sorted(SHAPES)}, or "
                         "ROWSxCOLUMNS[xBINS[xC]] (256 bins, C 3; "
                         "8-bit codes)")
    ap.add_argument("--classes", type=int, default=None,
                    help="K: time the class batch's forms "
                         f"{sorted(CLASS_FORMS)} at a ROWSxCOLUMNS shape")
    ap.add_argument("--forms", default=None,
                    help="comma-separated, overrides the shape's list")
    ap.add_argument("--nodes", default=None, help="comma-separated")
    ap.add_argument("--no-segment", action="store_true",
                    help="skip `_hist_segment` (136 columns of it take "
                         "minutes): forms are compared with the shipped "
                         "one alone")
    ap.add_argument("--compact", action="store_true",
                    help="time the levels past one hi block over rows "
                         f"ordered by block {sorted(COMPACT_FORMS)}, and "
                         "the ordering's pieces, at "
                         f"{sorted(COMPACT_SHAPES)}")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--seed", type=int, default=35)
    ap.add_argument("--limit", type=int, default=0,
                    help="seconds a (form, shape) may take, compile "
                         "included; 0: none")
    args = ap.parse_args(argv)
    known = COMPACT_SHAPES if args.compact else SHAPES
    shapes = {name: known[name] if name in known
              else _custom_shape(name, args.classes)
              for name in args.shape or sorted(known)}
    if args.classes and any(name in SHAPES for name in shapes):
        ap.error("--classes needs --shape ROWSxCOLUMNS")
    from h2o_kubernetes_tpu.runtime.backend import require_tpu

    require_tpu("hist_forms")
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/hist_forms.jsonl", "a") as sink:
        def emit(line):
            txt = json.dumps(line)
            print(txt, flush=True)
            sink.write(txt + "\n")
            sink.flush()

        for name in shapes if args.compact else ():
            if name in ORDER_SHAPES:
                for line in measure_order(name, ORDER_SHAPES[name],
                                          args.calls, args.seed,
                                          limit=args.limit):
                    emit(line)
        for name, shape in shapes.items():
            forms = tuple(args.forms.split(",")) if args.forms \
                else shape[6]
            nodes = tuple(int(n) for n in args.nodes.split(",")) \
                if args.nodes else shape[5]
            for n_nodes in nodes:
                for line in measure(name, shape, n_nodes, forms,
                                    args.calls, args.seed,
                                    segment=not args.no_segment,
                                    classes=args.classes,
                                    limit=args.limit,
                                    compact=args.compact):
                    emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
