"""Out-of-core chunk-streamed boosting (the 10M-row training path).

When the uint8 binned matrix itself exceeds the device-memory headroom
left by `H2O_TPU_HIST_BYTES_BUDGET` (models/gbm.py derives the
trigger), training switches from the fused all-rows-resident
`core._boost_jit` scan (one tree a round; its round is
`core._boost_shard`) to this driver: the binned matrix lives as
HOST-resident row chunks and is streamed to device per tree level with
double-buffered `device_put` (the upload of chunk c+1 overlaps the
histogram build of chunk c), exactly the compressed-stream design of
the GBDT-on-accelerator literature (PAPERS.md: *Out-of-Core GPU
Gradient Boosting*, arXiv:2005.09148; *XGBoost: Scalable GPU
Accelerated Learning*, arXiv:1806.11248 §"out-of-core").

Only the per-row COLUMNS stay device-resident full-length-equivalent —
y, weights and the boosting margin, each chunked alongside the binned
chunks (12 B/row total) — so the device working set is
O(chunk · F + rows · 12 B + level histograms).

Numerics: per-level histograms are accumulated over chunks in FIXED
chunk order with f32 adds, and every split/leaf computation reuses the
shared `core._find_splits` / `core._leaf_value` code paths — so the
streamed (host-chunk) and resident (device-chunk) modes are
bitwise-identical (tests/test_chunked_path.py asserts it; the
`H2O_TPU_OOC_RESIDENT=1` debug mode exists for exactly that test).
Versus the monolithic fused path the only difference is the f32
reassociation at chunk boundaries: sums that are exact (e.g. the
first gaussian round on a ±0.5-gradient response) are bitwise equal,
general multi-tree models agree to float tolerance.

Scope: pointwise single-output boosting (GBM/XGBoost gaussian,
bernoulli, poisson, gamma, tweedie, laplace, quantile) at
sample_rate=1 with no scoring cadence. GOSS gradient-based sampling
(H2O_TPU_GOSS, docs/SCALING.md "Gradient-based sampling") IS
stream-eligible: its per-round selection is a pure function of
exactly-associative global stats plus a per-row (key, global row id)
hash, so the chunk grid picks the same rows the fused in-HBM path
picks at the same seed. Multinomial (K margins), DRF
voting, huber (needs a global residual quantile per round),
checkpoint continuation, score_every (the stream scores once at the
end — a requested cadence must not be dropped silently), row/column
subsampling (the streamed key schedule differs from the fused
core's, so sampled models would depend on which path engaged or on
the chunk-size knob) and multi-host meshes stay on the in-HBM path —
models/gbm.BoostPlan.ooc_chunk is the single gate; docs/SCALING.md
documents the matrix.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...ops.histogram import build_histogram as _build_histogram_op
from ...ops.histogram import expand_unit_hess as _expand_unit_hess
from ...ops.histogram import resolve_impl as _resolve_impl
from ...runtime import telemetry
from ...runtime.mesh import ROWS, global_mesh
from .core import (BoostParams, Tree, TreeParams, _boost_grad_hess,
                   _find_splits, _leaf_value, descend_tree,
                   goss_cap_rows, goss_compact, goss_local_counts,
                   goss_rank_stat, goss_round_keys, goss_row_factor,
                   goss_threshold, row_orig_bins)


# ---------------------------------------------------------------------------
# Chunk container
# ---------------------------------------------------------------------------

@dataclass
class BinnedChunks:
    """Row-chunked training set: binned uint8 chunks (host numpy in
    streamed mode, device arrays in resident mode) plus aligned
    per-chunk device columns. All chunks share one shape so every
    jitted per-chunk program compiles once per tree level."""

    binned: list                    # [chunk_rows, F] uint8 (np or jax)
    y: list                         # [chunk_rows] f32 device
    w: list                         # [chunk_rows] f32 device
    margin: list                    # [chunk_rows] f32 device
    chunk_rows: int
    padded_rows: int                # logical padded length (pre-chunking)
    streamed: bool                  # True: host chunks, device_put per use

    @property
    def n_chunks(self) -> int:
        return len(self.binned)

    @property
    def n_features(self) -> int:
        return self.binned[0].shape[1]


def chunk_rows_for(padded_rows: int, n_features: int, budget: float,
                   hist_bytes: int, mesh=None) -> int:
    """Rows per chunk: a quarter of the histogram-budget headroom (two
    staging buffers + the device copy in flight + slack), floored at
    1 MiB of uint8 codes, aligned to the mesh row axis, capped at the
    table. ``H2O_TPU_OOC_CHUNK_ROWS`` overrides (tests force tiny
    chunks with it)."""
    mesh = mesh or global_mesh()
    shards = mesh.shape[ROWS]
    env = os.environ.get("H2O_TPU_OOC_CHUNK_ROWS")
    if env:
        rows = int(env)
    else:
        headroom = max(budget - hist_bytes, 1 << 20)
        rows = int(max(headroom // 4, 1 << 20) // max(n_features, 1))
    rows = max(shards, (rows // shards) * shards)
    return min(rows, ((padded_rows + shards - 1) // shards) * shards)


def make_chunks(frame, bin_spec, y, w, margin, chunk_rows: int,
                mesh=None, plan=None) -> BinnedChunks:
    """Build the chunked training set from a Frame + resolved columns.

    ``y``/``w``/``margin`` are the full [padded] device columns from
    resolve_xy/_init_margin; they are fetched once and re-sharded per
    chunk. Binned chunks come from `binning.bin_frame_host_chunks`
    (one column on device at a time — the full f32 matrix never
    exists), or from the EFB ``plan``'s bundled host matrix when
    bundling engaged (models/tree/efb.py — the chunks then carry
    BUNDLED slot codes at width Fb). ``H2O_TPU_OOC_RESIDENT=1`` keeps
    the binned chunks device-resident (the bitwise
    streamed-vs-resident test harness)."""
    from .binning import bin_frame_host_chunks

    mesh = mesh or global_mesh()
    sharding = NamedSharding(mesh, P(ROWS))
    if plan is not None:
        from .efb import chunk_plan_host

        bufs = chunk_plan_host(plan, chunk_rows)
    else:
        bufs = bin_frame_host_chunks(frame, bin_spec, chunk_rows)
    n_chunks = len(bufs)
    total = n_chunks * chunk_rows

    def _cols(full, fill):
        a = np.asarray(full)
        out = np.full(total, fill, dtype=np.float32)
        out[: a.shape[0]] = a
        return [jax.device_put(out[c * chunk_rows:(c + 1) * chunk_rows],
                               sharding) for c in range(n_chunks)]

    streamed = os.environ.get("H2O_TPU_OOC_RESIDENT", "0") != "1"
    if not streamed:
        bufs = [jax.device_put(b, sharding) for b in bufs]
    return BinnedChunks(binned=bufs, y=_cols(y, 0.0), w=_cols(w, 0.0),
                        margin=_cols(margin, 0.0),
                        chunk_rows=chunk_rows,
                        padded_rows=np.asarray(y).shape[0],
                        streamed=streamed)


def _stream(chunks: BinnedChunks, mesh):
    """Yield device binned chunks with one-ahead prefetch: the
    (asynchronous) ``device_put`` of chunk c+1 is issued before chunk c
    is consumed, double-buffering host→device transfer against the
    histogram build. Resident chunks pass through untouched.

    Each streamed pass reports its upload/compute split to the fleet
    telemetry registry (``ooc_stream_account``): time blocked inside
    ``device_put`` vs time the CONSUMER held the generator suspended —
    the overlap-efficiency gauge (compute/(compute+upload) → 1.0 when
    every upload hides under the histogram build) the SCALING docs
    previously estimated by hand. The timestamps are host clock reads
    around calls already on this path — no extra device syncs."""
    if not chunks.streamed:
        yield from chunks.binned
        return
    import time

    sharding = NamedSharding(mesh, P(ROWS))
    upload_s = compute_s = 0.0
    t0 = time.monotonic()
    t = t0
    nxt = jax.device_put(chunks.binned[0], sharding)
    upload_s += time.monotonic() - t
    for c in range(chunks.n_chunks):
        cur = nxt
        if c + 1 < chunks.n_chunks:
            t = time.monotonic()
            nxt = jax.device_put(chunks.binned[c + 1], sharding)
            upload_s += time.monotonic() - t
        t = time.monotonic()
        yield cur
        compute_s += time.monotonic() - t
    telemetry.ooc_stream_account(upload_s, compute_s,
                                 time.monotonic() - t0)


# ---------------------------------------------------------------------------
# Per-chunk jitted programs
# ---------------------------------------------------------------------------

def _shard_hist(binned, rel, g, h, w, n_nodes, p: TreeParams, mesh):
    def body(b, r, g_, h_, w_):
        hh = _build_histogram_op(b, r, g_, h_, w_, n_nodes, p.n_bins,
                                 impl=p.hist_impl, unit_hess=p.unit_hess)
        return lax.psum(hh, ROWS)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(ROWS),) * 5, out_specs=P(),
        check_vma=_resolve_impl(p.hist_impl) == "segment")
    return fn(binned, rel, g, h, w)


@functools.partial(jax.jit, static_argnums=(3,))
def _chunk_grads_jit(margin, y, w, bp: BoostParams):
    """Per-chunk (g, h) for one boosting round. No row sampling here:
    sample_rate < 1 is OOC-ineligible (a per-chunk keep-draw would tie
    the model to the chunk grid — models/gbm.BoostPlan.ooc_chunk)."""
    return _boost_grad_hess(bp, margin, y, w)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _chunk_root_hist_jit(binned, g, h, w, rel0, n_bins_full: bool,
                         p: TreeParams, mesh):
    """Level-0 histogram for one chunk: full bins (tree root), or a
    single zero bin (the depth-0 stump's root totals)."""
    if n_bins_full:
        return _shard_hist(binned, rel0, g, h, w, 1, p, mesh)
    zero_bin = jnp.zeros((binned.shape[0], 1), dtype=binned.dtype)
    p1 = p._replace(n_bins=1)
    return _shard_hist(zero_bin, rel0, g, h, w, 1, p1, mesh)


def _descend(binned, rel, absn, feat, bin_, nal, can, d: int,
             n_bins: int, efb=None):
    """Move every row from level ``d`` to ``d+1`` given level-``d``
    splits — the exact row-walk of core._grow_tree_shard (the row's
    bin comes from the shared core.row_orig_bins: a select over the
    chunk's columns, bundle slots decoded through its LUTs)."""
    live = rel >= 0
    safe_rel = jnp.where(live, rel, 0)
    f = feat[safe_rel]
    b = bin_[safe_rel]
    nl = nal[safe_rel]
    rowbin = row_orig_bins(binned, f, efb)
    is_na = rowbin == n_bins - 1
    go_right = jnp.where(is_na, ~nl, rowbin > b)
    child = 2 * rel + go_right.astype(jnp.int32)
    moved = live & can[safe_rel]
    rel = jnp.where(moved, child, -1)
    absn = jnp.where(moved, (2 ** (d + 1) - 1) + child, absn)
    return rel, absn


@functools.partial(jax.jit, static_argnums=(10, 11, 12))
def _chunk_desc_hist_jit(binned, rel, absn, g, h, w, feat, bin_, nal,
                         can, d: int, p: TreeParams, mesh, efb=None):
    """ONE streamed pass of a chunk for level d+1: descend the rows
    from level d's splits, then build the LEFT-child histogram (sibling
    subtraction happens after cross-chunk accumulation). Fusing the
    descent into the histogram pass is what keeps the stream at one
    read of the binned chunk per level."""
    rel, absn = _descend(binned, rel, absn, feat, bin_, nal, can, d,
                         p.n_bins, efb)
    left_rel = jnp.where((rel >= 0) & (rel % 2 == 0), rel // 2, -1)
    hist_l = _shard_hist(binned, left_rel, g, h, w, 2 ** d, p, mesh)
    return rel, absn, hist_l


_add_jit = jax.jit(jnp.add)
_expand_unit_hess_jit = jax.jit(_expand_unit_hess)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _level_logic_jit(hist_l2, hist_prev, can_prev, col_key,
                     p: TreeParams, d: int, efb=None):
    """Sibling subtraction + split finding for level d >= 1 — the same
    math core._grow_tree_shard runs inside the fused scan."""
    if p.unit_hess:
        hist_l2 = _expand_unit_hess(hist_l2)
    parent = jnp.where(can_prev[:, None, None, None], hist_prev, 0.0)
    hist_l = jnp.where(can_prev[:, None, None, None], hist_l2, 0.0)
    hist_r = parent - hist_l
    n_nodes = 2 ** d
    F = hist_l.shape[1]
    hist = jnp.stack([hist_l, hist_r], axis=1).reshape(
        n_nodes, F, p.n_bins, 3)
    return hist, _splits_with_mask(hist, col_key, p, d, efb)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _root_logic_jit(hist, col_key, p: TreeParams, d: int, efb=None):
    if p.unit_hess:
        hist = _expand_unit_hess(hist)
    return hist, _splits_with_mask(hist, col_key, p, d, efb)


def _splits_with_mask(hist, col_key, p: TreeParams, d: int, efb=None):
    n_nodes = hist.shape[0]
    col_mask, key = col_key
    F = col_mask.shape[0]        # ORIGINAL feature count under EFB
    feat_ok = jnp.broadcast_to(col_mask[None, :], (n_nodes, F))
    if p.mtries > 0 and p.mtries < F:
        # same per-node draw as core (key folded with the level)
        r = jax.random.uniform(jax.random.fold_in(key, d), (n_nodes, F))
        r = jnp.where(feat_ok, r, jnp.inf)
        kth = jnp.sort(r, axis=1)[:, p.mtries - 1: p.mtries]
        feat_ok = feat_ok & (r <= kth)
    # the stream takes no set splits (BoostPlan.validate refuses them
    # before it engages): the finder's tenth value, `left_bins`, is None
    return _find_splits(hist, p, feat_ok, efb)[:9]


@functools.partial(jax.jit, static_argnums=(3,))
def _final_leaves_jit(can_prev, left_prev, right_prev, p: TreeParams):
    """Final-level leaf values/covers from the previous level's chosen
    split side stats — zero extra row passes, like the fused core."""
    n_nodes = can_prev.shape[0] * 2
    tot = jnp.where(can_prev[:, None, None],
                    jnp.stack([left_prev, right_prev], axis=1),
                    0.0).reshape(n_nodes, 3)
    return _leaf_value(tot[:, 0], tot[:, 1], p), tot[:, 2]


@functools.partial(jax.jit, static_argnums=(9, 10))
def _chunk_finish_jit(binned, rel, absn, margin, feat, bin_, nal, can,
                      value_scaled, d: int, p: TreeParams, efb=None):
    """Last streamed pass of a tree: descend the final level's rows and
    fold the (already learn-rate-scaled) leaf values into the margin."""
    rel, absn = _descend(binned, rel, absn, feat, bin_, nal, can, d,
                         p.n_bins, efb)
    margin = margin + value_scaled[absn]
    return rel, absn, margin


# ---------------------------------------------------------------------------
# GOSS per-chunk programs (models/tree/core.py "GOSS" — the selection
# rule is a pure function of exactly-associative GLOBAL stats plus a
# per-row hash, so the chunk grid and the in-HBM mesh layout pick the
# SAME rows at one seed; docs/SCALING.md "Gradient-based sampling")
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2,))
def _chunk_goss_max_jit(g, w, mesh):
    """Replicated per-chunk max |g| over live rows (pmax over shards;
    the cross-chunk max is exact whatever the chunk order)."""
    def body(g_, w_):
        return lax.pmax(jnp.max(goss_rank_stat(g_, w_)), ROWS)

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(ROWS), P(ROWS)),
                       out_specs=P())
    return fn(g, w)


@functools.partial(jax.jit, static_argnums=(3,))
def _chunk_goss_counts_jit(g, w, m, mesh):
    """Replicated per-chunk int32 |g|-bin counts + live count (int
    sums are exactly associative — cross-chunk adds are order-free)."""
    def body(g_, w_, m_):
        absg = goss_rank_stat(g_, w_)
        counts, nlive = goss_local_counts(absg, w_ > 0, m_)
        return lax.psum(counts, ROWS), lax.psum(nlive, ROWS)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(ROWS), P(ROWS), P()),
                       out_specs=(P(), P()))
    return fn(g, w, m)


@functools.partial(jax.jit, static_argnums=(2,))
def _goss_threshold_jit(counts, total, a: float):
    return goss_threshold(counts, total, a)


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _chunk_goss_compact_jit(binned, g, h, w, m, T, frac, kg, row0,
                            cap_local: int, bp: BoostParams, mesh):
    """ONE streamed read of a binned chunk per round: per-row GOSS
    factor from the global stats + the (round key, global row id)
    hash, then per-shard static-cap compaction — the compacted buffers
    stay DEVICE-resident for every level of this round's tree, so the
    stream pays one upload per ROUND instead of one per level."""
    def body(bc, g_, h_, w_, m_, T_, f_, kg_, r0_):
        rows_local = w_.shape[0]
        row_ids = (r0_ + lax.axis_index(ROWS) * rows_local +
                   jnp.arange(rows_local, dtype=jnp.int32))
        absg = goss_rank_stat(g_, w_)
        factor = goss_row_factor(absg, w_ > 0, m_, T_, f_, kg_,
                                 row_ids, bp.goss_a, bp.goss_b)
        bC, gC, hC, wC, dropped = goss_compact(bc, g_, h_,
                                               w_ * factor, cap_local)
        return bC, gC, hC, wC, lax.psum(dropped, ROWS)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(ROWS),) * 4 + (P(),) * 5,
                       out_specs=(P(ROWS),) * 4 + (P(),))
    return fn(binned, g, h, w, m, T, frac, kg, row0)


@functools.partial(jax.jit, static_argnums=(3,))
def _chunk_goss_margin_jit(binned, margin, tree: Tree, p: TreeParams,
                           efb=None):
    """Full re-descent margin update for one chunk: the sampled grow
    only walked the compacted rows, so every row re-descends the grown
    tree (shared core.descend_tree — split semantics cannot drift).
    tree.value is already learn-rate-scaled."""
    node = descend_tree(tree, binned, p.max_depth, p.n_bins, efb)
    return margin + tree.value[node]


_max_jit = jax.jit(jnp.maximum)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

def _grow_tree_chunked(chunks: BinnedChunks, gs, hs, wts, col_key,
                       p: TreeParams, mesh, efb=None):
    """Grow one tree over the chunk stream. Returns (Tree of host
    arrays, per-chunk final abs leaf nodes) — margin update is the
    caller's (it owns the learn-rate scaling)."""
    C = chunks.n_chunks
    N = 2 ** (p.max_depth + 1) - 1
    sf = np.full(N, -1, dtype=np.int32)
    sb = np.zeros(N, dtype=np.int32)
    nl = np.zeros(N, dtype=bool)
    isp = np.zeros(N, dtype=bool)
    val = np.zeros(N, dtype=np.float32)
    gn = np.zeros(N, dtype=np.float32)
    cov = np.zeros(N, dtype=np.float32)

    zeros = jnp.zeros(chunks.chunk_rows, dtype=jnp.int32)
    rel = [zeros] * C
    absn = [zeros] * C
    hist_prev = can_prev = left_prev = right_prev = None
    feat_d = bin_d = nal_d = can_d = None

    for d in range(p.max_depth + 1):
        n_nodes = 2 ** d
        off = n_nodes - 1
        if d == p.max_depth:
            if d == 0:
                # depth-0 stump: root totals via a single-bin pass
                tot = None
                for ci, bc in enumerate(_stream(chunks, mesh)):
                    t = _chunk_root_hist_jit(bc, gs[ci], hs[ci],
                                             wts[ci], rel[ci], False,
                                             p, mesh)
                    tot = t if tot is None else _add_jit(tot, t)
                if p.unit_hess:
                    # jitted: an eager op over the committed
                    # replicated total is the XLA:CPU rendezvous flake
                    tot = _expand_unit_hess_jit(tot)
                t3 = np.asarray(tot)[:, 0, 0, :]
                vals_np = np.asarray(
                    _leaf_value(jnp.asarray(t3[:, 0]),
                                jnp.asarray(t3[:, 1]), p))
                covs_np = t3[:, 2]
            else:
                vals_l, covs_l = _final_leaves_jit(
                    can_prev, left_prev, right_prev, p)
                vals_np, covs_np = np.asarray(vals_l), np.asarray(covs_l)
            idx = off + np.arange(n_nodes)
            val[idx] = vals_np
            cov[idx] = covs_np
            break
        # phase spans (h2o_train_phase_seconds + /3/Timeline): the
        # per-level chunk-accumulated histogram build vs the split
        # search — the level-by-level attribution behind any ooc
        # wall-clock claim (host-observable on this path because each
        # level is a host loop over chunk programs)
        if d == 0:
            hist2 = None
            with telemetry.phase_span("level_hist", kind="enqueue",
                                      depth=d):
                for ci, bc in enumerate(_stream(chunks, mesh)):
                    hc = _chunk_root_hist_jit(bc, gs[ci], hs[ci],
                                              wts[ci], rel[ci], True,
                                              p, mesh)
                    hist2 = hc if hist2 is None \
                        else _add_jit(hist2, hc)
            with telemetry.phase_span("split_find", kind="enqueue",
                                      depth=d):
                hist, found = _root_logic_jit(hist2, col_key, p, d,
                                              efb)
        else:
            hist_l2 = None
            with telemetry.phase_span("level_hist", kind="enqueue",
                                      depth=d):
                for ci, bc in enumerate(_stream(chunks, mesh)):
                    rel[ci], absn[ci], hc = _chunk_desc_hist_jit(
                        bc, rel[ci], absn[ci], gs[ci], hs[ci],
                        wts[ci], feat_d, bin_d, nal_d, can_d, d - 1,
                        p, mesh, efb)
                    hist_l2 = hc if hist_l2 is None \
                        else _add_jit(hist_l2, hc)
            with telemetry.phase_span("split_find", kind="enqueue",
                                      depth=d):
                hist, found = _level_logic_jit(hist_l2, hist_prev,
                                               can_prev, col_key, p,
                                               d, efb)
        (feat_d, bin_d, nal_d, can_d, val_d, gain_d, cov_d,
         left_prev, right_prev) = found
        idx = off + np.arange(n_nodes)
        can_np = np.asarray(can_d)
        sf[idx] = np.where(can_np, np.asarray(feat_d), -1)
        sb[idx] = np.asarray(bin_d)
        nl[idx] = np.asarray(nal_d)
        isp[idx] = can_np
        val[idx] = np.asarray(val_d)
        gn[idx] = np.where(can_np, np.asarray(gain_d), 0.0)
        cov[idx] = np.asarray(cov_d)
        hist_prev, can_prev = hist, can_d

    tree = Tree(sf, sb, nl, isp, val, gn, cov)
    return tree, (feat_d, bin_d, nal_d, can_d), rel, absn


def _goss_round_chunked(chunks: BinnedChunks, gs, hs, wts, kg, col_key,
                        cap_local: int, p: TreeParams, bp: BoostParams,
                        mesh, efb=None):
    """One GOSS boosting round over the chunk stream: global ranking
    stats (device scalars, combined lazily — the host never blocks),
    one compaction stream pass, grow over the device-resident
    compacted chunks, one margin-update stream pass. Returns the
    learn-rate-scaled host Tree + the round's compaction-overflow
    device scalar (goss_compact)."""
    m = None
    for ci in range(chunks.n_chunks):
        mc = _chunk_goss_max_jit(gs[ci], wts[ci], mesh)
        m = mc if m is None else _max_jit(m, mc)
    counts = total = None
    for ci in range(chunks.n_chunks):
        cc, nc = _chunk_goss_counts_jit(gs[ci], wts[ci], m, mesh)
        counts = cc if counts is None else _add_jit(counts, cc)
        total = nc if total is None else _add_jit(total, nc)
    T, frac = _goss_threshold_jit(counts, total, bp.goss_a)
    bufsC, gsC, hsC, wtsC = [], [], [], []
    dropped = None
    for ci, bc in enumerate(_stream(chunks, mesh)):
        bC, gC, hC, wC, dc = _chunk_goss_compact_jit(
            bc, gs[ci], hs[ci], wts[ci], m, T, frac, kg,
            ci * chunks.chunk_rows, cap_local, bp, mesh)
        bufsC.append(bC)
        gsC.append(gC)
        hsC.append(hC)
        wtsC.append(wC)
        dropped = dc if dropped is None else _add_jit(dropped, dc)
    shards = mesh.shape[ROWS]
    comp = BinnedChunks(binned=bufsC, y=[], w=[], margin=[],
                        chunk_rows=cap_local * shards,
                        padded_rows=chunks.padded_rows,
                        streamed=False)
    tree, _, _, _ = _grow_tree_chunked(comp, gsC, hsC, wtsC, col_key,
                                       p, mesh, efb)
    # scale leaves once (f32, same IEEE multiply as the fused core)
    scaled = (tree.value
              * np.float32(bp.learn_rate)).astype(np.float32)
    tree = tree._replace(value=scaled)
    tree_dev = jax.tree.map(jnp.asarray, tree)
    for ci, bc in enumerate(_stream(chunks, mesh)):
        chunks.margin[ci] = _chunk_goss_margin_jit(
            bc, chunks.margin[ci], tree_dev, p, efb)
    return tree, dropped


def boost_trees_chunked(chunks: BinnedChunks, key, n_trees: int,
                        p: TreeParams, bp: BoostParams, mesh=None,
                        efb=None, goss_keys=None):
    """n_trees boosting rounds over the chunk stream.

    Returns (margin [padded_rows] numpy, [Tree] with host arrays,
    goss_dropped int — total GOSS compaction-overflow contributions,
    0 when sampling is off; models/gbm surfaces it as a warning) —
    the margin is reassembled once at the end for final metrics; it
    never leaves the device during boosting (each chunk's slice stays
    a sharded device column).

    GOSS (bp.goss_b > 0) composes with the stream WITHOUT a host
    sync: per round, the global |g| ranking stats combine across
    chunks as device scalars (max + int32 adds — exactly associative,
    so they equal the in-HBM psum bit for bit), one streamed pass
    compacts each chunk's sampled rows into a device-resident buffer,
    every tree level then builds from the compacted buffers (no
    per-level streaming at 1/(a+b)-ish of the rows), and a final
    streamed pass re-descends the full chunks for the margin update —
    2 uploads per round instead of max_depth+2."""
    assert not bp.drf_mode, "OOC mode is pointwise boosting only"
    assert bp.sample_rate >= 1.0 and \
        bp.col_sample_rate_per_tree >= 1.0 and p.mtries <= 0, \
        "OOC requires sample_rate=col_sample_rate_per_tree=1, no " \
        "mtries (gated in models/gbm — streamed keys differ from " \
        "the fused core's)"
    mesh = mesh or global_mesh()
    # col_mask lives in ORIGINAL feature space (chunks.n_features is
    # the BUNDLED width when EFB engaged)
    F = efb.feat_col.shape[0] if efb is not None else chunks.n_features
    trees: list[Tree] = []
    # every stochastic option (sample_rate, col_sample_rate_per_tree,
    # mtries) is gated OFF this path in models/gbm.BoostPlan.ooc_chunk —
    # the key below is plumbed only for _splits_with_mask's signature
    col_mask = jnp.ones(F, dtype=bool)
    goss = bp.goss_b > 0.0
    goss_dropped = None
    if goss:
        if goss_keys is None:
            goss_keys = goss_round_keys(key, n_trees)
        shards = mesh.shape[ROWS]
        cap_local = goss_cap_rows(chunks.chunk_rows // shards,
                                  bp.goss_a, bp.goss_b)
    for t in range(n_trees):
        key, k_tree = jax.random.split(key)
        gs, hs, wts = [], [], []
        for ci in range(chunks.n_chunks):
            g, h = _chunk_grads_jit(
                chunks.margin[ci], chunks.y[ci], chunks.w[ci], bp)
            gs.append(g)
            hs.append(h)
            wts.append(chunks.w[ci])
        if goss:
            tree, dc = _goss_round_chunked(chunks, gs, hs, wts,
                                           goss_keys[t],
                                           (col_mask, k_tree),
                                           cap_local, p, bp, mesh,
                                           efb)
            goss_dropped = dc if goss_dropped is None \
                else _add_jit(goss_dropped, dc)
            trees.append(tree)
            continue
        tree, last_split, rel, absn = _grow_tree_chunked(
            chunks, gs, hs, wts, (col_mask, k_tree), p, mesh, efb)
        # scale leaves once (f32, same IEEE multiply as the fused
        # core's tree._replace(value=lr*value)) and fold into margins
        scaled = (tree.value
                  * np.float32(bp.learn_rate)).astype(np.float32)
        tree = tree._replace(value=scaled)
        value_dev = jnp.asarray(scaled)
        if p.max_depth > 0:
            feat_d, bin_d, nal_d, can_d = last_split
            for ci, bc in enumerate(_stream(chunks, mesh)):
                _, _, chunks.margin[ci] = _chunk_finish_jit(
                    bc, rel[ci], absn[ci], chunks.margin[ci], feat_d,
                    bin_d, nal_d, can_d, value_dev,
                    p.max_depth - 1, p, efb)
        else:
            for ci in range(chunks.n_chunks):
                chunks.margin[ci] = _add_root_jit(chunks.margin[ci],
                                                  value_dev)
        trees.append(tree)
    margin = np.concatenate([np.asarray(m) for m in chunks.margin])
    dropped_total = 0 if goss_dropped is None \
        else int(np.asarray(goss_dropped))
    return margin[: chunks.padded_rows], trees, dropped_total


_add_root_jit = jax.jit(lambda m, v: m + v[0])
