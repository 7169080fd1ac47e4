"""Grouped (per-query) gradients of the ranking objectives: the query
layout and LambdaMART's pairwise gradients over it.

`rank:pairwise` and `rank:ndcg` are distributions of the boost plan
(models/gbm.BoostPlan, mode ``single``): the layout built here is an
operand of `core._boost_jit` and `rank_grad_hess` runs inside its scan,
where `core._round_grad_hess` takes every round's gradients.

THE SEMANTICS (bench/reference/lambdamart_plain.py has them in numpy
float64). A query q holds documents with margins s and labels y. Its
pairs are EVERY (i, j) of q with y_i > y_j: no pair crosses a query, no
query is truncated, no pair is sampled. r_i is the 1-based rank of i in
q by s descending, ties by row order (a stable sort);
maxDCG_q = sum_k (2^y(k) - 1) / log2(1 + k) over the whole list by label
descending. rho_ij = 1 / (1 + exp(s_i - s_j));
w_ij = |2^y_i - 2^y_j| * |1/log2(1 + r_i) - 1/log2(1 + r_j)| / maxDCG_q
for rank:ndcg, 1 for rank:pairwise.
g_i = -sum_{j: y_i > y_j} w_ij rho_ij + sum_{j: y_j > y_i} w_ji rho_ji;
h_i = the same pairs' w rho (1 - rho), summed. A query whose labels are
all equal gives zeros. This is Burges' LambdaMART over all pairs;
XGBoost samples or truncates a query's pairs and normalises by the DCG
at its truncation level, which no reference could be held to.

THE LAYOUT. Queries are sorted into SIZE CLASSES (`CLASS_LENGTHS`: 8,
16, 24, 32, 48, 64, 96, ... — a query of n documents lies in a class of
length under 1.5 n past the first), each class a dense [queries, length]
table of row indices worked in batches of at most `SLOT_BUDGET` pair
slots, so the pair slots computed stay within a small factor of
sum n_q^2 (the pairs that exist; `RankLayout.pairs_real` /
`.pairs_slots`) whatever the longest query. The compiled shapes hang
on the table's (rows, queries, multiset of query sizes) alone: which
query has which size is in the VALUES of the index tables.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ...runtime.mesh import ROWS, global_mesh, replicated, row_sharding

# lengths of the size classes: 8, then every 2^e and 1.5 * 2^e
CLASS_LENGTHS = np.array(
    [8] + [v for e in range(4, 31) for v in (2 ** e, 3 * 2 ** (e - 1))],
    dtype=np.int64)
# most pair slots (queries x length^2) one step of a class's lax.map
# holds: 2^22 slots are 16 MiB a float32 temporary
SLOT_BUDGET = 1 << 22


def grouped(distribution: str) -> bool:
    """This distribution's gradient hangs on a row's QUERY, not on the
    row alone: the one test of what a grouped objective is."""
    return distribution.startswith("rank:")


class RankClass(NamedTuple):
    """One size class: ``nb`` batches of ``B`` queries of at most ``L``
    documents each, in row order within a query."""

    idx: jax.Array       # [nb, B, L] int32 row of each slot (0: empty)
    y: jax.Array         # [nb, B, L] float32 label of each slot
    n: jax.Array         # [nb, B] int32 documents a query (0: no query)
    inv_dcg: jax.Array   # [nb, B] float32 1 / maxDCG (0 where it is 0)


class RankGroups(NamedTuple):
    """The layout as the boost program takes it: the classes,
    replicated, and every row's slot in their concatenation (the one
    past the last: a row of no query, which reads zeros), row-sharded."""

    classes: tuple
    pos: jax.Array       # [padded] int32


class RankLayout(NamedTuple):
    """A job's query layout: the device form and what the host keeps
    for the root span's attributes and the counters."""

    groups: RankGroups
    queries: int
    max_query: int
    pairs_real: int      # sum of n_q^2: the pairs that exist, a round
    pairs_slots: int     # pair slots the classes compute, a round


def _class_shape(n_queries: int, length: int) -> tuple[int, int]:
    """(batches, queries a batch) of a class: the fewest batches the
    slot budget allows, evenly filled."""
    cap = max(1, SLOT_BUDGET // (length * length))
    nb = -(-n_queries // cap)
    return nb, -(-n_queries // nb)


def query_runs(gids: np.ndarray) -> tuple:
    """(order, starts, sizes) of the queries of a group column: one
    pass where every query's rows are contiguous (``order`` None: the
    rows are taken as they lie), one stable sort where they are not.
    Queries come in the order their first row has in ``order``."""
    def runs(g):
        starts = np.concatenate(
            [[0], np.flatnonzero(g[1:] != g[:-1]) + 1]) if len(g) \
            else np.zeros(0, dtype=np.int64)
        return starts, g[starts]

    starts, ids = runs(gids)
    order = None
    if len(ids) > 1 and not np.all(ids[1:] > ids[:-1]) and \
            len(np.unique(ids)) != len(ids):
        order = np.argsort(gids, kind="stable")
        starts, ids = runs(gids[order])
    sizes = np.diff(np.concatenate([starts, [len(gids)]]))
    return order, starts, sizes


def rank_layout(gids: np.ndarray, y: np.ndarray, padded: int,
                mesh=None) -> RankLayout:
    """The layout of a job from its group column and labels (host
    arrays over the frame's rows). A row whose label is missing belongs
    to no query."""
    gids = np.asarray(gids)
    y = np.asarray(y, dtype=np.float32)
    rows = None
    if np.isnan(y).any():
        rows = np.flatnonzero(~np.isnan(y))
    order, starts, sizes = query_runs(gids if rows is None else gids[rows])
    if order is not None:
        rows = order if rows is None else rows[order]
    # rows[k]: the frame row at position k of the query-sorted sequence
    cls = np.searchsorted(CLASS_LENGTHS, sizes)
    pos = np.full(padded, -1, dtype=np.int64)
    classes, offset, slots = [], 0, 0
    for c in np.unique(cls):
        qs = np.flatnonzero(cls == c)
        L = int(CLASS_LENGTHS[c])
        nb, B = _class_shape(len(qs), L)
        k = starts[qs][:, None] + np.arange(L)[None, :]
        valid = np.arange(L)[None, :] < sizes[qs][:, None]
        k = np.where(valid, k, 0)
        row = k if rows is None else rows[k]
        pos[row[valid]] = (offset + np.arange(len(qs))[:, None] * L
                           + np.arange(L)[None, :])[valid]
        yq = np.where(valid, y[row], 0.0)
        # maxDCG over the whole list, labels descending (float64)
        gains = np.sort(np.where(valid, 2.0 ** yq.astype(np.float64) - 1.0,
                                 0.0), axis=1)[:, ::-1]
        dcg = gains @ (1.0 / np.log2(np.arange(2, L + 2)))
        inv = np.where(dcg > 0, 1.0 / np.maximum(dcg, 1e-300), 0.0)

        def batched(a, dtype):
            out = np.zeros((nb * B,) + a.shape[1:], dtype=dtype)
            out[: len(a)] = a
            return out.reshape((nb, B) + a.shape[1:])

        classes.append((batched(np.where(valid, row, 0), np.int32),
                        batched(yq, np.float32),
                        batched(sizes[qs], np.int32),
                        batched(inv, np.float32)))
        offset += nb * B * L
        slots += nb * B * L * L
    pos[pos < 0] = offset              # rows of no query: the zero slot
    mesh = mesh or global_mesh()
    rep = replicated(mesh)
    groups = RankGroups(
        tuple(RankClass(*(jax.device_put(a, rep) for a in c))
              for c in classes),
        jax.device_put(pos.astype(np.int32), row_sharding(mesh)))
    return RankLayout(groups, len(sizes),
                      int(sizes.max()) if len(sizes) else 0,
                      int(np.sum(sizes.astype(np.int64) ** 2)), int(slots))


def groups_specs(groups: RankGroups):
    """The PartitionSpecs of `groups` under the boost program's
    shard_map: the classes replicated, the rows' slots by rows."""
    return RankGroups(tuple(RankClass(P(), P(), P(), P())
                            for _ in groups.classes), P(ROWS))


def groups_abstract(groups: RankGroups):
    """`groups` as ShapeDtypeStructs with its shardings, for a
    lowering ahead of the job."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=a.sharding), groups)


def _ranks(f, valid):
    """int32 [B, L]: the 1-based rank a stable descending sort of ``f``
    gives each document of its query, by counting: one more than the
    query's documents that beat it — a higher value, or the same one
    earlier in row order. (The pair tensor is [B, j, i].)"""
    at = jnp.arange(f.shape[1])
    fj, fi = f[:, :, None], f[:, None, :]
    first = (at[:, None] < at[None, :])[None]
    beats = valid[:, :, None] & ((fj > fi) | ((fj == fi) & first))
    return 1 + jnp.sum(beats, axis=1, dtype=jnp.int32)


def _class_grads(f, y, n, inv_dcg, use_ndcg: bool):
    """(g, h) [B, L] of one batch of a class: ``f`` margins and ``y``
    labels [B, L], ``n`` documents a query [B]. The pair tensor is
    [B, j, i]: the partner j is summed over, the document i stays on
    the minor axis."""
    valid = jnp.arange(f.shape[1])[None, :] < n[:, None]
    fj, fi = f[:, :, None], f[:, None, :]
    vj = valid[:, :, None]
    with jax.named_scope("rank_sort"):
        rank = _ranks(f, valid)
    with jax.named_scope("rank_pairs"):
        s = jnp.sign(y[:, None, :] - y[:, :, None])   # +1: i above j
        # the pair's chance of being the wrong way round, whichever of
        # the two is the higher label
        rho = jax.nn.sigmoid(-s * (fi - fj))
        a = rho
        if use_ndcg:
            gain = 2.0 ** y
            disc = 1.0 / jnp.log2(1.0 + rank.astype(jnp.float32))
            a = a * jnp.abs(gain[:, None, :] - gain[:, :, None]) \
                * jnp.abs(disc[:, None, :] - disc[:, :, None]) \
                * inv_dcg[:, None, None]
        a = jnp.where(vj & valid[:, None, :] & (s != 0), a, 0.0)
        return jnp.sum(-s * a, axis=1), jnp.sum(a * (1.0 - rho), axis=1)


def rank_grad_hess(distribution: str, margin, groups: RankGroups):
    """Per-row (g, h) of a ranking objective at ``margin``, inside the
    boost program's shard_map: ``margin`` and ``groups.pos`` are this
    shard's rows. A query may straddle a shard's edge, so every shard
    gathers the whole margin (4 B a row) and works every query; its
    rows then read their own slots — what eight shards compute is
    bitwise what one does."""
    use_ndcg = distribution == "rank:ndcg"
    full = lax.all_gather(margin, ROWS, tiled=True)
    out = []
    for c in groups.classes:
        g, h = lax.map(
            lambda t: _class_grads(*t, use_ndcg),
            (full[c.idx], c.y, c.n, c.inv_dcg))
        out.append(jnp.stack([g, h], axis=-1).reshape(-1, 2))
    gh = jnp.concatenate(out + [jnp.zeros((1, 2), jnp.float32)])[groups.pos]
    return gh[:, 0], gh[:, 1]


@functools.partial(jax.jit, static_argnums=(2,))
def _ndcg_parts(margin, groups: RankGroups, k: int):
    """Per batch of every class: the sum of DCG@k / ideal DCG@k over
    its queries whose ideal is positive, and how many those are."""
    def one(t):
        f, y, n = t
        valid = jnp.arange(f.shape[1])[None, :] < n[:, None]
        gain = jnp.where(valid, 2.0 ** y - 1.0, 0.0)

        def dcg(rank):
            return jnp.sum(jnp.where(
                rank <= k, gain / jnp.log2(1.0 + rank.astype(jnp.float32)),
                0.0), axis=1)

        got, ideal = dcg(_ranks(f, valid)), dcg(_ranks(y, valid))
        ok = ideal > 0
        return (jnp.sum(jnp.where(ok, got / jnp.where(ok, ideal, 1.0), 0.0)),
                jnp.sum(ok))

    return [lax.map(one, (margin[c.idx], c.y, c.n))
            for c in groups.classes]


def ndcg_at(margin, groups: RankGroups, k: int = 10) -> float:
    """Mean NDCG@k of ``margin`` over the layout's queries (those whose
    ideal DCG is positive), ties by row order: `metrics.ndcg`'s number,
    ranked on the device over the size classes (float32 a query, the
    mean in float64 on the host)."""
    parts = jax.device_get(_ndcg_parts(margin, groups, k))
    total = sum(float(np.sum(t, dtype=np.float64)) for t, _ in parts)
    return total / max(sum(int(np.sum(c)) for _, c in parts), 1)
