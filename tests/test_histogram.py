"""Histogram kernel tests: the Pallas one-hot-matmul implementation
(interpret mode on CPU) must match the segment_sum reference exactly
(SURVEY.md §7 'Pallas histogram kernel quality')."""

import numpy as np
import pytest

import jax.numpy as jnp

from h2o_kubernetes_tpu.ops.histogram import build_histogram


def _random_case(r, F, n_nodes, n_bins, seed, dead_frac=0.2):
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, n_bins, size=(r, F)).astype(np.uint8)
    rel = rng.integers(0, n_nodes, size=r).astype(np.int32)
    rel[rng.random(r) < dead_frac] = -1
    g = rng.normal(size=r).astype(np.float32)
    h = rng.random(r).astype(np.float32)
    w = (rng.random(r) < 0.9).astype(np.float32)
    # dead rows may carry NaN gradients — must not poison sums
    g[rel < 0] = np.nan
    return (jnp.asarray(binned), jnp.asarray(rel), jnp.asarray(g),
            jnp.asarray(h), jnp.asarray(w))


def _class_batch_case(K, rows, F, n_nodes, n_bins, seed):
    """One stored `binned`, K classes' node ids and gradients: the
    shape the multinomial grower vmaps `build_histogram` over."""
    rng = np.random.default_rng(seed)
    binned = jnp.asarray(
        rng.integers(0, n_bins, size=(rows, F)).astype(np.uint8))
    relK = jnp.asarray(np.where(
        rng.random((K, rows)) < 0.85,
        rng.integers(0, n_nodes, size=(K, rows)), -1).astype(np.int32))
    gK = jnp.asarray(rng.normal(size=(K, rows)).astype(np.float32))
    hK = jnp.asarray(rng.random((K, rows)).astype(np.float32))
    w = jnp.asarray((rng.random(rows) < 0.9).astype(np.float32))
    return binned, relK, gK, hK, w


@pytest.mark.parametrize("r,F,n_nodes,n_bins", [
    (300, 4, 1, 16),
    (1000, 3, 4, 64),
    (513, 2, 32, 17),       # odd bin count, rows not tile-aligned
    (128, 5, 8, 32),
])
def test_pallas_matches_segment(r, F, n_nodes, n_bins):
    binned, rel, g, h, w = _random_case(r, F, n_nodes, n_bins, seed=r)
    ref = build_histogram(binned, rel, g, h, w, n_nodes, n_bins,
                          impl="segment")
    got = build_histogram(binned, rel, g, h, w, n_nodes, n_bins,
                          impl="pallas")
    assert got.shape == (n_nodes, F, n_bins, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# levels past the hi-block cap, at CPU sizes: (rows, F, nodes, bins,
# unit_hess). The cap is set small so these need several hi blocks.
_DEEP = {
    "pow2": (1000, 3, 32, 64, False),
    "full_lanes": (777, 2, 16, 128, False),
    "odd_bins_17": (513, 2, 64, 17, False),     # n_hi 9: junk slots
    "odd_bins_20": (1300, 3, 96, 20, False),    # n_hi 15, 2 row tiles
    "two_channels": (900, 4, 64, 20, True),
    "one_row_tile_many_nodes": (128, 5, 256, 32, True),
}


@pytest.mark.parametrize("cap", [1, 2, 8])
@pytest.mark.parametrize("case", sorted(_DEEP))
def test_hi_blocked_level_matches_segment_and_one_block(
        monkeypatch, case, cap):
    """A level with more hi slots than one grid step holds is served in
    blocks of hi slots. It must (a) match `segment` to 1e-5 and (b) be
    BITWISE what the same level gives served in one block: every cell
    sums the same products over the same row tiles in the same order.
    Rows are not tile-aligned, ~20% are dead and carry NaN gradients,
    and odd bin counts leave the last block junk slots to slice off."""
    import h2o_kubernetes_tpu.ops.histogram as H

    r, F, n_nodes, n_bins, unit = _DEEP[case]
    binned, rel, g, h, w = _random_case(r, F, n_nodes, n_bins, seed=r)
    if unit:
        h = jnp.ones_like(w)
    args = (binned, rel, g, h, w, n_nodes, n_bins)
    ref = build_histogram(*args, impl="segment", unit_hess=unit)
    assert H._hi_blocks(n_nodes * n_bins)[0] == 1
    one = build_histogram(*args, impl="pallas", unit_hess=unit)
    monkeypatch.setattr(H, "_FACT_MAX_NHI", cap)
    n_ht, ht = H._hi_blocks(n_nodes * n_bins)
    assert n_ht > 1 and ht <= cap
    got = build_histogram(*args, impl="pallas", unit_hess=unit)
    assert got.shape == (n_nodes, F, n_bins, 2 if unit else 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))


def _vmapped(binned, w, n_nodes, n_bins, impl, unit=False, in_axes=0):
    """`build_histogram` under `vmap` over the class axis, as the
    K-class grower calls it; ``binned`` stored once unless
    ``in_axes`` batches it too."""
    import jax

    if in_axes == 0:
        return jax.vmap(lambda rel, g, h: build_histogram(
            binned, rel, g, h, w, n_nodes, n_bins, impl, unit_hess=unit))
    return jax.vmap(lambda b, rel, g, h: build_histogram(
        b, rel, g, h, w, n_nodes, n_bins, impl, unit_hess=unit))


def _pallas_calls(fn, *args):
    import jax

    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call[")


@pytest.mark.parametrize("cap", [1, 2, 8])
def test_hi_blocked_class_batch_stores_binned_once(monkeypatch, cap):
    """The class batch (vmap over K = 3, `binned` unbatched) past the
    cap: ONE `pallas_call` over the one stored copy of `binned`, with
    `[K, rows]` node ids beside it. It matches `segment` to 1e-5 and is
    BITWISE the batch served within the cap."""
    import h2o_kubernetes_tpu.ops.histogram as H

    K, rows, F, n_nodes, n_bins = 3, 1500, 4, 5, 128
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, F, n_nodes, n_bins, seed=23)

    def build(impl):
        return _vmapped(binned, w, n_nodes, n_bins, impl)(relK, gK, hK)

    one = build("pallas")
    monkeypatch.setattr(H, "_FACT_MAX_NHI", cap)
    assert H._hi_blocks(K * n_nodes * n_bins)[0] > 1
    calls = []
    real = H._hist_pallas

    def spy(binned_f, rel_f, vals_f, *a):
        calls.append((binned_f.shape, rel_f.shape, vals_f.shape))
        return real(binned_f, rel_f, vals_f, *a)

    monkeypatch.setattr(H, "_hist_pallas", spy)
    got = build("pallas")
    # the batching rule's call (after `custom_vmap`'s own trace of the
    # unbatched one): the one copy of binned, as it is stored
    assert calls[-1] == ((rows, F), (K, rows), (K, rows, 3))
    assert all(c[0] == (rows, F) for c in calls)
    assert _pallas_calls(_vmapped(binned, w, n_nodes, n_bins, "pallas"),
                         relK, gK, hK) == 1
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(build("segment")),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))


@pytest.mark.parametrize("n_nodes", [1, 4, 16, 32])
@pytest.mark.parametrize("C", [2, 3])
@pytest.mark.parametrize("K", [2, 3, 7])
def test_class_batch_equals_a_build_a_class(K, C, n_nodes):
    """ISSUE 39: inside the one call a class's rows meet that class's
    hi slots only. The batched build — `binned` stored once, and
    batched — equals K per-class builds: to 1e-5 of `segment`, the
    counts (the `w` channel: 0/1 weights) exactly, and BITWISE the
    unbatched kernel's wherever the K stacked classes keep the row tile
    one class would take. Rows are no multiple of either tile, 15% are
    dead; a node of whole 128-lane rows (256 and 128 bins: one lo
    one-hot for the K classes) at three depths, and 20 bins (a class's
    lo follows its own nodes: a class a call) at the fourth."""
    import jax

    import h2o_kubernetes_tpu.ops.histogram as H

    n_bins = {1: 256, 4: 128, 16: 256, 32: 20}[n_nodes]
    rows = 8300 if n_nodes == 16 else 1300
    unit = C == 2
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, 3, n_nodes, n_bins, seed=K + C + n_nodes)
    if unit:
        hK = jnp.ones_like(hK)
    got = _vmapped(binned, w, n_nodes, n_bins, "pallas", unit)(
        relK, gK, hK)
    assert got.shape == (K, n_nodes, 3, n_bins, C)
    # every class its own codes (here: the same ones, K times)
    own = _vmapped(binned, w, n_nodes, n_bins, "pallas", unit,
                   in_axes=(0, 0, 0, 0))(
        jnp.broadcast_to(binned, (K,) + binned.shape), relK, gK, hK)
    ht = H._hi_blocks(n_nodes * n_bins)[1]
    same_tile = n_bins % 128 != 0 or \
        H._fact_row_tile(K * ht, rows) == H._fact_row_tile(ht, rows)
    for k in range(K):
        args = (binned, relK[k], gK[k], hK[k], w, n_nodes, n_bins)
        want = build_histogram(*args, "segment", unit_hess=unit)
        one = build_histogram(*args, "pallas", unit_hess=unit)
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got[k, ..., -1]),
                                      np.asarray(want[..., -1]))
        np.testing.assert_array_equal(np.asarray(own[k]),
                                      np.asarray(one))
        if same_tile:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(one))


# (cap, class blocks, classes a block, hi blocks a class) for K = 3
# classes of 5 hi slots each (5 nodes x 128 bins)
_CLASS_CAPS = [(16, 1, 3, 1),       # the three stacked in one block
               (10, 2, 2, 1),       # merged slots pass the cap: blocks
                                    # of whole classes, one dead class
               (8, 3, 1, 1),        # a class a block
               (2, 3, 1, 3)]        # one class alone passes it: a class
                                    # a block, served in hi blocks


@pytest.mark.parametrize("cap,n_cb,kb,n_ht", _CLASS_CAPS)
@pytest.mark.parametrize("C", [2, 3])
def test_class_batch_blocks_by_whole_classes_then_by_hi_blocks(
        monkeypatch, C, cap, n_cb, kb, n_ht):
    """Past `_FACT_MAX_NHI` the batch is blocked by whole classes
    first, by hi blocks inside a class only where one class alone
    passes the cap; whatever the blocking the sums are bitwise the
    unblocked batch's and the counts exact."""
    import h2o_kubernetes_tpu.ops.histogram as H

    K, rows, F, n_nodes, n_bins = 3, 1500, 4, 5, 128
    unit = C == 2
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, F, n_nodes, n_bins, seed=29)
    build = _vmapped(binned, w, n_nodes, n_bins, "pallas", unit)
    one = build(relK, gK, hK)
    monkeypatch.setattr(H, "_FACT_MAX_NHI", cap)
    blocks = H._hi_blocks(n_nodes * n_bins)
    assert blocks[0] == n_ht
    assert H._class_blocks(K, blocks[1]) == (n_cb, kb)
    got = build(relK, gK, hK)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(one))
    want = _vmapped(binned, w, n_nodes, n_bins, "segment", unit)(
        relK, gK, hK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[..., -1]),
                                  np.asarray(want[..., -1]))
    names = _kernel_names_for_tpu(build, relK, gK, hK)
    assert names == ["hist_fact" if n_ht == 1 else "hist_blocked"]


@pytest.mark.parametrize("K,ht,want", [
    (7, 2, (1, 7)), (7, 32, (1, 7)),    # `xgb-covtype.train`: one block
    (9, 2, (2, 5)),                     # evenly filled, one dead class
    (32, 2, (4, 8)), (128, 2, (16, 8)),     # the cap on classes binds,
    (32, 64, (8, 4)),                       # the cap on stacked slots,
    (3, 300, (3, 1)),                       # a class alone passes it
])
def test_class_blocks_hold_at_most_eight_classes(K, ht, want):
    """A block's VMEM follows its classes as well as its stacked slots
    (the values block, the ids, the mantissa stacks: classes x row
    tile whatever ht is), so both are capped."""
    import h2o_kubernetes_tpu.ops.histogram as H

    assert H._class_blocks(K, ht) == want
    assert want[1] <= H._CLASS_BLOCK_MAX
    assert want[1] * ht <= max(ht, H._FACT_MAX_NHI)


def test_class_batch_past_eight_classes_goes_in_class_blocks():
    """Nine class trees at the root: two blocks of five classes on the
    grid's class axis (the tenth dead), still ONE call, each class
    bitwise its own build."""
    import jax

    K, rows, F, n_nodes, n_bins = 9, 1300, 3, 1, 256
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, F, n_nodes, n_bins, seed=41)
    build = _vmapped(binned, w, n_nodes, n_bins, "pallas")
    (call,) = [e for e in jax.make_jaxpr(build)(relK, gK, hK).eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (1, 1, 2, 2)
    got = build(relK, gK, hK)
    for k in range(K):
        np.testing.assert_array_equal(
            np.asarray(got[k]), np.asarray(build_histogram(
                binned, relK[k], gK[k], hK[k], w, n_nodes, n_bins,
                "pallas")))


def test_class_batch_of_part_rows_goes_a_class_a_call():
    """A node that takes part of a 128-lane row (a K-class forest's 64
    bins): a class's lo one-hot follows its own nodes, nothing of a
    column's work is shared, so the batch is the UNBATCHED kernel under
    `lax.map` — one `pallas_call` in the program, `binned` stored once
    and never stacked K times."""
    import jax

    K, rows, F, n_nodes, n_bins = 3, 2048, 4, 4, 64
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, F, n_nodes, n_bins, seed=43)
    build = _vmapped(binned, w, n_nodes, n_bins, "pallas", unit=True)
    jaxpr = jax.make_jaxpr(build)(relK, gK, jnp.ones_like(hK))
    assert [e.primitive.name for e in jaxpr.eqns].count("pallas_call") == 0
    (loop,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    (call,) = [e for e in loop.params["jaxpr"].eqns
               if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    assert gm.grid == (1, 1, 1, 2)
    assert [tuple(getattr(d, "block_size", None) for d in bm.block_shape)
            for bm in gm.block_mappings] == [
        (F, 1, 1, 1024), (1024,), (1024, 2), (1, 1, F, 2 * 2, 128)]
    assert f"[{K},{rows},{F}]" not in str(jaxpr).replace(" ", "")

def test_class_batch_under_the_mesh_matches_one_shard(mesh8):
    """Row-sharded (the per-level psum sees the same
    `[K, n_nodes, F, B, C]` array): the batched Pallas build under
    shard_map equals `segment` over all rows."""
    import jax
    from jax.sharding import PartitionSpec as P

    from h2o_kubernetes_tpu.runtime.mesh import ROWS

    K, n_nodes, n_bins = 3, 4, 256
    binned, relK, gK, hK, w = _class_batch_case(
        K, 8 * 300, 5, n_nodes, n_bins, seed=31)

    def shard(b, r, g, h, ww):
        return jax.lax.psum(_vmapped(b, ww, n_nodes, n_bins, "pallas")(
            r, g, h), ROWS)

    cls = P(None, ROWS)
    got = jax.jit(jax.shard_map(
        shard, mesh=mesh8, in_specs=(P(ROWS), cls, cls, cls, P(ROWS)),
        out_specs=P(), check_vma=False))(binned, relK, gK, hK, w)
    want = _vmapped(binned, w, n_nodes, n_bins, "segment")(relK, gK, hK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _ordered_case(rows, F, n_nodes, n_bins, seed, dead=0.368,
                  empty_block=None, dead_inside=0.0):
    """A level's rows ordered by hi block as a tree orders them: the
    live rows (``rel`` >= 0, ``w`` > 0) by node, ``dead`` of them last;
    ``dead_inside`` of the ordered live rows then lose their weight or
    their node where they stand; ``empty_block``: no row in that hi
    block. Returns the level's operands and the blocks' first rows."""
    import h2o_kubernetes_tpu.ops.histogram as H

    n_ht, per = H.node_blocks(n_nodes, n_bins)
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, n_bins, size=(rows, F)).astype(
        np.uint16 if n_bins > 256 else np.uint8)
    rel = rng.integers(0, n_nodes, size=rows).astype(np.int32)
    if empty_block is not None:
        rel = np.where(rel // per == empty_block,
                       (rel + per) % n_nodes, rel).astype(np.int32)
    live = rng.random(rows) >= dead
    rel = np.sort(np.where(live, rel, n_nodes))
    rel[rel == n_nodes] = -1
    w = (rel >= 0).astype(np.float32)
    g = rng.normal(size=rows).astype(np.float32)
    h = rng.random(rows).astype(np.float32)
    starts = np.searchsorted(np.where(rel >= 0, rel // per, n_ht),
                             np.arange(n_ht + 1)).astype(np.int32)
    inside = (rng.random(rows) < dead_inside) & (rel >= 0)
    w[inside & (rng.random(rows) < 0.5)] = 0.0
    rel[inside & (w > 0)] = -1
    g[rel < 0] = np.nan
    return [jnp.asarray(a) for a in (binned, rel, g, h, w)], \
        jnp.asarray(starts)


# a level past one hi block over rows ordered by block: (rows, F, nodes,
# bins, unit_hess, what the order holds)
_COMPACT = {
    "2_blocks_512_bins": (3000, 3, 128, 512, True, {}),
    "4_blocks_512_bins": (2500, 2, 256, 512, False, {}),
    "16_blocks_512_bins": (5000, 2, 1024, 512, True, {}),
    "2_blocks_64_bins": (3000, 3, 1024, 64, True, {}),
    "4_blocks_64_bins": (2100, 2, 2048, 64, False, {}),
    "16_blocks_64_bins": (4500, 2, 8192, 64, True, {}),
    "an_empty_block": (3000, 2, 512, 512, True, dict(empty_block=2)),
    "the_first_block_empty": (2500, 2, 256, 512, True,
                              dict(empty_block=0)),
    "tiles_of_dead_rows_only": (6000, 2, 256, 512, True,
                                dict(dead=0.7)),
    "dead_rows_between_live_ones": (3000, 3, 256, 512, False,
                                    dict(dead_inside=0.3)),
    "every_row_dead": (2000, 2, 128, 512, True, dict(dead=1.0)),
}


@pytest.mark.parametrize("case", sorted(_COMPACT))
def test_compacted_call_matches_segment_and_the_blocked_call(case):
    """The level's rows ordered by hi block as the grower orders them,
    served each block over its own row tiles (`build_histogram`'s
    ``starts``): to 1e-5 of `segment` and BITWISE the blocked call over
    the same rows — a tile a block skips holds none of its rows. At 2,
    4 and 16 blocks of 512 and of 64 bins (a node of part of a 128-lane
    row), with an empty block, tiles of dead rows alone, and rows dead
    or of weight 0 between the live ones."""
    import h2o_kubernetes_tpu.ops.histogram as H

    rows, F, n_nodes, n_bins, unit, kw = _COMPACT[case]
    (binned, rel, g, h, w), starts = _ordered_case(
        rows, F, n_nodes, n_bins, seed=rows + n_nodes, **kw)
    if unit:
        h = jnp.ones_like(w)
    assert starts.shape == (H.node_blocks(n_nodes, n_bins)[0] + 1,)
    if "empty_block" in kw:
        b = kw["empty_block"]
        assert starts[b] == starts[b + 1]
    args = (binned, rel, g, h, w, n_nodes, n_bins)
    want = build_histogram(*args, impl="segment", unit_hess=unit)
    blocked = build_histogram(*args, impl="pallas", unit_hess=unit)
    got = build_histogram(*args, impl="pallas", unit_hess=unit,
                          starts=starts)
    assert got.shape == (n_nodes, F, n_bins, 2 if unit else 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(blocked))
    # the segment sum takes the same order as any other
    np.testing.assert_array_equal(
        np.asarray(build_histogram(*args, impl="segment", unit_hess=unit,
                                   starts=starts)), np.asarray(want))


@pytest.mark.parametrize("starts,T,tiles,want", [
    # four blocks over 5 tiles of 4 rows: block 1 shares tile 1 with
    # block 0, block 2 is empty, rows from 15 on are dead
    ((0, 6, 9, 9, 15), 4, 5,
     [(0, 0, 1), (0, 1, 1), (1, 1, 1), (1, 2, 1), (2, 2, 0), (3, 2, 1),
      (3, 3, 1), (3, 3, 0)]),
    # every row dead: each block one step that adds nothing
    ((0, 0, 0), 4, 3, [(0, 0, 0), (1, 0, 0), (1, 0, 0), (1, 0, 0)]),
    # one block over every tile, the second past the last row
    ((0, 12, 12), 4, 3, [(0, 0, 1), (0, 1, 1), (0, 2, 1), (1, 2, 0)]),
])
def test_compact_steps_visit_each_block_over_its_own_tiles(starts, T,
                                                           tiles, want):
    """(hi block, row tile, adds) of each grid step: blocks in order,
    a tile two blocks share visited by both, an empty block one step
    that adds nothing, padding that repeats the last step's tile."""
    import h2o_kubernetes_tpu.ops.histogram as H

    block, tile = H._compact_steps(jnp.asarray(starts, jnp.int32), T,
                                   tiles)
    assert block.shape == tile.shape == (tiles + len(starts) - 2,)
    assert [(int(b) >> 1, int(t), int(b) & 1)
            for b, t in zip(block, tile)] == want


def test_compacted_level_under_the_mesh_matches_one_shard(mesh8):
    """Row-sharded: each shard orders its OWN rows by node block
    (`core._order_rows`) and serves its blocks over its own tiles; the
    psum of the shards' levels equals `segment` over all rows."""
    import jax
    from jax.sharding import PartitionSpec as P

    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.runtime.mesh import ROWS

    n_nodes, n_bins, depth = 256, 512, 6
    binned, rel, g, h, w = _random_case(8 * 1100, 3, n_nodes, n_bins,
                                        seed=42)
    binned = binned.astype(jnp.uint16)
    n_ht = 4                                # blocks of 64 nodes
    abs_node = rel + 7

    def shard(b, r, gg, hh, ww, a):
        # order by the node's ancestor at depth 6 (the nodes here are a
        # depth-8 level's), as the grower orders by its depth-s node
        order, bounds, (b, r, gg, hh, ww, a) = core._order_rows(
            depth, jnp.where(r >= 0, r >> 2, -1), ww,
            (b, r, gg, hh, ww, a))
        starts = bounds[::2 ** depth // n_ht]
        hist = build_histogram(b, r, gg, hh, ww, n_nodes, n_bins,
                               "pallas", starts=starts)
        back = jnp.zeros_like(a).at[order].set(a, unique_indices=True)
        return jax.lax.psum(hist, ROWS), back

    got, back = jax.jit(jax.shard_map(
        shard, mesh=mesh8, in_specs=(P(ROWS),) * 6,
        out_specs=(P(), P(ROWS)), check_vma=False))(
        binned, rel, g, h, w, abs_node)
    want = build_histogram(binned, rel, g, h, w, n_nodes, n_bins,
                           "segment")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(abs_node))


def test_compacted_level_lowers_for_tpu_as_one_hist_blocked_call():
    """AOT-lower a level over ordered rows for a TPU target: ONE
    `pallas_call`, named `hist_blocked` as the blocked call is — the
    name the benchmark's readers count a level of a tree by."""
    import jax

    (binned, rel, g, h, w), starts = _ordered_case(2048, 3, 256, 512,
                                                   seed=9)

    def level(r, s):
        return build_histogram(binned, r, g, h, w, 256, 512, "pallas",
                               unit_hess=True, starts=s)

    assert _kernel_names_for_tpu(level, rel, starts) == ["hist_blocked"]
    assert _pallas_calls(level, rel, starts) == 1
    (call,) = [e for e in jax.make_jaxpr(level)(rel, starts).eqns
               if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (1, 2 + 4 - 1)
    assert call.params["grid_mapping"].num_index_operands == 2


def _kernel_names_for_tpu(fn, *args):
    import re
    import unittest.mock as mock

    import jax

    with mock.patch("jax.default_backend", lambda: "tpu"):
        txt = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    return sorted(re.findall(r'kernel_name = "(\w+)"', txt))


def test_hi_blocked_level_lowers_for_tpu_under_its_own_name(monkeypatch):
    """AOT-lower a level past the cap for a TPU target from the CPU:
    Mosaic accepts the 4-D grid with several hi blocks, and the call is
    named `hist_blocked` (a level within the cap stays `hist_fact`) —
    the name a profile and the benchmark's readers find it by."""
    import h2o_kubernetes_tpu.ops.histogram as H

    rows, F, n_bins = 2048, 3, 64
    binned, rel, g, h, w = _random_case(rows, F, 16, n_bins, seed=3)
    monkeypatch.setattr(H, "_FACT_MAX_NHI", 8)
    for n_nodes, name in ((16, "hist_fact"), (64, "hist_blocked")):
        assert _kernel_names_for_tpu(lambda r: build_histogram(
            binned, r, g, h, w, n_nodes, n_bins, "pallas"),
            rel) == [name]


# the shallow levels — a handful of histogrammed nodes at the cells'
# widths, where the lo one-hot's layout bound the call until PR 35:
# (rows, F, nodes, bins, unit_hess, 16-bit codes)
_SHALLOW = {
    "root_256": (1300, 28, 1, 256, False, False),
    "two_nodes_256": (1100, 5, 2, 256, False, False),
    "eight_nodes_256_wide_tile": (9000, 28, 8, 256, False, False),
    "fourteen_nodes_256": (2100, 3, 14, 256, False, False),
    "fifteen_nodes_256": (2100, 3, 15, 256, False, False),
    "sixteen_nodes_256_two_channels": (1500, 4, 16, 256, True, False),
    "forest_64_bins_32_nodes": (1800, 28, 32, 64, True, False),
    "forest_64_bins_root_wide_tile": (8300, 6, 1, 64, True, False),
    "three_channels_64_bins": (1500, 5, 15, 64, False, False),
    "wide_136_columns": (700, 136, 8, 256, False, False),
    "odd_bins_17": (900, 3, 4, 17, False, False),
    "airline_512_bins_16_bit": (1200, 8, 1, 512, False, True),
    "airline_512_bins_8_nodes": (1000, 8, 8, 512, False, True),
    "wide_codes_256_bins": (1200, 8, 2, 256, False, True),
    "leaf_totals_one_bin": (700, 1, 32, 1, False, False),
}


@pytest.mark.parametrize("case", sorted(_SHALLOW))
def test_shallow_levels_match_segment(case):
    """Rows not a multiple of the tile (both tiles: 1,024 and, from
    8,192 rows, 4,096), ~20% dead rows carrying NaN gradients, ~10% of
    weight 0, 8- and 16-bit codes, 2 and 3 channels, 28 and 136
    columns (17 groups of 8), to 1e-5 of `segment`."""
    r, F, n_nodes, n_bins, unit, wide = _SHALLOW[case]
    binned, rel, g, h, w = _random_case(r, F, n_nodes, n_bins, seed=r + F)
    if wide:
        binned = binned.astype(jnp.uint16)
    if unit:
        h = jnp.ones_like(w)
    args = (binned, rel, g, h, w, n_nodes, n_bins)
    ref = build_histogram(*args, impl="segment", unit_hess=unit)
    got = build_histogram(*args, impl="pallas", unit_hess=unit)
    assert got.shape == (n_nodes, F, n_bins, 2 if unit else 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_nodes,n_bins,unit", [
    (1, 256, False), (8, 256, False), (16, 64, True)])
def test_shallow_levels_under_the_mesh_match_one_shard(mesh8, n_nodes,
                                                       n_bins, unit):
    """Under shard_map over the 8-device mesh (per-shard rows, psum):
    the sharded Pallas build equals one shard's over all rows."""
    import jax
    from jax.sharding import PartitionSpec as P

    from h2o_kubernetes_tpu.runtime.mesh import ROWS

    binned, rel, g, h, w = _random_case(8 * 300, 6, n_nodes, n_bins,
                                        seed=n_nodes)
    g = jnp.nan_to_num(g)

    def shard(b, r, gg, hh, ww):
        return jax.lax.psum(build_histogram(
            b, r, gg, hh, ww, n_nodes, n_bins, "pallas",
            unit_hess=unit), ROWS)

    got = jax.jit(jax.shard_map(
        shard, mesh=mesh8, in_specs=(P(ROWS),) * 5, out_specs=P(),
        check_vma=False))(binned, rel, g, h, w)
    want = build_histogram(binned, rel, g, h, w, n_nodes, n_bins,
                           "segment", unit_hess=unit)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("rows,n_nodes,n_bins,T", [
    (8192, 1, 256, 4096), (8192, 16, 256, 4096), (2048, 128, 256, 1024)])
def test_both_one_hots_are_built_rows_on_lanes(rows, n_nodes, n_bins, T):
    """The kernel holds no `[T, 128]` operand: the lo one-hot is
    `iota[128, T] == lo[None, :]`, contracted over the row axis of both
    operands. Built as `iota[T, 128] == lo[:, None]` it cost 896 lane
    permutes a (column, 4,096-row tile) and bound every shallow call at
    2.4 µs a (column, tile), 3x what the call costs without them
    (PERF.md section 3, PR 35)."""
    import jax

    from h2o_kubernetes_tpu.ops.histogram import _hist_pallas

    jaxpr = str(jax.make_jaxpr(
        lambda b, r, v: _hist_pallas(b, r, v, n_nodes, n_bins))(
        jnp.zeros((rows, 3), jnp.uint8), jnp.zeros(rows, jnp.int32),
        jnp.zeros((rows, 3), jnp.float32)))
    assert f"bf16[128,{T}]" in jaxpr
    assert f"[{T},128]" not in jaxpr


def test_class_batch_lowers_to_one_hist_fact_call():
    """The K-class grower's vmapped build is ONE call of the kernel a
    level, under the name every reader finds it by, `binned` stored
    once — and the kernel's blocks carry the class axis whole
    (`[K, T]` node ids, `[K, C, T]` values)."""
    import jax

    K, rows, F, n_nodes, n_bins = 3, 2048, 4, 1, 128
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, F, n_nodes, n_bins, seed=5)
    build = _vmapped(binned, w, n_nodes, n_bins, "pallas")
    assert _kernel_names_for_tpu(build, relK, gK, hK) == ["hist_fact"]
    jaxpr = jax.make_jaxpr(build)(relK, gK, hK)
    (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    gm = call.params["grid_mapping"]
    assert gm.grid == (1, 1, 1, 2)
    assert [tuple(getattr(d, "block_size", None) for d in bm.block_shape)
            for bm in gm.block_mappings] == [
        (F, 1, 1, 1024), (None, K, 1024), (None, K, 3, 1024),
        (1, 1, None, F, K * 3 * 1, 128)]


# the unbatched call at the six cells' shapes:
# (rows, F, C, bins, code dtype, nodes) -> kernel parameters, grid,
# block shapes (codes, node ids, values, out), out shape, name
_UNBATCHED = {
    "higgs_root": ((4194304, 28, 3, 256, "uint8", 1),
                   dict(n_bins=256, ht=2, n_ht=1, n_ch=3, fg=28, terms=3),
                   (1, 1, 1, 1024),
                   [(28, 1, 1, 4096), (4096,), (4096, 3),
                    (1, 1, 28, 6, 128)], (1, 1, 28, 6, 128), "hist_fact"),
    "higgs_16_nodes": ((4194304, 28, 3, 256, "uint8", 16),
                       dict(n_bins=256, ht=32, n_ht=1, n_ch=3, fg=28,
                            terms=3), (1, 1, 1, 1024),
                       [(28, 1, 1, 4096), (4096,), (4096, 3),
                        (1, 1, 28, 96, 128)], (1, 1, 28, 96, 128),
                       "hist_fact"),
    "higgs_64_nodes": ((4194304, 28, 3, 256, "uint8", 64),
                       dict(n_bins=256, ht=128, n_ht=1, n_ch=3, fg=16,
                            terms=3), (2, 1, 1, 4096),
                       [(16, 1, 1, 1024), (1024,), (1024, 3),
                        (1, 1, 16, 384, 128)], (2, 1, 16, 384, 128),
                       "hist_fact"),
    "forest_2048_nodes_blocked": (
        (4194304, 28, 2, 64, "uint8", 2048),
        dict(n_bins=64, ht=256, n_ht=4, n_ch=2, fg=8, terms=3),
        (4, 4, 1, 4096),
        [(8, 1, 1, 1024), (1024,), (1024, 2), (1, 1, 8, 512, 128)],
        (4, 4, 8, 512, 128), "hist_blocked"),
    "airline_root_16_bit": (
        (8388608, 8, 3, 512, "uint16", 1),
        dict(n_bins=512, ht=4, n_ht=1, n_ch=3, fg=8, terms=3),
        (1, 1, 1, 2048),
        [(8, 1, 1, 4096), (4096,), (4096, 3), (1, 1, 8, 12, 128)],
        (1, 1, 8, 12, 128), "hist_fact"),
    "airline_256_nodes_blocked": (
        (8388608, 8, 3, 512, "uint16", 256),
        dict(n_bins=512, ht=256, n_ht=4, n_ch=3, fg=8, terms=3),
        (1, 4, 1, 8192),
        [(8, 1, 1, 1024), (1024,), (1024, 3), (1, 1, 8, 768, 128)],
        (1, 4, 8, 768, 128), "hist_blocked"),
    "mslr_root_17_groups": (
        (2270296, 136, 3, 256, "uint8", 1),
        dict(n_bins=256, ht=2, n_ht=1, n_ch=3, fg=8, terms=3),
        (17, 1, 1, 555),
        [(8, 1, 1, 4096), (4096,), (4096, 3), (1, 1, 8, 6, 128)],
        (17, 1, 8, 6, 128), "hist_fact"),
    "covtype_one_class": (
        (581632, 54, 3, 256, "uint8", 16),
        dict(n_bins=256, ht=32, n_ht=1, n_ch=3, fg=54, terms=3),
        (1, 1, 1, 142),
        [(54, 1, 1, 4096), (4096,), (4096, 3), (1, 1, 54, 96, 128)],
        (1, 1, 54, 96, 128), "hist_fact"),
    "auc_one_column": (
        (8192, 1, 3, 4096, "uint16", 1),
        dict(n_bins=4096, ht=32, n_ht=1, n_ch=3, fg=1, terms=3),
        (1, 1, 1, 2),
        [(1, 1, 1, 4096), (4096,), (4096, 3), (1, 1, 1, 96, 128)],
        (1, 1, 1, 96, 128), "hist_fact"),
}


@pytest.mark.parametrize("case", sorted(_UNBATCHED))
def test_unbatched_call_is_the_pallas_call_it_was(monkeypatch, case):
    """Every cell but the K-class one reaches the kernel unbatched:
    the kernel function and its parameters, the 4-axis grid (the third
    axis 1: class blocks, where a class batch has them), block shapes,
    index maps, out shape, semantics and name that their ledger lines
    were measured with. A PR that means to change them measures every cell. Traced at
    the cells' own sizes; nothing runs."""
    import jax

    import h2o_kubernetes_tpu.ops.histogram as H

    (rows, F, C, bins, dtype, n), params, grid, blocks, out, name = \
        _UNBATCHED[case]
    seen = []
    real = H.pl.pallas_call

    def spy(kernel, **kw):
        seen.append((kernel, kw))
        return real(kernel, **kw)

    monkeypatch.setattr(H.pl, "pallas_call", spy)
    jax.eval_shape(
        lambda b, r, v: H._hist_pallas(b, r, v, n, bins),
        jax.ShapeDtypeStruct((rows, F), dtype),
        jax.ShapeDtypeStruct((rows,), jnp.int32),
        jax.ShapeDtypeStruct((rows, C), jnp.float32))
    ((kernel, kw),) = seen
    assert kernel.func is H._hist_fact_kernel
    assert kernel.keywords == params
    assert kw["grid"] == grid and kw["name"] == name
    assert [tuple(sp.block_shape) for sp in kw["in_specs"]] + \
        [tuple(kw["out_specs"].block_shape)] == blocks
    assert kw["out_shape"].shape == out
    # the index maps at (group 2, hi block 3, copy 1, row block 5): the
    # node ids and values carry `k·row blocks`, 0 on this grid
    assert [sp.index_map(2, 3, 1, 5) for sp in kw["in_specs"]] + \
        [kw["out_specs"].index_map(2, 3, 1, 5)] == [
        (2, 5, 0, 0), (grid[3] + 5,), (grid[3] + 5, 0), (2, 3, 0, 0, 0)]
    assert tuple(str(d) for d in
                 kw["compiler_params"].dimension_semantics) == (
        "parallel", "parallel", "arbitrary", "arbitrary")


def test_auc_histogram_lowers_to_hist_fact():
    """The AUC's one column of `_AUC_BINS` bins (32 hi slots) goes
    through the same kernel under the same name."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu import metrics as M

    col = jnp.zeros(8192, jnp.float32)
    prev = h2o.get_config("hist_impl")
    h2o.set_config("hist_impl", "pallas")
    try:
        names = _kernel_names_for_tpu(M._score_hist_shard, col, col, col)
    finally:
        h2o.set_config("hist_impl", prev)
    assert names == ["hist_fact"]


@pytest.mark.parametrize("impl", ["segment", "pallas"])
def test_unit_hess_two_channel_matches_three(impl):
    """h ≡ 1: the 2-channel accumulation (expanded back to 3) must
    equal the full 3-channel build with h = ones."""
    from h2o_kubernetes_tpu.ops.histogram import expand_unit_hess

    binned, rel, g, _, w = _random_case(900, 4, 8, 32, seed=11)
    ones = jnp.ones_like(w)
    ref = build_histogram(binned, rel, g, ones, w, 8, 32, impl=impl)
    got2 = build_histogram(binned, rel, g, ones, w, 8, 32, impl=impl,
                           unit_hess=True)
    assert got2.shape == (8, 4, 32, 2)
    got = expand_unit_hess(got2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gaussian_gbm_unit_hess_matches_full_channels(mesh8):
    """End to end: a gaussian GBM (unit_hess path) must predict the
    same as a build forced through the 3-channel kernels."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM
    from h2o_kubernetes_tpu.models.tree import core as C

    rng = np.random.default_rng(12)
    n = 600
    x = rng.normal(size=n).astype(np.float32)
    y = np.sin(2 * x) + rng.normal(scale=0.2, size=n)
    fr = h2o.Frame.from_arrays({"x": x, "y": y})
    m2 = GBM(ntrees=3, max_depth=3, nbins=32, seed=0).train(
        y="y", training_frame=fr)
    orig = C.TreeParams.__new__.__defaults__
    m3 = None
    try:
        # forcing unit_hess=False exercises the 3-channel path on the
        # same data (TreeParams is a NamedTuple: patch the default)
        import h2o_kubernetes_tpu.models.gbm as G

        real_tp = C.TreeParams

        def no_unit(*a, **kw):
            kw["unit_hess"] = False
            return real_tp(*a, **kw)

        G.TreeParams = no_unit
        m3 = GBM(ntrees=3, max_depth=3, nbins=32, seed=0).train(
            y="y", training_frame=fr)
    finally:
        import h2o_kubernetes_tpu.models.gbm as G

        G.TreeParams = C.TreeParams
        del orig
    np.testing.assert_allclose(m2.predict_raw(fr), m3.predict_raw(fr),
                               rtol=1e-6)


def test_vmapped_batch_matches_loop():
    """vmap over a class axis (the fused multinomial scan's shape) must
    equal per-class builds. The custom_vmap rule hands the batch to a
    kernel that takes it whole instead of batching the Pallas kernel —
    Mosaic rejects vmapped rank-1 block specs (round-4 on-chip gate)."""
    import jax

    K, rows, F, n_nodes, n_bins = 3, 1500, 4, 8, 32
    binned, relK, gK, hK, w = _class_batch_case(
        K, rows, F, n_nodes, n_bins, seed=21)

    for impl in ("segment", "pallas"):
        got = jax.vmap(
            lambda rel, g, h: build_histogram(
                binned, rel, g, h, w, n_nodes, n_bins, impl))(
            relK, gK, hK)
        assert got.shape == (K, n_nodes, F, n_bins, 3)
        for k in range(K):
            want = build_histogram(binned, relK[k], gK[k], hK[k], w,
                                   n_nodes, n_bins, "segment")
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want),
                rtol=1e-5, atol=1e-5, err_msg=f"{impl} class {k}")


def test_mosaic_lowering_for_tpu_target():
    """AOT-lower the vmapped pallas build for a TPU target FROM CPU —
    catches Mosaic block-spec rejections (the round-4 gate failure:
    vmap prepends a squeezed batch dim that Mosaic refuses on rank-1
    operands) without needing a chip."""
    import unittest.mock as mock

    import jax

    rng = np.random.default_rng(7)
    rows, F, n_nodes, n_bins, K = 2048, 3, 8, 64, 3
    binned = jnp.asarray(
        rng.integers(0, n_bins, size=(rows, F)).astype(np.uint8))
    relK = jnp.asarray(
        rng.integers(0, n_nodes, size=(K, rows)).astype(np.int32))
    gK = jnp.asarray(rng.normal(size=(K, rows)).astype(np.float32))
    hK = jnp.asarray(np.ones((K, rows), np.float32))
    w = jnp.ones(rows, jnp.float32)

    with mock.patch("jax.default_backend", lambda: "tpu"):
        def one(rel, g, h):
            return build_histogram(binned, rel, g, h, w, n_nodes,
                                   n_bins, "pallas")

        # single (rank-1 specs) and vmapped (batched) forms both lower
        jax.jit(one).trace(relK[0], gK[0], hK[0]).lower(
            lowering_platforms=("tpu",))
        jax.jit(jax.vmap(one)).trace(relK, gK, hK).lower(
            lowering_platforms=("tpu",))


def test_mosaic_lowering_bench_shape_paths():
    """AOT-lower the fact kernel's OTHER configurations from CPU: the
    wide 4096 row tile (rows >= 8192 — the production bench shape; the
    small-rows case above stays at rt=1024) and the feature-group
    SPLIT path (F_pad > F grid), which needs _OUT_BUDGET forced down
    since hitting it naturally takes F > 64."""
    import unittest.mock as mock

    import jax

    from h2o_kubernetes_tpu.ops import histogram as H

    rng = np.random.default_rng(11)
    rows, n_nodes, n_bins = 8192, 16, 256
    w = jnp.ones(rows, jnp.float32)
    g = jnp.asarray(rng.normal(size=rows).astype(np.float32))
    h = jnp.asarray(rng.random(rows).astype(np.float32))
    rel = jnp.asarray(
        rng.integers(0, n_nodes, size=rows).astype(np.int32))

    with mock.patch("jax.default_backend", lambda: "tpu"):
        # rt=4096 path (n_hi = 32 <= 64, rows >= 8192)
        binned = jnp.asarray(
            rng.integers(0, n_bins, size=(rows, 10)).astype(np.uint8))
        jax.jit(lambda r: build_histogram(
            binned, r, g, h, w, n_nodes, n_bins, "pallas")).trace(
            rel).lower(lowering_platforms=("tpu",))
        # feature-group split: budget forced to one feature's out block
        per_f = 3 * 32 * 128 * 4
        binned_wide = jnp.asarray(
            rng.integers(0, n_bins, size=(rows, 18)).astype(np.uint8))
        with mock.patch.object(H, "_OUT_BUDGET", per_f * 8):
            jax.jit(lambda r: build_histogram(
                binned_wide, r, g, h, w, n_nodes, n_bins,
                "pallas")).trace(rel).lower(lowering_platforms=("tpu",))


def test_feature_group_split_parity():
    """Interpret-mode parity through the F_pad > F split path (padded
    feature columns must histogram into junk rows that are sliced
    away, not into real features)."""
    import unittest.mock as mock

    from h2o_kubernetes_tpu.ops import histogram as H

    binned, rel, g, h, w = _random_case(3000, 18, 8, 64, seed=13)
    want = build_histogram(binned, rel, g, h, w, 8, 64, impl="segment")
    per_f = 3 * (-(-8 * 64 // 128)) * 128 * 4
    with mock.patch.object(H, "_OUT_BUDGET", per_f * 8):
        got = build_histogram(binned, rel, g, h, w, 8, 64,
                              impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_totals_preserved():
    binned, rel, g, h, w = _random_case(700, 3, 8, 32, seed=1)
    hist = build_histogram(binned, rel, g, h, w, 8, 32, impl="pallas")
    live = (np.asarray(rel) >= 0) & (np.asarray(w) > 0)
    want_w = np.asarray(w)[live].sum()
    # per-feature totals all equal the live weight mass
    tot = np.asarray(hist).sum(axis=(0, 2))[:, 2]
    np.testing.assert_allclose(tot, want_w, rtol=1e-5)


def test_tree_with_pallas_impl(mesh8):
    """Whole GBM trained with the pallas histogram (interpret mode)
    predicts identically to the segment_sum build."""
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    rng = np.random.default_rng(3)
    n = 500
    x = rng.normal(size=n).astype(np.float32)
    y = np.where(x + rng.normal(scale=0.3, size=n) > 0, "a", "b")
    fr = h2o.Frame.from_arrays({"x": x, "y": y})
    m_seg = GBM(ntrees=3, max_depth=3, nbins=32, seed=0).train(
        y="y", training_frame=fr)
    m_pal = GBM(ntrees=3, max_depth=3, nbins=32, seed=0,
                _hist_impl="pallas").train(y="y", training_frame=fr)
    np.testing.assert_allclose(m_pal.predict_raw(fr),
                               m_seg.predict_raw(fr), rtol=1e-5)


def test_histogram_auc_matches_exact():
    from h2o_kubernetes_tpu import metrics as M

    rng = np.random.default_rng(2)
    n = 30_000
    y = (rng.random(n) < 0.4).astype(np.float32)
    s = np.clip(y * 0.3 + rng.normal(scale=0.35, size=n) + 0.35, 0, 1)
    s = s.astype(np.float32)
    w = (rng.random(n) < 0.9).astype(np.float32)
    exact = M.roc_auc(y, s, w=w, exact=True)
    hist = M.roc_auc(y, s, w=w, exact=False)
    assert abs(exact - hist) < 2e-3, (exact, hist)
    # NaN on a live row surfaces through the histogram path too
    s2 = s.copy(); s2[17] = np.nan
    assert np.isnan(M.roc_auc(y, s2, w=w, exact=False))


def test_histogram_auc_inf_scores_pinned():
    from h2o_kubernetes_tpu import metrics as M

    rng = np.random.default_rng(4)
    n = 20_000
    y = (rng.random(n) < 0.5).astype(np.float32)
    s = (y * 0.5 + rng.normal(scale=0.3, size=n)).astype(np.float32)
    exact = M.roc_auc(y, s, exact=True)
    s_inf = s.copy(); s_inf[0] = np.inf; s_inf[1] = -np.inf
    hist = M.roc_auc(y, s_inf, exact=False)
    # one +inf / one -inf row must not collapse the binning
    assert abs(exact - hist) < 5e-3, (exact, hist)
