"""Row descent (models/tree/core.py::row_orig_bins) — tier-1.

Since PR 31 the bin of a row in the feature its node splits on is
SELECTED while the binned matrix streams, not gathered (the gather was
9.7 s of a 20.7 s GBM job on the chip: PERF.md section 6). The select
has to return the very integers the gather did, for every caller: the
grower's `descend` scope, `descend_tree` and `ooc._descend`. The
gather lives on here, as the reference.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from h2o_kubernetes_tpu.models.tree import core
from h2o_kubernetes_tpu.models.tree.efb import EFBLuts
from h2o_kubernetes_tpu.runtime.mesh import ROWS

N = 1024                         # 128 rows a shard on the 8-device mesh


def _gathered(binned, f, efb):
    """`row_orig_bins` as it was until PR 31: a `take_along_axis`."""
    def column(col):
        return jnp.take_along_axis(
            binned, col[:, None].astype(jnp.int32), axis=1)[:, 0].astype(
            jnp.int32)
    if efb is None:
        return column(f)
    col = efb.feat_col[f]
    s = column(col)
    return jnp.where(efb.slot_feat[col, s] == f, efb.slot_bin[col, s],
                     efb.feat_default[f]).astype(jnp.int32)


def _bundle(rng, F, Fb, B) -> EFBLuts:
    """LUTs of F features bundled into Fb columns. Only the four
    tables the decode reads are drawn; the split finder's stay zero."""
    z = jnp.zeros((Fb, B), jnp.int32)
    return EFBLuts(
        slot_feat=jnp.asarray(rng.integers(-1, F, (Fb, B)), jnp.int32),
        slot_bin=jnp.asarray(rng.integers(0, B, (Fb, B)), jnp.int32),
        na_slot=z, mstart=z, mend=z, has_rem=z.astype(bool), dbin=z,
        perm=jnp.zeros(Fb * (B - 1), jnp.int32),
        feat_col=jnp.asarray(rng.integers(0, Fb, F), jnp.int32),
        feat_default=jnp.asarray(rng.integers(0, B, F), jnp.int32))


# F: columns of the binned matrix; edge: half the rows sit at bin 0 or
# at the NA bin in EVERY column; f: how the per-row feature is drawn;
# wrap: the transform the callers put the function under
CASES = {
    "F1": dict(F=1),
    "F28": dict(F=28),
    "F300": dict(F=300),
    "bins64_rows_at_na_and_zero": dict(F=28, n_bins=64, edge=True),
    "bins256_rows_at_na_and_zero": dict(F=28, n_bins=256, edge=True),
    "f_first_column": dict(F=28, f="first"),
    "f_last_column": dict(F=28, f="last"),
    "unsplit_nodes_clamped": dict(F=28, f="unsplit"),
    "efb_bundle": dict(F=5, n_bins=32, features=12),
    "vmap_over_classes": dict(F=28, wrap="vmap"),
    "shard_map_8_devices": dict(F=28, wrap="shard_map"),
}


@pytest.mark.parametrize("case", CASES)
def test_row_orig_bins_is_bitwise_the_gather(mesh8, case):
    c = dict(dict(n_bins=256, edge=False, f="any", features=None,
                  wrap=None), **CASES[case])
    rng = np.random.default_rng(sorted(CASES).index(case))
    F, B = c["F"], c["n_bins"]
    binned = rng.integers(0, B, (N, F)).astype(np.uint8)
    if c["edge"]:
        binned[rng.random(N) < 0.25] = B - 1
        binned[rng.random(N) < 0.25] = 0
    n_feat = c["features"] or F
    efb = _bundle(rng, n_feat, F, B) if c["features"] else None
    K = 3 if c["wrap"] == "vmap" else 1
    f = {"any": rng.integers(0, n_feat, (K, N)),
         "first": np.zeros((K, N)),
         "last": np.full((K, N), n_feat - 1),
         # what `descend_tree` hands over: split_feat is -1 at a leaf
         "unsplit": np.maximum(rng.integers(-1, n_feat, (K, N)), 0),
         }[c["f"]].astype(np.int32)
    binned, f = jnp.asarray(binned), jnp.asarray(f)

    def call(fn):
        one = lambda b, ff: fn(b, ff, efb)      # noqa: E731
        if c["wrap"] == "vmap":     # the multinomial grower's rule
            return jax.jit(jax.vmap(one, in_axes=(None, 0)))(binned, f)
        if c["wrap"] == "shard_map":
            return jax.jit(jax.shard_map(
                one, mesh=mesh8, in_specs=(P(ROWS), P(ROWS)),
                out_specs=P(ROWS)))(binned, f[0])
        return jax.jit(one)(binned, f[0])

    got, want = call(core.row_orig_bins), call(_gathered)
    assert got.dtype == want.dtype == jnp.int32
    assert got.shape == want.shape
    assert np.array_equal(np.asarray(got), np.asarray(want))
    if c["edge"]:
        assert {0, B - 1} <= set(np.unique(np.asarray(got)))


@pytest.mark.parametrize("depth", [6, 12])
def test_descend_tree_rests_where_the_grower_left_each_row(mesh8, depth):
    """The grower's `leaf_node` (where each row rested as the tree
    grew) and `descend_tree` over the finished heap walk the same rows
    through the same splits: NA rows by `na_left`, the others by
    `bin > split_bin`."""
    rng = np.random.default_rng(depth)
    n, F, B = 8192, 6, 16
    binned = rng.integers(0, B, (n, F)).astype(np.uint8)
    binned[rng.random((n, F)) < 0.05] = B - 1            # NAs
    g = rng.normal(size=n).astype(np.float32)
    ones = np.ones(n, np.float32)
    p = core.TreeParams(max_depth=depth, n_bins=B, min_rows=1.0,
                        hist_impl="segment")

    def grow(binned, g, h, w):
        return core._grow_tree_shard(binned, g, h, w,
                                     jnp.ones(F, dtype=bool),
                                     jax.random.key(0), p)

    tree, leaf_node = jax.jit(jax.shard_map(
        grow, mesh=mesh8, in_specs=(P(ROWS),) * 4,
        out_specs=(P(), P(ROWS))))(binned, g, ones, ones)
    walked = jax.jit(core.descend_tree, static_argnums=(2, 3))(
        tree, jnp.asarray(binned), depth, B)
    leaf_node, walked = np.asarray(leaf_node), np.asarray(walked)
    assert np.array_equal(walked, leaf_node)
    assert leaf_node.max() >= 2 ** depth - 1     # rows reach the floor
    assert not np.asarray(tree.is_split)[leaf_node].any()
