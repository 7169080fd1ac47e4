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


def _bits(a):
    """An array's bits, so that `-0.0` and `0.0` (and NaNs) differ."""
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _table(rng, dtype, shape):
    if dtype == "bool":
        return rng.random(shape) < 0.5
    if dtype == "int32":
        return rng.integers(-2 ** 31, 2 ** 31 - 1, shape, dtype=np.int32)
    t = rng.normal(size=shape).astype(np.float32)
    t.reshape(-1, shape[-1])[:, 0] = -0.0       # a leaf of G = 0
    t.reshape(-1, shape[-1])[:, -1] = 0.0
    return t


@pytest.mark.parametrize("form", ["select", "gather"])
@pytest.mark.parametrize("batch", ["none", "vmap7", "lead7"])
@pytest.mark.parametrize("entries", [1, 2, 32, 127, 128, 2048])
@pytest.mark.parametrize("dtype", ["int32", "bool", "float32"])
def test_node_lookup_is_bitwise_the_gather(monkeypatch, dtype, entries,
                                           batch, form):
    """`core._node_lookup` is `table[idx]` bit for bit in both
    of its forms, whatever `_SELECT_MAX_ENTRIES` picks: for an int32, a
    bool and a float32 table with `-0.0` among its entries (a sum
    against `+0.0` fills would give `+0.0` there), for every entry
    count the cells' depths give, one tree or K = 7 of them — under
    `vmap`, as the class batch grows them, or on a leading axis, as the
    K-class margin reads its leaves. Every entry is read by some row."""
    monkeypatch.setattr(core, "_SELECT_MAX_ENTRIES",
                        1 << 30 if form == "select" else 0)
    rng = np.random.default_rng(entries)
    lead = () if batch == "none" else (7,)
    table = _table(rng, dtype, lead + (entries,))
    idx = rng.integers(0, entries, lead + (2 * N,)).astype(np.int32)
    idx[..., :entries] = np.arange(entries)
    want = np.take_along_axis(table, idx, axis=-1)
    fn = jax.vmap(core._node_lookup) if batch == "vmap7" \
        else core._node_lookup
    got = jax.jit(fn)(jnp.asarray(table), jnp.asarray(idx))
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("n_feat,n_bins,words", [
    (28, 256, 1), (136, 512, 1), (1, 2, 1), (2 ** 22, 256, 2)])
def test_split_of_rows_packs_and_unpacks(monkeypatch, n_feat, n_bins,
                                         words):
    """The descent's four tables read as one packed word, or as two
    where a feature index and a bin do not fit in 29 bits together:
    the very values the four gathers read."""
    monkeypatch.setattr(core, "_SELECT_MAX_ENTRIES", 1 << 30)
    rng = np.random.default_rng(n_feat)
    E = 64
    feat = rng.integers(0, n_feat, E).astype(np.int32)
    feat[0] = n_feat - 1
    bin_ = rng.integers(0, max(n_bins - 1, 1), E).astype(np.int32)
    bin_[1] = n_bins - 2
    na_l, can = rng.random(E) < 0.5, rng.random(E) < 0.5
    idx = rng.integers(0, E, N).astype(np.int32)
    fn = jax.jit(lambda i, *t: core._split_of_rows(i, *t, n_feat, n_bins))
    got = fn(*(jnp.asarray(a) for a in (idx, feat, bin_, na_l, can)))
    for g, t in zip(got, (feat, bin_, na_l, can)):
        assert np.array_equal(np.asarray(g), t[idx])
    hlo = fn.lower(*(jnp.asarray(a) for a in (idx, feat, bin_, na_l,
                                               can))).as_text()
    assert hlo.count("stablehlo.reduce") == words


_BOOST_MODES = {            # mode -> (distribution, K, forest, GOSS)
    "single": ("bernoulli", 1, False, False),
    "multi": ("multinomial", 7, False, False),
    "forest": ("bernoulli", 1, True, False),
    "rank": ("rank:ndcg", 1, False, False),
    "multi_forest": ("multinomial", 3, True, False),
    # past `_MULTI_HIST_BUDGET`: a class at a time under lax.map
    "multi_map": ("multinomial", 3, False, False),
    "goss_single": ("bernoulli", 1, False, True),
    "goss_multi": ("multinomial", 3, False, True),
}


def _boost_case(mode, mesh):
    """(operands less the static K and mesh, K, query layout or None) of
    a small job of ``mode`` (`_BOOST_MODES`)."""
    from h2o_kubernetes_tpu.models.tree import rank

    dist, K, forest, goss = _BOOST_MODES[mode]
    rng = np.random.default_rng(41)
    n, F, B = 4096, 6, 16
    binned = rng.integers(0, B, (n, F)).astype(np.uint8)
    binned[rng.random((n, F)) < 0.05] = B - 1            # NAs
    y = (rng.integers(0, K if K > 1 else 5, n) if K > 1 or mode == "rank"
         else rng.random(n) < 0.4).astype(np.float32)
    tp = core.TreeParams(max_depth=6 if not forest else 8,
                         n_bins=B, min_rows=1.0, reg_lambda=1.0,
                         mtries=3 if forest else -1,
                         hist_impl="segment",
                         unit_hess=forest)
    bp = core.BoostParams(
        distribution=dist,
        learn_rate=1.0 if forest else 0.3,
        sample_rate=0.632 if forest else 1.0,
        drf_mode=forest, goss_a=0.2 if goss else 0.0,
        goss_b=0.1 if goss else 0.0)
    margin = np.zeros((n, K) if K > 1 else n, np.float32)
    keys = jax.random.split(jax.random.key(7), 3)
    if goss:
        keys = (keys, core.goss_round_keys(jax.random.key(9), 3))
    groups = None
    if mode == "rank":
        gids = np.repeat(np.arange(n // 64), 64)[rng.permutation(n)]
        groups = rank.rank_layout(gids, y, n, mesh).groups
    return (jnp.asarray(binned), jnp.asarray(y), jnp.ones(n, jnp.float32),
            jnp.asarray(margin), keys, None, tp, bp), K, groups


@pytest.mark.parametrize("mode", list(_BOOST_MODES))
def test_boost_scans_are_bitwise_under_either_form(mesh8, monkeypatch,
                                                   mode):
    """`_boost_jit` (boosted, ranked, a forest, under GOSS) and
    `_boost_multi_jit` (K class trees a round: under the class batch,
    under lax.map, a K-class forest, under GOSS) grow bitwise the same
    trees and margin with every node table read by a gather and with
    every one read by a select (the rule is read as the program is
    traced: the caches are cleared between the two)."""
    args, K, groups = _boost_case(mode, mesh8)
    if mode == "multi_map":
        monkeypatch.setattr(core, "_MULTI_HIST_BUDGET", 1)
    if K > 1:
        assert core.multi_grow_vmapped(args[6], 6, K) == \
            (mode != "multi_map")
    out = {}
    for form, n in (("gather", 0), ("select", 1 << 30)):
        monkeypatch.setattr(core, "_SELECT_MAX_ENTRIES", n)
        jax.clear_caches()
        if K == 1:
            got = core._boost_jit(*args, 1, mesh8, groups)
        else:
            got = core._boost_multi_jit(*args, K, mesh8)
        out[form] = [_bits(a) for a in jax.tree.leaves(got)]
    jax.clear_caches()
    assert len(out["gather"]) == len(out["select"]) >= 8
    for a, b in zip(out["gather"], out["select"]):
        assert np.array_equal(a, b)
    assert out["select"][4].any()           # (margin, split_feat, ...)


def test_node_lookups_are_counted_by_form(mesh8, monkeypatch):
    """`h2o_train_node_lookups_total{form}`: a job's trees, each level's
    descent and each tree's margin update, by the form the rule gives
    the table's entry count — at depth 4 with the select up to 4
    entries, levels of 1, 2 and 4 nodes select, the level of 8 and the
    31-leaf margin gather."""
    from h2o_kubernetes_tpu import Frame
    from h2o_kubernetes_tpu.models import GBM
    from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

    assert core.node_lookup_forms(6) == ["select"] * 7
    monkeypatch.setattr(core, "_SELECT_MAX_ENTRIES", 4)
    assert core.node_lookup_forms(4) == ["select"] * 3 + ["gather"] * 2
    jax.clear_caches()
    rng = np.random.default_rng(0)
    fr = Frame.from_arrays({"a": rng.normal(size=2000).astype(np.float32),
                            "b": rng.normal(size=2000).astype(np.float32),
                            "y": (rng.random(2000) < 0.5).astype(
                                np.float32)})
    ctr = REGISTRY.counter("h2o_train_node_lookups_total", label="form")
    before = {k: ctr.value(k) for k in ("select", "gather")}
    GBM(ntrees=3, max_depth=4, seed=0).train(y="y", training_frame=fr)
    jax.clear_caches()
    assert {k: ctr.value(k) - v for k, v in before.items()} == \
        {"select": 9, "gather": 6}
