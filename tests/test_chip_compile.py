"""The main path's kernels and jitted steps, compiled by the chip's own
compiler for a DESCRIBED v5e — no chip attached, nothing runs (ISSUE 22;
`on-chip-measurement` guide, section 2). Interpret mode accepts what
Mosaic refuses (a block shape that does not tile, too much VMEM), so
these compiles guard every later PR at no chip time: real widths (HIGGS'
28 features, 256 bins, depth 6), modest row counts.

Code that asks `jax.default_backend()` sees the CPU here, so the tests
call the kernel or the jitted step itself with `hist_impl="pallas"` and
steer `ops/histogram._interpret` — the test steers, the package gets no
option. The topology is described inside a module-scoped fixture (never
at import, in a `skipif`, or in `parametrize`): only the xdist worker
that is handed this file loads the TPU's library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models.tree import core
from h2o_kubernetes_tpu.models.tree.shap import ShapTables
from h2o_kubernetes_tpu.ops import histogram, shap_kernel
from h2o_kubernetes_tpu.runtime.mesh import COLS, ROWS

F, BINS, DEPTH = 28, 256, 6      # chip_smoke.py's widths
ROWS_N = 65_536


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compile what the CHIP would run: kernels non-interpret. The
    # choice is made at trace time, so no trace from before or after
    # this module may be reused across the switch. A compile for a
    # described device can be written to the persistent cache but not
    # read back without a chip — keep these out of it.
    mp = pytest.MonkeyPatch()
    mp.setattr(histogram, "_interpret", lambda: False)
    mp.setattr(shap_kernel, "_interpret", lambda: False)
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield t
    mp.undo()
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    jax.clear_caches()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _s(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _kernel_names(compiled) -> set:
    """The instruction names of the Mosaic calls (`hist_fact.42` →
    `hist_fact`): what a profile's event and the benchmark's breakdown
    show for a kernel."""
    return {m[1] for m in re.finditer(
        r"%([A-Za-z_]+)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        compiled.as_text())}


def _row_gathers(text: str, results, scope: str) -> list:
    """The gathers of a compiled program whose result is one of
    ``results`` (`"7,65536"`: one element a row, or a class and a row)
    and whose name stack passes through ``scope``: a `kCustom` fusion,
    as the chip's compiler emits a gather by row, or a bare `gather`."""
    shape = "|".join(re.escape(r) for r in results)
    return [ln for ln in text.split("\n")
            if re.search(rf"= \w+\[({shape})\]\S* "
                         rf"(fusion\([^\n]*kind=kCustom|gather\()", ln)
            and any(f"/{scope}/" in name
                    for name in re.findall(r'op_name="([^"]+)"', ln))]


def _scopes(text: str) -> set:
    """Every component of every operation's JAX name stack."""
    return {part for name in re.findall(r'op_name="([^"]+)"', text)
            for part in name.split("/")}


@pytest.mark.parametrize("n_nodes,unit_hess", [
    (1, False), (8, False),         # the shallow levels (row tile 4,096),
    (16, True),                     # whose lo one-hot PR 35 transposed
    (32, False), (32, True),        # factorized kernel, 3 and 2 channels
    (512, False), (512, True),      # deep level: four hi blocks
])
def test_histogram_kernels_compile(one_chip, n_nodes, unit_hess):
    assert (-(-n_nodes * BINS // 128) <= histogram._FACT_MAX_NHI) == \
        (n_nodes <= 32)             # one hi block, and several
    fn = jax.jit(lambda b, r, g, h, w: histogram.build_histogram(
        b, r, g, h, w, n_nodes, BINS, "pallas", unit_hess=unit_hess))
    f32 = _s((ROWS_N,), jnp.float32, one_chip)
    c = fn.lower(_s((ROWS_N, F), jnp.uint8, one_chip),
                 _s((ROWS_N,), jnp.int32, one_chip), f32, f32,
                 f32).compile()
    assert _kernels(c) == 1
    assert _kernel_names(c) == {
        "hist_fact" if n_nodes <= 32 else "hist_blocked"}


@pytest.mark.parametrize("F,n_nodes,bins,C,dtype", [
    (136, 8, 256, 3, jnp.uint8),    # MSLR's width: 17 groups of 8
    (28, 64, 64, 2, jnp.uint8),     # a forest's level of 32 hi slots
    (8, 8, 512, 3, jnp.uint16),     # the airline's 16-bit codes
])
def test_shallow_levels_compile_at_the_other_cells_shapes(
        one_chip, F, n_nodes, bins, C, dtype):
    """The row-axis contraction of the lo one-hot (PR 35) at the wide
    row tile and the widths of the cells that are not HIGGS'."""
    fn = jax.jit(lambda b, r, v: histogram._hist_pallas(
        b, r, v, n_nodes, bins))
    c = fn.lower(_s((ROWS_N, F), dtype, one_chip),
                 _s((ROWS_N,), jnp.int32, one_chip),
                 _s((ROWS_N, C), jnp.float32, one_chip)).compile()
    assert _kernels(c) == 1 and _kernel_names(c) == {"hist_fact"}


@pytest.mark.parametrize("n_nodes,blocks,ordered", [
    pytest.param(128, 2, False, id="128-2"),
    pytest.param(256, 4, False, id="256-4"),
    pytest.param(512, 8, False, id="512-8"),
    pytest.param(1024, 16, False, id="1024-16"),
    # the same levels over rows ordered by node block: each block over
    # its own row tiles, the steps' blocks and tiles scalar-prefetched
    pytest.param(128, 2, True, id="128-2-compacted"),
    pytest.param(256, 4, True, id="256-4-compacted"),
    pytest.param(512, 8, True, id="512-8-compacted"),
    pytest.param(1024, 16, True, id="1024-16-compacted")])
def test_forest_levels_of_many_hi_blocks_compile(one_chip, n_nodes,
                                                 blocks, ordered):
    """`drf-airline.train`'s deep levels: a forest (2 channels) over the
    airline's 8 columns of 16-bit codes in a 512-bin matrix, whose
    levels 8-11 take 2, 4, 8 and 16 blocks of hi slots in ONE call,
    named `hist_blocked` whichever form serves it."""
    assert -(-n_nodes * 512 // 128) // histogram._FACT_MAX_NHI == blocks
    fn = jax.jit(lambda b, r, g, h, w, s: histogram.build_histogram(
        b, r, g, h, w, n_nodes, 512, "pallas", unit_hess=True, starts=s))
    f32 = _s((ROWS_N,), jnp.float32, one_chip)
    c = fn.lower(_s((ROWS_N, 8), jnp.uint16, one_chip),
                 _s((ROWS_N,), jnp.int32, one_chip), f32, f32, f32,
                 _s((blocks + 1,), jnp.int32, one_chip) if ordered
                 else None).compile()
    assert _kernels(c) == 1 and _kernel_names(c) == {"hist_blocked"}


def test_forest_scan_orders_its_rows_once(topo, monkeypatch):
    """`_boost_jit` on a forest at `drf-airline.train`'s widths (8
    columns of 16-bit codes, 512 bins, two channels, set features),
    depth 10: its levels 8 and 9 pass one hi block, so where the rule
    engages (forced here, whatever the measured costs say of two
    levels) each tree orders its rows by node block once — one stable
    sort of the rows' node keys and one scatter of the leaves back,
    under `row_order` — and both levels are `hist_blocked` calls over
    the ordered rows, one a level."""
    monkeypatch.setattr(core, "_ORDER_NS", 0.0)
    monkeypatch.setattr(core, "_ARRAY_NS", 0.0)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), (ROWS, COLS))
    a = _boost_args(mesh, ROWS_N, ntrees=1)
    rs = NamedSharding(mesh, P(ROWS))
    tp = a[6]._replace(max_depth=10, n_bins=512, min_rows=1.0, mtries=2,
                       unit_hess=True, set_feats=(True,) * 6 + (False,) * 2)
    bp = a[7]._replace(sample_rate=0.632, drf_mode=True, learn_rate=1.0)
    assert core.hist_level_forms(tp, 8) == ["fact"] * 8 + \
        ["compacted"] * 2
    c = core._boost_jit.lower(_s((ROWS_N, 8), jnp.uint16, rs),
                              *a[1:6], tp, bp, 1, mesh).compile()
    txt = c.as_text()
    assert _kernels(c) == 10
    assert txt.count('"kernel":"hist_blocked"') == 2
    ordered = [ln for ln in txt.split("\n")
               if "/row_order/" in ln and re.search(
                   rf"= \(?s32\[{ROWS_N}\].* (sort|scatter)\(", ln)]
    assert sorted(re.search(r" (sort|scatter)\(", ln)[1]
                  for ln in ordered) == ["scatter", "sort"], ordered


@pytest.mark.parametrize("K,F,n_nodes,bins,unit_hess,name", [
    (7, 54, 1, 256, False, "hist_fact"),    # `xgb-covtype.train`'s root
    (7, 54, 16, 256, False, "hist_fact"),   # and its deepest level
    (7, 54, 64, 256, False, "hist_fact"),   # 7 x 128 slots pass the cap:
                                            # four blocks of two classes
    (7, 54, 512, 256, False, "hist_blocked"),   # one class passes it
    (3, 28, 32, 64, True, "hist_fact"),     # a K-class forest: a class's
                                            # lo one-hot follows its nodes
    # a block's VMEM follows its classes too (`_CLASS_BLOCK_MAX`): the
    # widest block, 8 classes, at the wide row tile's most slots, at the
    # cap, and the roots of many classes (32 in one block ask 19.6 MB)
    (8, 54, 4, 256, False, "hist_fact"),
    (8, 54, 16, 256, False, "hist_fact"),
    (32, 54, 1, 256, False, "hist_fact"),
    (128, 54, 1, 256, False, "hist_fact"),
    (40, 54, 32, 256, False, "hist_fact"),  # about the most classes
                                            # `multi_grow_vmapped` batches
                                            # at this width and depth 6
])
def test_class_batch_compiles_at_the_cells_shapes(one_chip, K, F, n_nodes,
                                                  bins, unit_hess, name):
    """The K-class grower's vmapped build (ISSUE 39): ONE kernel call
    whose row tile carries `[K, T]` node ids and `[K, C, T]` values —
    blocks whose class dim is the array's own, which Mosaic's (8, 128)
    rule has nothing against — at K 7, 54 columns, 256 bins, 1 and 16
    histogrammed nodes a class, past the cap on stacked slots both
    ways, and past the cap on classes a block."""
    fn = jax.jit(jax.vmap(
        lambda b, r, g, h, w: histogram.build_histogram(
            b, r, g, h, w, n_nodes, bins, "pallas", unit_hess=unit_hess),
        in_axes=(None, 0, 0, 0, None)))
    rows = _s((ROWS_N,), jnp.float32, one_chip)
    cls = _s((K, ROWS_N), jnp.float32, one_chip)
    c = fn.lower(_s((ROWS_N, F), jnp.uint8, one_chip),
                 _s((K, ROWS_N), jnp.int32, one_chip), cls, cls,
                 rows).compile()
    # (under a bare `vmap` the instruction is `vmap_hist_fact_`; inside
    # the boost program it is `hist_fact.N`: the K-class scan's test)
    assert _kernels(c) == 1
    assert c.as_text().count(f'"kernel":"{name}"') == 1


def _boost_args(mesh, rows, ntrees):
    rs, rep = NamedSharding(mesh, P(ROWS)), NamedSharding(mesh, P())
    tp = core.TreeParams(max_depth=DEPTH, n_bins=BINS, min_rows=10.0,
                         reg_lambda=0.0, reg_alpha=0.0, gamma=1e-5,
                         mtries=-1, min_child_weight=0.0,
                         hist_impl="pallas", unit_hess=False)
    bp = core.BoostParams(distribution="bernoulli", learn_rate=0.1,
                          sample_rate=1.0, col_sample_rate_per_tree=1.0,
                          drf_mode=False, goss_a=0.0, goss_b=0.0)
    keys = jax.eval_shape(
        lambda: jax.random.split(jax.random.key(0), ntrees))
    f32 = _s((rows,), jnp.float32, rs)
    return (_s((rows, F), jnp.uint8, rs), f32, f32, f32,
            _s(keys.shape, keys.dtype, rep), None, tp, bp, 1, mesh)


@pytest.fixture(scope="module")
def boost_scan(topo):
    """n_dev -> the compiled `_boost_jit`, compiled once a module."""
    done = {}

    def compiled(n_dev):
        if n_dev not in done:
            mesh = Mesh(np.array(topo.devices[:n_dev]).reshape(n_dev, 1),
                        (ROWS, COLS))
            done[n_dev] = core._boost_jit.lower(
                *_boost_args(mesh, ROWS_N * n_dev, ntrees=3)).compile()
        return done[n_dev]

    return compiled


@pytest.mark.parametrize("n_dev", [1, 4])
def test_boost_scan_compiles(boost_scan, n_dev):
    """`_boost_jit` — the fused boost scan `GBM.train()` dispatches —
    binomial at HIGGS width and depth 6, on one chip and row-sharded
    over the 2x2 host with the level histograms psum-ed."""
    c = boost_scan(n_dev)
    txt = c.as_text()
    assert txt.count("tpu_custom_call") == DEPTH   # one kernel a level
    assert ("all-reduce" in txt) == (n_dev > 1)
    assert c.memory_analysis().temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("n_dev,scope", [
    pytest.param(1, "descend", id="1"), pytest.param(4, "descend", id="4"),
    pytest.param(1, "margin", id="margin-1"),
    pytest.param(4, "margin", id="margin-4")])
def test_boost_scan_descends_without_a_gather(boost_scan, n_dev, scope):
    """Row descent selects each row's split column in one loop fusion
    (PR 31). The `take_along_axis` it replaced compiled to a `kCustom`
    gather fusion with a `u8[rows]` result, one a level: 72-88 ms a
    call on the chip at 4,194,304 x 28, 47% of a GBM job (PERF.md
    section 6). The margin update reads each row's leaf value from the
    tree's 127 entries by a select too: the gather it replaced,
    `f32[rows]` under `margin`, took 33 ms a tree at 4,194,304 rows."""
    txt = boost_scan(n_dev).as_text()
    if scope == "descend":
        assert not re.findall(
            rf"= u8\[{ROWS_N}\]\S* fusion\([^\n]*kind=kCustom", txt)
    else:
        assert not _row_gathers(txt, [str(ROWS_N)], "margin")
    assert scope in _scopes(txt)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_boost_scan_names_its_kernel_and_scopes(boost_scan, n_dev):
    """What a profile of the chip shows for the boost program: the
    histogram kernel's instruction is `hist_fact.N` (it was the name
    stack's innermost component, `closed_call.N`), still a
    `tpu_custom_call`, with the kernel's name in `kernel_metadata`; and
    every phase of the step is a component of its operations'
    `op_name`. A scope whose operations the compiler folds away leaves
    no name: `sample` at sample_rate 1, `hist_psum` on one chip."""
    c = boost_scan(n_dev)
    txt = c.as_text()
    assert _kernel_names(c) == {"hist_fact"}
    assert txt.count('"kernel":"hist_fact"') == DEPTH
    scopes = _scopes(txt)
    want = {"grad_hess", "margin", "level_hist", "sibling", "split_find",
            "descend", "leaves", "hist_fact"}
    if n_dev > 1:
        want.add("hist_psum")
    assert want <= scopes, want - scopes
    assert any(n.endswith("level_hist/hist_fact/pallas_call")
               for n in re.findall(r'op_name="([^"]+)"', txt))


@pytest.mark.parametrize("depth,kernels", [
    (6, {"hist_fact"}), (12, {"hist_fact", "hist_blocked"})])
def test_forest_scan_holds_one_trees_temporaries(topo, depth, kernels):
    """`_boost_jit` on a forest — what `DRF.train()` dispatches —
    grows one tree a scan step, so six trees a dispatch reserve what
    one does (grouped under vmap they reserved six times that: 25 G of
    a 16 G chip at 4,194,304 rows, PERF.md section 6). At depth 12 x 64
    bins the deepest histogram level (1,024 left children) is past what
    one block of hi slots holds and the kernel serves it in two, under
    the name `hist_blocked`: the one path of `drf-higgs.train` that no
    other cell runs. The scan carries the sum of its trees' leaf values
    (PR 33: the forest's train metric is read off it), so the `margin`
    scope names operations of this program too."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), (ROWS, COLS))
    args = _boost_args(mesh, ROWS_N, ntrees=1)
    tp = args[6]._replace(max_depth=depth, n_bins=64, min_rows=1.0,
                          mtries=5, unit_hess=True)
    bp = args[7]._replace(sample_rate=0.632, drf_mode=True,
                          learn_rate=1.0)
    temp = {}
    for ntrees in (1, 6):
        a = _boost_args(mesh, ROWS_N, ntrees)
        c = core._boost_jit.lower(*a[:6], tp, bp, 1, mesh).compile()
        assert _kernels(c) == depth and _kernel_names(c) == kernels
        assert "margin" in _scopes(c.as_text())
        temp[ntrees] = c.memory_analysis().temp_size_in_bytes
    # the trees stacked for the way out are the difference
    assert temp[6] < 1.5 * temp[1]


def test_every_scope_is_traced(topo):
    """Before the compiler folds anything: the traced programs of the
    training path hold every scope name PERF.md lists."""
    from h2o_kubernetes_tpu import metrics
    from h2o_kubernetes_tpu.models.tree import binning

    def traced(fn, *args):
        return {part for name in re.findall(
            r'loc\("([^"]+)"', fn.lower(*args).as_text(debug_info=True))
            for part in name.split("/")}

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), (ROWS, COLS))
    boost = traced(core._boost_jit, *_boost_args(mesh, ROWS_N, ntrees=3))
    assert {"sample", "grad_hess", "margin", "level_hist", "hist_psum",
            "sibling", "split_find", "descend", "leaves"} <= boost
    col = jax.ShapeDtypeStruct((ROWS_N,), jnp.float32)
    fit = traced(
        binning._fused_fit_bin_jit,
        jax.ShapeDtypeStruct((F, BINS - 2), jnp.float32),
        jax.ShapeDtypeStruct((F,), jnp.int32),
        jax.ShapeDtypeStruct((4096, F), jnp.float32), (col,) * F,
        jax.ShapeDtypeStruct((F,), jnp.bool_), BINS - 1)
    assert {"fit_quantiles", "apply_bins"} <= fit
    assert "logloss" in traced(metrics._logloss_w, col, col, col, 1e-7)
    assert "auc" in traced(metrics._auc_impl, col, col, col)
    assert "auc" in traced(metrics._score_hist_one, col, col, col)


@pytest.mark.parametrize("entry", ["_bin_block_jit", "_fused_fit_bin_jit"])
def test_binning_compiles_without_a_gather_loop(one_chip, entry):
    """The cell's first column block (16 of HIGGS' 28 columns x
    4,194,304 rows, 256 bins) through both binning programs. The codes
    are a count of compares: the chip ran `searchsorted`'s binary search
    as a `while` of 8 steps, each a per-element gather of one edge,
    6.3 s a block against 11.5 ms (PERF.md section 6, PR 27). The
    temporaries stay under what the search took, far under the boost
    program's reservation."""
    from h2o_kubernetes_tpu.models.tree import binning

    rows, block = 1 << 22, 16
    cols = (_s((rows,), jnp.float32, one_chip),) * block
    enum = _s((block,), jnp.bool_, one_chip)
    if entry == "_bin_block_jit":
        args = (cols, _s((block, BINS - 2), jnp.float32, one_chip),
                BINS - 1, enum)
    else:
        args = (_s((F, BINS - 2), jnp.float32, one_chip),
                _s((F,), jnp.int32, one_chip),
                # the quantile half is not what this guards, and its
                # sort compiles in 17 s at 4096 rows: a sliver of a sample
                _s((128, F), jnp.float32, one_chip),
                cols, enum, BINS - 1)
    c = getattr(binning, entry).lower(*args).compile()
    binned = [ln for ln in c.as_text().split("\n") if re.search(
        r'op_name="[^"]*\bapply_bins\b', ln)]
    assert binned, "no operation carries the apply_bins scope"
    assert not [ln for ln in binned if "searchsorted" in ln]
    assert not [ln for ln in binned if re.search(r" gather\(", ln)]
    assert c.memory_analysis().temp_size_in_bytes <= 1.75 * (1 << 30)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_train_metric_compiles(topo, n_dev):
    """The AUC `GBM.train()` ends with bins 2M scores through the same
    histogram kernel. Over four chips it must do so per shard under
    shard_map: a jit over sharded rows asks the compiler to partition
    the Mosaic kernel, which it refuses — the fault the first 4-chip
    run of PR 22 found, after the boost scan had already passed."""
    from h2o_kubernetes_tpu import metrics

    mesh = Mesh(np.array(topo.devices[:n_dev]).reshape(n_dev, 1),
                (ROWS, COLS))
    sh = NamedSharding(mesh, P(ROWS)) if n_dev > 1 else \
        SingleDeviceSharding(topo.devices[0])
    rows = _s((1 << 21,), jnp.float32, sh)
    prev = h2o.get_config("hist_impl")
    h2o.set_config("hist_impl", "pallas")
    try:
        fn = metrics._score_hist_on(mesh, "pallas") if n_dev > 1 \
            else metrics._score_hist_one
        c = fn.lower(rows, rows, rows).compile()
    finally:
        h2o.set_config("hist_impl", prev)
    assert _kernels(c) == 1
    assert ("all-reduce" in c.as_text()) == (n_dev > 1)


def test_flat_scorer_compiles(one_chip):
    """`flat_margin` at a serving bucket: 50 depth-6 trees, 8192 rows."""
    T, M = 50, 2 ** (DEPTH + 1) - 1
    flat = core.FlatTrees(*(_s((T, M), d, one_chip) for d in (
        jnp.int32, jnp.float32, jnp.int32, jnp.bool_, jnp.float32)))
    core.flat_margin.lower(flat, _s((8192, F), jnp.float32, one_chip),
                           _s((F,), jnp.bool_, one_chip), DEPTH,
                           1).compile()


@pytest.mark.parametrize("rows,T,L,D", [
    (1024, 50, 32, 6),      # the smoke's ensemble at a serving bucket
    (128, 50, 32, 6),       # smallest bucket: one row tile of 128
    (512, 4, 32, 11),       # the largest group `kernel_fits` accepts
])
def test_shap_kernel_compiles(one_chip, rows, T, L, D):
    """`flat_shap_tab_kernel`: Mosaic refused it at every shape until
    PR 22 (the per-tree bias block (1, 1) of a [T, 1] SMEM array does
    not tile). What `kernel_fits` accepts must compile."""
    tb = ShapTables(*(_s((T, L, D), d, one_chip) for d in (
        jnp.int32, jnp.float32, jnp.float32, jnp.bool_, jnp.float32)),
        _s((T, L), jnp.float32, one_chip),
        _s((T,), jnp.float32, one_chip))
    ct = _s((T, L, D, 1 << D), jnp.float32, one_chip)
    assert shap_kernel.kernel_fits(tb, ct, rows)
    c = shap_kernel.flat_shap_tab_kernel.lower(
        tb, ct, _s((rows, F), jnp.float32, one_chip),
        _s((F,), jnp.bool_, one_chip)).compile()
    assert _kernels(c) == 1
    assert _kernel_names(c) == {"shap_tab"}


def test_kernel_fits_refuses_what_cannot_fit():
    """One past the largest accepted group (D=12: a 4096-pattern
    one-hot over a 512-row tile) is refused before any trace."""
    class G:
        feat = np.zeros((4, 32, 12), np.int32)

    ct = jax.ShapeDtypeStruct((4, 32, 12, 1 << 12), jnp.float32)
    assert not shap_kernel.kernel_fits(G, ct, 512)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_ranking_boost_scan_compiles(topo, n_dev):
    """`_boost_jit` with a query layout for its last operand — what
    `XGBoost(objective="rank:ndcg").train()` dispatches (ISSUE 34) — at
    MSLR-WEB30K's width: 136 columns at depth 8 x 256 bins, ragged
    queries of 1 to 1,251 rows. One kernel a level, the frame's
    transposition 136 columns wide and not padded to 192 (the groups of
    `histogram._feature_groups`), the pairwise gradients under
    `grad_hess/rank_sort` and `grad_hess/rank_pairs`; on four chips the
    margin is gathered (a query may straddle a shard's edge)."""
    from h2o_kubernetes_tpu.models.tree import rank

    mesh = Mesh(np.array(topo.devices[:n_dev]).reshape(n_dev, 1),
                (ROWS, COLS))
    rs, rep = NamedSharding(mesh, P(ROWS)), NamedSharding(mesh, P())
    rng = np.random.default_rng(0)
    sizes = np.concatenate([[1, 1251, 300], rng.integers(2, 260, 500)])
    rows = -(-int(sizes.sum()) // (1024 * n_dev)) * 1024 * n_dev
    host = Mesh(np.array(jax.devices("cpu")[:1]).reshape(1, 1),
                (ROWS, COLS))
    lay = rank.rank_layout(
        np.repeat(np.arange(len(sizes)), sizes),
        rng.integers(0, 5, int(sizes.sum())).astype(np.float32), rows,
        host)
    groups = rank.RankGroups(
        tuple(rank.RankClass(*(_s(a.shape, a.dtype, rep) for a in c))
              for c in lay.groups.classes),
        _s((rows,), jnp.int32, rs))
    args = _boost_args(mesh, rows, ntrees=2)
    tp = args[6]._replace(max_depth=8, min_rows=1.0, reg_lambda=1.0,
                          gamma=0.0, min_child_weight=100.0)
    bp = args[7]._replace(distribution="rank:ndcg")
    c = core._boost_jit.lower(
        _s((rows, 136), jnp.uint8, rs), *args[1:6], tp, bp, 1, mesh,
        groups).compile()
    txt = c.as_text()
    assert txt.count("tpu_custom_call") == 8
    assert _kernel_names(c) == {"hist_fact"}
    widths = {int(m) for m in re.findall(r"s32\[(\d+),\d+,1,\d+\]", txt)}
    # (a 34-wide form is the same 136 byte columns, four to a word)
    assert max(widths) == 136, widths
    assert {"grad_hess", "rank_sort", "rank_pairs"} <= _scopes(txt)
    assert ("all-gather" in txt) == (n_dev > 1)


@pytest.mark.parametrize("n_dev", [1, 4])
def test_k_class_boost_scan_compiles(topo, n_dev):
    """`_boost_multi_jit` — what `XGBoost(objective="multi:softprob")
    .train()` dispatches on a 7-level response (ISSUE 38) — at the
    widths of the `xgb-covtype.train` cell: K 7, 54 `uint8` columns,
    depth 6, 256 bins, one round, the `[rows, 7]` margin sharded by
    rows. The seven class trees of a round grow under `vmap`, whose
    batching rule hands the kernel the class batch whole: ONE
    `hist_fact` call a level for the seven (6 in all, the deepest with
    the seven classes' 32 hi slots each stacked on one A operand),
    never seven calls and never a hi-blocked one. The temporaries:
    the kernel takes the values as `[K, C, rows]`, the transposition
    fuses into the stack's own fusion and no lane-padded
    `f32[K·rows, C]` stack exists: 1.04 KB a row at the cell's size
    (PERF.md section 6) and 2.1 at this one, where the level
    histograms weigh more; held here under 3. Every phase of the step
    names its operations for K > 1 as it does for one tree a round.
    Row-sharded over the 2x2 host the class kernel runs a shard under
    shard_map and the per-level psum sees the same
    `[K, n_nodes, F, B, C]` array (compiled here; no cell runs it)."""
    K, F_COV = 7, 54
    mesh = Mesh(np.array(topo.devices[:n_dev]).reshape(n_dev, 1),
                (ROWS, COLS))
    rs = NamedSharding(mesh, P(ROWS))
    rows = ROWS_N * n_dev
    args = _boost_args(mesh, rows, ntrees=1)
    tp = args[6]._replace(min_rows=1.0, reg_lambda=1.0, gamma=0.0,
                          min_child_weight=1.0)
    bp = args[7]._replace(distribution="multinomial", learn_rate=0.3)
    assert core.multi_grow_vmapped(tp, F_COV, K)
    lowered = core._boost_multi_jit.lower(
        _s((rows, F_COV), jnp.uint8, rs), *args[1:3],
        _s((rows, K), jnp.float32, rs), *args[4:6], tp, bp, K, mesh)
    c = lowered.compile()
    txt = c.as_text()
    assert _kernels(c) == DEPTH
    assert ("all-reduce" in txt) == (n_dev > 1)
    assert _kernel_names(c) == {"hist_fact"}
    # the deepest level's call: 7 feature groups of 8 (54 padded to
    # 56), one hi block, one class block of 7 classes x 3 channels x
    # 32 hi slots = 672 rows
    assert re.search(r"f32\[7,1,1,8,672,128\]", txt)
    assert c.memory_analysis().temp_size_in_bytes < 3_000 * ROWS_N
    # no by-row lookup of a node table is a batched gather (they were 20 a
    # round under `vmap(descend)` and one under `margin`, 6.4 s of the
    # cell's 9.0 s job); a select that did not fuse into its reduce
    # would show in the temporaries above as `[K, entries, rows]`
    per_row = [f"{K},{ROWS_N}", str(K * ROWS_N)]
    for scope in ("vmap(descend)", "margin"):
        assert not _row_gathers(txt, per_row, scope), scope
    # (the grower's scopes come out as `vmap(level_hist)`: a reader that
    # takes a name stack apart by "/" alone files them under no scope)
    scopes = _scopes(txt)
    scopes |= {m for s in scopes for m in re.findall(r"vmap\((\w+)\)", s)}
    want = {"grad_hess", "margin", "level_hist", "sibling", "split_find",
            "descend", "leaves", "hist_fact"}
    assert want <= scopes, want - scopes
    assert "vmap(level_hist)" in scopes and "level_hist" not in _scopes(txt)
    # (`sample` names nothing at sample_rate 1: the compiler folds it)
    traced = set(re.findall(r"\w+", " ".join(re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))))
    assert {"sample", "grad_hess", "margin", "level_hist", "split_find",
            "descend"} <= traced
