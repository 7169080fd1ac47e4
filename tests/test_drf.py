import numpy as np
import pytest

from h2o_kubernetes_tpu import Frame
from h2o_kubernetes_tpu import metrics as M
from h2o_kubernetes_tpu.models import DRF


def test_drf_binary(mesh8):
    rng = np.random.default_rng(0)
    n = 4000
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = ((1.2 * x1 - 0.8 * x2 + rng.normal(scale=0.4, size=n)) > 0).astype(int)
    fr = Frame.from_arrays({"x1": x1, "x2": x2,
                            "y": np.array(["n", "p"])[y]})
    m = DRF(ntrees=30, max_depth=8, seed=1).train(y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["auc"] > 0.95

    from sklearn.ensemble import RandomForestClassifier
    sk = RandomForestClassifier(n_estimators=30, max_depth=8,
                                random_state=0).fit(
        np.stack([x1, x2], 1), y)
    sk_auc = M.roc_auc(y, sk.predict_proba(np.stack([x1, x2], 1))[:, 1])
    assert perf["auc"] > sk_auc - 0.035  # parity band vs sklearn RF


@pytest.mark.slow
def test_drf_regression(mesh8):
    rng = np.random.default_rng(2)
    n = 3000
    x1 = rng.normal(size=n)
    x2 = rng.uniform(-2, 2, size=n)
    y = 2.0 * x1 + x2 ** 2 + rng.normal(scale=0.2, size=n)
    fr = Frame.from_arrays({"x1": x1, "x2": x2, "y": y})
    m = DRF(ntrees=40, max_depth=10, seed=3).train(y="y", training_frame=fr)
    perf = m.model_performance(fr, "y")
    assert perf["r2"] > 0.85


@pytest.mark.slow
def test_drf_multiclass_probs_sum_to_one(mesh8):
    rng = np.random.default_rng(4)
    n = 2000
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    cls = np.where(x1 > 0.5, 2, np.where(x2 > 0, 1, 0))
    fr = Frame.from_arrays({"x1": x1, "x2": x2,
                            "y": np.array(["a", "b", "c"])[cls]})
    m = DRF(ntrees=20, max_depth=6, seed=5).train(y="y", training_frame=fr)
    out = m.predict_raw(fr)
    np.testing.assert_allclose(out.sum(axis=1), 1.0, atol=1e-5)
    assert m.model_performance(fr, "y")["accuracy"] > 0.9


def test_deep_tree_budget_validation(mesh8):
    """Depth past 12 trains when the level histograms fit the memory
    budget and fails with sizing guidance when they cannot — the
    reference reaches depth 20 via dynamic row partitions; the dense
    heap's answer is a validated budget (models/gbm.py)."""
    import pytest

    rng = np.random.default_rng(9)
    n = 4096
    cols = {f"x{i}": rng.normal(size=n).astype(np.float32)
            for i in range(4)}
    cols["y"] = np.where(cols["x0"] + 0.5 * cols["x1"] > 0, "p", "n")
    fr = Frame.from_arrays(cols)
    # depth 16, 4 features x 16 bins: ~25 MiB of level histograms —
    # must TRAIN, not error (depth itself is not capped)
    m = DRF(ntrees=2, max_depth=16, nbins=16, min_rows=1,
            seed=1).train(y="y", training_frame=fr)
    assert m.model_performance(fr, "y")["auc"] > 0.8
    # many features x 64 bins at depth 16 blows the budget: the error
    # must name the knobs (max_depth / nbins / budget)
    wide = {f"x{i}": rng.normal(size=256).astype(np.float32)
            for i in range(30)}
    wide["y"] = np.where(wide["x0"] > 0, "p", "n")
    fr_wide = Frame.from_arrays(wide)
    with pytest.raises(ValueError, match="max_depth.*nbins|nbins.*budget"):
        DRF(ntrees=1, max_depth=16, nbins=64, seed=1).train(
            y="y", training_frame=fr_wide)


def _forest_args(mesh, rows, F, n_bins, depth, ntrees, seed):
    """`_boost_jit`'s operands for a bagged forest over ``rows`` x
    ``F`` random codes of ``n_bins`` bins, the first column a set
    feature, a 0/1 response."""
    import jax
    import jax.numpy as jnp

    from h2o_kubernetes_tpu.models.tree import core
    from h2o_kubernetes_tpu.runtime.mesh import row_sharding

    rng = np.random.default_rng(seed)
    codes = rng.integers(0, n_bins, size=(rows, F))
    y = ((codes[:, 0] % 7 < 3) ^ (rng.random(rows) < 0.2)).astype(
        np.float32)
    rs = row_sharding(mesh)
    put = lambda a: jax.device_put(jnp.asarray(a), rs)     # noqa: E731
    tp = core.TreeParams(max_depth=depth, n_bins=n_bins, min_rows=1.0,
                         gamma=1e-5, mtries=2, hist_impl="pallas",
                         unit_hess=True,
                         set_feats=(True,) + (False,) * (F - 1))
    bp = core.BoostParams(distribution="bernoulli", learn_rate=1.0,
                          sample_rate=0.632, drf_mode=True)
    keys = core.round_keys(jax.random.key(seed), ntrees)
    return (put(codes.astype(np.uint16)), put(y), put(np.ones(rows,
            np.float32)), put(np.zeros(rows, np.float32)), keys, None,
            tp, bp, 1, mesh)


def test_forest_grown_over_rows_ordered_by_node_block_is_the_same(
        mesh8, monkeypatch):
    """Where a tree orders its rows by node block (forced here: the
    rule's costs set to nothing, `hist_impl="pallas"`), a forest whose
    levels 8 and 9 pass one hi block (512 bins: 2 and 4 blocks) grows
    BITWISE the trees it grows in the caller's order — splits, sets,
    values, gains and covers (its sums are integers) — and the leaf
    each row is handed back in the caller's order: the carried sum of
    leaf values is bitwise the same too. Each of the eight shards
    orders its own rows."""
    import jax

    from h2o_kubernetes_tpu.models.tree import core

    args = _forest_args(mesh8, 8 * 520, 3, 512, 10, 2, seed=5)
    tp = args[6]
    out = {}
    for form, cost in (("blocked", float("inf")), ("compacted", 0.0)):
        monkeypatch.setattr(core, "_ORDER_NS", cost)
        monkeypatch.setattr(core, "_ARRAY_NS", 0.0)
        assert core.hist_level_forms(tp, 3) == \
            ["fact"] * 8 + [form] * 2
        assert core.compact_depth(tp, 3) == (
            None if form == "blocked" else 8)
        jax.clear_caches()
        out[form] = jax.device_get(core._boost_jit(*args))
    jax.clear_caches()
    (m0, t0), (m1, t1) = out["blocked"], out["compacted"]
    assert t0.is_split.sum() > 40
    for name in t0._fields:
        np.testing.assert_array_equal(getattr(t1, name),
                                      getattr(t0, name), err_msg=name)
    np.testing.assert_array_equal(m1, m0)


# each cell's tree: (max_depth, matrix bins, histogram columns,
# unit_hess, under the class batch's vmap) -> its levels' forms
_CELL_TREES = {
    "gbm-higgs.train": ((6, 256, 28, False, False), ["fact"] * 6),
    "xgb-mslr.train": ((8, 256, 136, False, False), ["fact"] * 8),
    "xgb-covtype.train": ((6, 256, 54, False, True), ["fact"] * 6),
    "drf-higgs.train": ((12, 64, 28, True, False),
                        ["fact"] * 11 + ["blocked"]),
    # two levels of 2 and 4 blocks spare less than the order costs
    "gbm-airline.train": ((10, 512, 8, False, False),
                          ["fact"] * 8 + ["blocked"] * 2),
    "drf-airline.train": ((12, 512, 8, True, False),
                          ["fact"] * 8 + ["compacted"] * 4),
    # the class batch past one hi block: never ordered (the codes are
    # shared by the K classes)
    "k_class_depth_10": ((10, 512, 8, False, True),
                         ["fact"] * 8 + ["blocked"] * 2),
}


@pytest.mark.parametrize("cell", sorted(_CELL_TREES))
def test_the_cells_levels_by_form(cell):
    """THE rule (`core.compact_depth`) at each cell's tree on the
    kernel: a level past one hi block runs over rows ordered by node
    block where the blocks it spares cost more than the order (the
    costs measured alone on a v5e, PERF.md section 3), never under the
    class batch; a tree orders its rows at the first such level (the
    deepest level's block depth is shallower in every cell)."""
    from h2o_kubernetes_tpu.models.tree import core

    (depth, bins, F, unit, batched), want = _CELL_TREES[cell]
    tp = core.TreeParams(max_depth=depth, n_bins=bins, unit_hess=unit,
                         hist_impl="pallas")
    assert core.hist_level_forms(tp, F, batched) == want
    assert core.compact_depth(tp, F, batched) == (
        want.index("compacted") if "compacted" in want else None)
    # on the segment sum there is nothing to spare
    assert "compacted" not in core.hist_level_forms(
        tp._replace(hist_impl="segment"), F, batched)
