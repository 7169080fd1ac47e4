"""Exclusive Feature Bundling (models/tree/efb.py) — tier-1.

Parity discipline (same as PR 5's fused-binning tests): with zero
bundle conflicts the bundled path must produce IDENTICAL splits and
bitwise-identical predictions.  Full bitwise equality (values, gains,
covers, flat artifacts, predictions) is asserted on exact-sum fixtures
— a DRF forest on a 0/1 response (dyadic gradients every tree) and a
single gaussian round on a dyadic response — where the default-bin
remainder reconstruction is exactly associative; multi-round bernoulli
asserts identical structure per-round-1 plus float-tolerance
predictions (the ooc.py chunk-boundary caveat, documented in
docs/SCALING.md "Wide sparse frames").
"""

import os

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models import DRF, GBM
from h2o_kubernetes_tpu.models.tree import efb as E
from h2o_kubernetes_tpu.models.tree.binning import apply_bins_jit, fit_bins


def _wide_frame(n=4096, n_groups=6, card=8, seed=0, with_na=True,
                with_enum=True, dyadic_y=True):
    """One-hot groups (mutually exclusive within a group) + dense
    numerics + an enum sparse column + NAs: the rich EFB fixture."""
    rng = np.random.default_rng(seed)
    cols = {}
    cats = []
    for g in range(n_groups):
        cat = rng.integers(0, card, size=n)
        cats.append(cat)
        for k in range(card):
            v = (cat == k).astype(np.float32)
            if with_na and g == 0 and k == 0:
                v[::37] = np.nan
            cols[f"g{g}_{k}"] = v
    cols["d0"] = rng.normal(size=n).astype(np.float32)
    cols["d1"] = rng.gamma(2.0, 1.0, size=n).astype(np.float32)
    domains = {}
    if with_enum:
        e = rng.integers(0, 3, size=n).astype(np.float32)
        e[rng.random(n) > 0.06] = 0.0
        if with_na:
            e[1::53] = np.nan
        cols["e0"] = e
        domains["e0"] = ["a", "b", "c"]
    if dyadic_y:
        # y in {0, 1} and n a power of two: the gaussian prior and the
        # first-round gradients are dyadic, every histogram sum exact
        y = ((cats[0] == 1) | ((cols["d0"] > 0) & (cats[1] == 2)))
        cols["y"] = y.astype(np.float32)
    else:
        cols["y"] = (cols["d0"] + (cats[0] == 1)
                     - (cats[1] == 2)).astype(np.float32)
    return h2o.Frame.from_arrays(cols, domains=domains)


def _masked_tree_fields(trees):
    isp = np.asarray(trees.is_split)
    out = {"is_split": isp}
    for f in ("split_feat", "split_bin", "na_left"):
        out[f] = np.where(isp, np.asarray(getattr(trees, f)), -9)
    for f in ("value", "gain", "cover"):
        out[f] = np.asarray(getattr(trees, f))
    return out


def _assert_trees_equal(ta, tb, bitwise_leaves=True):
    a, b = _masked_tree_fields(ta), _masked_tree_fields(tb)
    for f in ("is_split", "split_feat", "split_bin", "na_left"):
        assert np.array_equal(a[f], b[f]), f"{f} differs"
    if bitwise_leaves:
        for f in ("value", "gain", "cover"):
            assert np.array_equal(a[f], b[f]), f"{f} differs"


def _train(algo_cls, env, fr, **kw):
    old = os.environ.get("H2O_TPU_EFB")
    os.environ["H2O_TPU_EFB"] = env
    try:
        return algo_cls(**kw).train(y="y", training_frame=fr)
    finally:
        if old is None:
            os.environ.pop("H2O_TPU_EFB", None)
        else:
            os.environ["H2O_TPU_EFB"] = old


class TestBundlePlan:
    def test_plan_exclusive_sets_and_decode(self):
        """Every bundle's members are mutually exclusive on the data,
        and the LUT decode of the bundled matrix reproduces the
        original bin code of EVERY (row, feature) — the invariant the
        grower's row descent rides."""
        fr = _wide_frame()
        names = [n for n in fr.names if n != "y"]
        os.environ["H2O_TPU_EFB"] = "1"
        try:
            spec = fit_bins(fr, names)
            plan = E.plan_bundles(fr, spec)
        finally:
            os.environ.pop("H2O_TPU_EFB", None)
        assert plan is not None and plan.fb < len(names)
        assert plan.conflicts == 0
        import jax.numpy as jnp

        full = np.asarray(apply_bins_jit(
            fr.to_matrix(names), jnp.asarray(spec.edges_matrix()),
            jnp.asarray(np.array(spec.is_enum)),
            spec.na_bin))[: fr.nrows]
        B = spec.n_bins
        luts = plan.device_luts()
        feat_col = np.asarray(luts.feat_col)
        slot_feat = np.asarray(luts.slot_feat)
        slot_bin = np.asarray(luts.slot_bin)
        feat_default = np.asarray(luts.feat_default)
        bundled = plan.binned_host[: fr.nrows]
        # decode every feature back through the LUTs
        for f in range(len(names)):
            s = bundled[:, feat_col[f]]
            sf, sb = slot_feat[feat_col[f], s], slot_bin[feat_col[f], s]
            decoded = np.where(sf == f, sb, feat_default[f])
            assert np.array_equal(decoded, full[:, f]), names[f]
        # mutual exclusivity: inside a bundle, at most one member
        # non-default per row
        for kind, payload in plan.cols:
            if kind != "bundle":
                continue
            nnd = np.zeros(fr.nrows, dtype=np.int64)
            for m in payload:
                nnd += (full[:, m.feat] != m.default_bin)
            assert int(nnd.max()) <= 1
        # bundles never use bin B-1 (the node-total formula relies on
        # it) and per-member slots are contiguous ascending bins
        assert bundled.max() <= B - 2 or any(
            k == "pass" for k, _ in plan.cols)

    def test_conflict_budget(self, monkeypatch):
        """Budget 0 keeps overlapping features apart; a generous
        budget bundles them with first-member-wins resolution."""
        n = 2048
        rng = np.random.default_rng(1)
        a = (rng.random(n) < 0.05).astype(np.float32)
        b = (rng.random(n) < 0.05).astype(np.float32)
        both = (a > 0) & (b > 0)
        assert both.sum() > 0          # real conflicts exist
        cols = {"a": a, "b": b,
                "c": (rng.random(n) < 0.04).astype(np.float32),
                "y": (a + rng.normal(size=n)).astype(np.float32)}
        fr = h2o.Frame.from_arrays(cols)
        names = ["a", "b", "c"]
        spec = fit_bins(fr, names)
        monkeypatch.setenv("H2O_TPU_EFB", "1")
        monkeypatch.setenv("H2O_TPU_EFB_CONFLICT", "0")
        p0 = E.plan_bundles(fr, spec)
        for kind, payload in (p0.cols if p0 else []):
            if kind == "bundle":
                feats = {m.feat for m in payload}
                assert not {0, 1} <= feats      # a+b never together
        monkeypatch.setenv("H2O_TPU_EFB_CONFLICT", "0.5")
        p1 = E.plan_bundles(fr, spec)
        assert p1 is not None
        together = any(kind == "bundle" and
                       {0, 1} <= {m.feat for m in payload}
                       for kind, payload in p1.cols)
        assert together
        assert p1.conflicts > 0

    def test_kill_switch_and_auto_gate(self, monkeypatch):
        """H2O_TPU_EFB=0 never plans; auto skips narrow frames."""
        fr = _wide_frame(n=1024, n_groups=2, card=4)
        names = [nm for nm in fr.names if nm != "y"]
        monkeypatch.setenv("H2O_TPU_EFB", "0")
        assert not E.efb_eligible(len(names), None)
        monkeypatch.setenv("H2O_TPU_EFB", "auto")
        assert not E.efb_eligible(11, None)      # < MIN_F floor
        assert E.efb_eligible(64, None)
        assert not E.efb_eligible(64, object())  # checkpoint blocked


class TestZeroConflictParity:
    def test_drf_forest_bitwise(self):
        """DRF on a 0/1 response: dyadic gradients for EVERY tree, so
        the full forest — splits, leaf values, gains, covers, flat
        artifacts, predictions — is bitwise-identical bundled vs
        unbundled, NAs + enums + per-node mtries included."""
        fr = _wide_frame()
        kw = dict(ntrees=8, max_depth=5, seed=3, mtries=10)
        m_b = _train(DRF, "1", fr, **kw)
        m_u = _train(DRF, "0", fr, **kw)
        _assert_trees_equal(m_b.trees, m_u.trees)
        # flat serving artifacts (the MOJO-v2 wire format) bitwise
        fa, fb_ = m_b._flat(), m_u._flat()
        for x, yv in zip(fa, fb_):
            assert np.array_equal(np.asarray(x), np.asarray(yv))
        X = m_b._design_matrix(fr)
        assert np.array_equal(np.asarray(m_b._margins(X)),
                              np.asarray(m_u._margins(X)))
        assert np.array_equal(np.asarray(m_b.predict_raw(fr)),
                              np.asarray(m_u.predict_raw(fr)))

    def test_gbm_gaussian_single_round_bitwise(self):
        """One gaussian round on a dyadic response: every histogram
        sum is exact, so bundled == unbundled to the last bit."""
        fr = _wide_frame(dyadic_y=True)
        kw = dict(ntrees=1, max_depth=6, seed=1, distribution="gaussian")
        m_b = _train(GBM, "1", fr, **kw)
        m_u = _train(GBM, "0", fr, **kw)
        _assert_trees_equal(m_b.trees, m_u.trees)
        assert np.array_equal(np.asarray(m_b.predict_raw(fr)),
                              np.asarray(m_u.predict_raw(fr)))

    def test_gbm_bernoulli_multiround_structure(self):
        """Multi-round bernoulli: non-dyadic gradients make the
        remainder reconstruction reassociate f32 sums, so the contract
        is identical split STRUCTURE modulo exact-gain ties and
        float-tolerance predictions (the documented ooc.py-style
        caveat)."""
        fr = _wide_frame(seed=5)
        kw = dict(ntrees=3, max_depth=4, seed=2)
        m_b = _train(GBM, "1", fr, **kw)
        m_u = _train(GBM, "0", fr, **kw)
        p_b = np.asarray(m_b.predict_raw(fr))
        p_u = np.asarray(m_u.predict_raw(fr))
        assert np.allclose(p_b, p_u, atol=1e-5)
        # round 1 is exact-sum-free of margins only in its argmax
        # inputs' magnitudes — still assert the first tree's structure
        isp_b = np.asarray(m_b.trees.is_split)[0]
        isp_u = np.asarray(m_u.trees.is_split)[0]
        assert np.array_equal(isp_b, isp_u)

    def test_multinomial_parity(self):
        """K-class trees ride the same bundled grower via vmap.

        The classes depend on the features (10% label noise): with
        labels drawn independently of them — this fixture until PR 22
        — every true gain is 0 and what the split search ranks and
        thresholds is rounding residue (gains of 1–2 ulp, 6.1e-5),
        which the bundled and unbundled layouts, summing the same
        terms in different orders, need not agree on."""
        fr = _wide_frame(seed=7, dyadic_y=True)
        rng = np.random.default_rng(7)
        cols = {nm: fr.vec(nm).to_numpy() for nm in fr.names
                if nm != "y"}
        y3 = np.where(cols["g0_1"] == 1, 0,
                      np.where(cols["g1_2"] == 1, 1, 2))
        flip = rng.random(fr.nrows) < 0.1
        y3[flip] = rng.integers(0, 3, size=flip.sum())
        cols["y"] = y3.astype(np.float32)
        fr3 = h2o.Frame.from_arrays(
            cols, domains={"y": ["a", "b", "c"],
                           "e0": ["a", "b", "c"]})
        kw = dict(ntrees=2, max_depth=3, seed=4)
        m_b = _train(GBM, "1", fr3, **kw)
        m_u = _train(GBM, "0", fr3, **kw)
        isp_b = np.asarray(m_b.trees.is_split)
        isp_u = np.asarray(m_u.trees.is_split)
        assert np.array_equal(isp_b, isp_u)
        assert np.allclose(np.asarray(m_b.predict_raw(fr3)),
                           np.asarray(m_u.predict_raw(fr3)), atol=1e-5)


class TestOocParity:
    def test_ooc_bundled_bitwise(self, monkeypatch):
        """Out-of-core chunk streaming over the BUNDLED layout:
        bitwise vs the in-HBM bundled path AND vs fully-unbundled on
        an exact-sum fixture (single gaussian round, dyadic y)."""
        fr = _wide_frame(n=4096, dyadic_y=True)
        kw = dict(ntrees=1, max_depth=4, seed=1,
                  distribution="gaussian")
        monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "1024")
        monkeypatch.setenv("H2O_TPU_OOC", "1")
        m_ooc = _train(GBM, "1", fr, **kw)
        monkeypatch.setenv("H2O_TPU_OOC", "0")
        m_hbm = _train(GBM, "1", fr, **kw)
        m_ref = _train(GBM, "0", fr, **kw)
        _assert_trees_equal(m_ooc.trees, m_hbm.trees)
        _assert_trees_equal(m_ooc.trees, m_ref.trees)
        p = [np.asarray(m.predict_raw(fr)) for m in
             (m_ooc, m_hbm, m_ref)]
        assert np.array_equal(p[0], p[1])
        assert np.array_equal(p[0], p[2])


class TestServingUntouched:
    def test_artifact_roundtrip_and_binned_scorer(self, tmp_path):
        """A bundled-trained model's MOJO artifact + legacy binned
        scorer work exactly like an unbundled model's — serving never
        sees a bundle."""
        fr = _wide_frame(dyadic_y=True)
        m = _train(GBM, "1", fr, ntrees=2, max_depth=4, seed=1,
                   distribution="gaussian")
        X = m._design_matrix(fr)
        assert np.array_equal(np.asarray(m._margins(X)),
                              np.asarray(m._margins_binned(X)))
        from h2o_kubernetes_tpu.mojo import export_mojo, import_mojo

        path = str(tmp_path / "m.mojo")
        export_mojo(m, path)
        m2 = import_mojo(path)
        assert np.allclose(
            np.asarray(m2.predict(fr)),
            np.asarray(m.predict_raw(fr))[: fr.nrows], atol=0)
