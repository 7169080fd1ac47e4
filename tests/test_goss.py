"""GOSS gradient-based one-side sampling through the training stack
(ISSUE 13, arXiv:1809.04559; docs/SCALING.md "Gradient-based
sampling"):

- kill-switch bitwise parity: H2O_TPU_GOSS=0 (and unset) trace the
  exact pre-GOSS program — identical trees and predictions;
- the a+b=1 identity: with the whole row set kept at amplification 1
  the masking + compaction + full-row re-descent plumbing must be
  provably NEUTRAL — bitwise-equal to unsampled training end to end;
- seeded determinism: the per-row (round key, global row id) hash
  draws are reproducible run to run;
- amplified-weight gain unbiasedness on an exact-sum fixture: the
  trained root split/gain equals a host recomputation from explicitly
  factor-amplified histograms (dyadic gradients, dyadic (1-a)/b
  amplification — every sum exact, any deviation is a bug);
- EFB + GOSS composition: bundled vs unbundled training with sampling
  on stays bitwise on the zero-conflict exact fixture;
- ooc-chunk path equivalence vs in-HBM at the same seed: the
  layout-invariant selection rule picks the SAME rows, so the streamed
  model is bitwise-equal where sums are exact (single round) and
  float-close in general — the same contract test_chunked_path pins
  for unsampled ooc;
- the AUC-parity gate: |ΔAUC| <= 0.002 vs unsampled at matched tree
  count on the 100k airlines shape (a=0.1, b=0.1);
- DRF stays bagged/unsampled; knob validation; CV folds and the
  compile-ahead mirror ride along.
"""

import os

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models import DRF, GBM, XGBoost
from tools import datasets as D

GOSS_KEYS = ("H2O_TPU_GOSS", "H2O_TPU_GOSS_TOP_A", "H2O_TPU_GOSS_RAND_B")


def _set_goss(monkeypatch, on: bool, a: str | None = None,
              b: str | None = None):
    for k in GOSS_KEYS:
        monkeypatch.delenv(k, raising=False)
    if on:
        monkeypatch.setenv("H2O_TPU_GOSS", "1")
        if a is not None:
            monkeypatch.setenv("H2O_TPU_GOSS_TOP_A", a)
        if b is not None:
            monkeypatch.setenv("H2O_TPU_GOSS_RAND_B", b)


def _tree_arrays(m):
    import jax

    return [np.asarray(a) for a in jax.tree.flatten(m.trees)[0]]


def _assert_trees_equal(m1, m2):
    for a, b in zip(_tree_arrays(m1), _tree_arrays(m2)):
        np.testing.assert_array_equal(a, b)


def _bern_frame(n=4096, seed=0, F=6):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = np.where(X[:, 0] + 0.5 * X[:, 1] +
                 rng.normal(scale=0.5, size=n) > 0, "p", "n")
    cols = {f"f{i}": X[:, i] for i in range(F)}
    cols["y"] = y
    return h2o.Frame.from_arrays(cols)


def _exact_gaussian_frame(n=4096, seed=11, F=5):
    """y ∈ {0,1} exactly even: init is exactly 0.5, round-1 gradients
    are ±0.5, and with a dyadic amplification every histogram partial
    sum is exactly representable — association order cannot change a
    bit (the test_chunked_path construction)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = np.zeros(n, dtype=np.float32)
    y[rng.permutation(n)[: n // 2]] = 1.0
    cols = {f"f{i}": X[:, i] for i in range(F)}
    cols["y"] = y
    return h2o.Frame.from_arrays(cols)


# amp = (1-a)/b = 2 — dyadic, so amplified sums stay exact
DYADIC_A, DYADIC_B = "0.5", "0.25"


def test_kill_switch_bitwise(mesh8, monkeypatch):
    """H2O_TPU_GOSS=0 and the unset default must produce identical
    trees (the off path traces byte-identically to a build without the
    feature), and a sampled config must actually differ."""
    fr = _bern_frame()
    _set_goss(monkeypatch, False)
    m_def = GBM(ntrees=4, max_depth=4, seed=3).train(
        y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_GOSS", "0")
    m_kill = GBM(ntrees=4, max_depth=4, seed=3).train(
        y="y", training_frame=fr)
    _assert_trees_equal(m_def, m_kill)
    np.testing.assert_array_equal(m_def.predict_raw(fr),
                                  m_kill.predict_raw(fr))
    _set_goss(monkeypatch, True, "0.2", "0.2")
    m_on = GBM(ntrees=4, max_depth=4, seed=3).train(
        y="y", training_frame=fr)
    assert not all(np.array_equal(a, b) for a, b in
                   zip(_tree_arrays(m_def), _tree_arrays(m_on)))


def test_identity_when_a_plus_b_covers_all_rows(mesh8, monkeypatch):
    """a=0.5, b=0.5: every row is kept at amplification (1-a)/b = 1,
    so GOSS-on must be BITWISE-equal to unsampled training — the
    structural proof that masking, static-cap compaction and the
    full-row re-descent margin update are neutral plumbing."""
    fr = _bern_frame(seed=5)
    _set_goss(monkeypatch, False)
    m_off = GBM(ntrees=5, max_depth=4, seed=2).train(
        y="y", training_frame=fr)
    _set_goss(monkeypatch, True, "0.5", "0.5")
    m_id = GBM(ntrees=5, max_depth=4, seed=2).train(
        y="y", training_frame=fr)
    _assert_trees_equal(m_off, m_id)
    np.testing.assert_array_equal(m_off.predict_raw(fr),
                                  m_id.predict_raw(fr))


def test_seeded_determinism(mesh8, monkeypatch):
    """Two runs at one seed draw identical keep patterns (the hashed
    (round key, global row id) stream); a different seed differs."""
    fr = _bern_frame(seed=1)
    _set_goss(monkeypatch, True, "0.2", "0.3")
    kw = dict(ntrees=4, max_depth=4)
    m1 = GBM(seed=9, **kw).train(y="y", training_frame=fr)
    m2 = GBM(seed=9, **kw).train(y="y", training_frame=fr)
    _assert_trees_equal(m1, m2)
    np.testing.assert_array_equal(m1.predict_raw(fr),
                                  m2.predict_raw(fr))
    m3 = GBM(seed=10, **kw).train(y="y", training_frame=fr)
    assert not all(np.array_equal(a, b) for a, b in
                   zip(_tree_arrays(m1), _tree_arrays(m3)))


def test_amplified_gain_unbiasedness_exact(mesh8, monkeypatch):
    """The unbiasedness contract, pinned exactly: recompute the GOSS
    factors host-side through the SAME shared helpers (goss_round_keys
    → threshold → per-row factor on global row ids), build the
    explicitly (1-a)/b-amplified root histogram with numpy adds, and
    the trained tree's root (feature, bin, gain, cover) must match a
    fresh _find_splits over it to the last bit — dyadic gradients
    (±0.5) and dyadic amplification (×2) make every sum exact."""
    import jax
    import jax.numpy as jnp

    from h2o_kubernetes_tpu.models.gbm import _make_tree_params
    from h2o_kubernetes_tpu.models.tree import core as C
    from h2o_kubernetes_tpu.models.tree.binning import apply_bins_jit

    fr = _exact_gaussian_frame()
    n = fr.nrows
    _set_goss(monkeypatch, True, DYADIC_A, DYADIC_B)
    m = GBM(ntrees=1, max_depth=2, distribution="gaussian", seed=6,
            min_rows=4.0).train(y="y", training_frame=fr)
    a, b = float(DYADIC_A), float(DYADIC_B)

    X = m._design_matrix(fr)
    binned = np.asarray(apply_bins_jit(
        X, m._edges, m._enum_mask, m.bin_spec.na_bin))
    padded = binned.shape[0]
    w = np.zeros(padded, dtype=np.float32)
    w[:n] = 1.0
    y = np.zeros(padded, dtype=np.float32)
    y[:n] = fr.vec("y").to_numpy()[:n]
    assert float(m.init_score) == 0.5          # exact even split
    g = np.float32(0.5) - y                    # margin0 - y, ±0.5

    # the reference factor stream — same helpers, global row ids
    kg = C.goss_round_keys(jax.random.key(6), 1)[0]
    absg = C.goss_rank_stat(jnp.asarray(g), jnp.asarray(w))
    live = jnp.asarray(w) > 0
    mmax = jnp.max(absg)
    counts, total = C.goss_local_counts(absg, live, mmax)
    T, frac = C.goss_threshold(counts, total, a)
    factor = np.asarray(C.goss_row_factor(
        absg, live, mmax, T, frac, kg,
        jnp.arange(padded, dtype=jnp.int32), a, b))
    assert set(np.unique(factor)).issubset({0.0, 1.0, 2.0})
    kept = float((factor > 0)[w > 0].mean())
    assert abs(kept - (a + b)) < 0.05          # expected a+b fraction

    # explicitly amplified root histogram (numpy, exact dyadic sums)
    w_amp = w * factor
    F, B = binned.shape[1], m.params.nbins
    hist = np.zeros((1, F, B, 3), dtype=np.float32)
    for f in range(F):
        np.add.at(hist[0, f], binned[:, f],
                  np.stack([g * w_amp, w_amp, w_amp], axis=1))
    tp = _make_tree_params(m.params, "gaussian")
    feat, bin_, _, can, _, gain, cover, _, _, _ = C._find_splits(
        jnp.asarray(hist), tp)
    assert bool(can[0])
    assert int(m.trees.split_feat[0, 0]) == int(feat[0])
    assert int(m.trees.split_bin[0, 0]) == int(bin_[0])
    assert float(m.trees.gain[0, 0]) == float(gain[0])
    assert float(m.trees.cover[0, 0]) == float(cover[0])


def test_efb_goss_composition(mesh8, monkeypatch):
    """Bundled vs unbundled training with GOSS ON: the sampling factor
    depends only on gradients (identical both ways), so the EFB
    exactness contract carries through — identical splits, bitwise
    predictions on the zero-conflict exact fixture."""
    rng = np.random.default_rng(4)
    ne = 4096
    ecols = {}
    cat_e = rng.integers(0, 16, size=(4, ne))
    for gi in range(4):
        for k in range(16):
            ecols[f"c{gi}_{k}"] = (cat_e[gi] == k).astype(np.float32)
    ecols["dx"] = rng.normal(size=ne).astype(np.float32)
    ecols["ye"] = ((cat_e[0] == 1).astype(np.float32) - (cat_e[1] == 2)
                   + (ecols["dx"] > 0)).astype(np.float32)
    fr_e = h2o.Frame.from_arrays(ecols)
    _set_goss(monkeypatch, True, DYADIC_A, DYADIC_B)

    def _leg(env):
        monkeypatch.setenv("H2O_TPU_EFB", env)
        try:
            return GBM(ntrees=1, max_depth=4, seed=0).train(
                y="ye", training_frame=fr_e)
        finally:
            monkeypatch.delenv("H2O_TPU_EFB", raising=False)

    m_b = _leg("1")
    m_u = _leg("0")
    isp = np.asarray(m_u.trees.is_split)
    np.testing.assert_array_equal(isp, np.asarray(m_b.trees.is_split))
    for fld in ("split_feat", "split_bin", "na_left"):
        np.testing.assert_array_equal(
            np.where(isp, np.asarray(getattr(m_u.trees, fld)), -9),
            np.where(isp, np.asarray(getattr(m_b.trees, fld)), -9),
            err_msg=fld)
    np.testing.assert_array_equal(np.asarray(m_u.predict_raw(fr_e)),
                                  np.asarray(m_b.predict_raw(fr_e)))


def test_ooc_matches_in_hbm_same_seed(mesh8, monkeypatch):
    """The streamed chunk grid selects the SAME rows as the fused
    in-HBM layout at one seed (layout-invariant threshold + per-row
    hash): bitwise-equal trees/predictions on the single exact-sum
    round, float-close over multiple rounds (the chunk-boundary
    reassociation caveat, same as unsampled ooc)."""
    fr = _exact_gaussian_frame(seed=13)
    _set_goss(monkeypatch, True, DYADIC_A, DYADIC_B)
    kw = dict(ntrees=1, max_depth=3, distribution="gaussian", seed=3,
              min_rows=4.0)
    monkeypatch.setenv("H2O_TPU_OOC", "0")
    m_hbm = GBM(**kw).train(y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    monkeypatch.setenv("H2O_TPU_OOC_CHUNK_ROWS", "1024")
    m_ooc = GBM(**kw).train(y="y", training_frame=fr)
    _assert_trees_equal(m_hbm, m_ooc)
    np.testing.assert_array_equal(m_hbm.predict_raw(fr),
                                  m_ooc.predict_raw(fr))
    # multi-round: general f32 gradients → tolerance, like unsampled
    kw2 = dict(ntrees=4, max_depth=3, distribution="gaussian", seed=3)
    monkeypatch.setenv("H2O_TPU_OOC", "0")
    m_h2 = GBM(**kw2).train(y="y", training_frame=fr)
    monkeypatch.setenv("H2O_TPU_OOC", "1")
    m_o2 = GBM(**kw2).train(y="y", training_frame=fr)
    p1, p2 = m_h2.predict_raw(fr), m_o2.predict_raw(fr)
    assert np.allclose(p1, p2, atol=2e-3), np.abs(p1 - p2).max()
    # streamed vs resident chunks stay bitwise with GOSS on
    monkeypatch.setenv("H2O_TPU_OOC_RESIDENT", "1")
    m_res = GBM(**kw2).train(y="y", training_frame=fr)
    monkeypatch.delenv("H2O_TPU_OOC_RESIDENT", raising=False)
    _assert_trees_equal(m_o2, m_res)


def test_auc_parity_100k_airlines(mesh8, monkeypatch):
    """The acceptance gate: |ΔAUC| <= 0.002 vs unsampled at matched
    tree count on the 100k airlines shape with the default a=0.1,
    b=0.1 — the sampled model must not trade measurable accuracy for
    its 3-5× histogram-row reduction."""
    fr = D.airlines_frame(100_000, seed=7)

    def _leg(on: bool):
        _set_goss(monkeypatch, on, "0.1", "0.1")
        return GBM(ntrees=10, max_depth=5, nbins=64, learn_rate=0.2,
                   seed=1).train(y="IsDepDelayed", training_frame=fr)

    auc_off = _leg(False).scoring_history[-1]["train_auc"]
    auc_on = _leg(True).scoring_history[-1]["train_auc"]
    assert auc_off > 0.7                     # the model actually fits
    assert abs(auc_off - auc_on) <= 0.002, (auc_off, auc_on)


def test_drf_stays_bagged(mesh8, monkeypatch):
    """DRF ignores the GOSS knobs entirely (trees vote from bootstrap
    bags — there is no gradient to rank by)."""
    fr = _bern_frame(n=2048, seed=8)
    _set_goss(monkeypatch, True, "0.1", "0.1")
    m_on = DRF(ntrees=4, max_depth=3, seed=2).train(
        y="y", training_frame=fr)
    _set_goss(monkeypatch, False)
    m_off = DRF(ntrees=4, max_depth=3, seed=2).train(
        y="y", training_frame=fr)
    _assert_trees_equal(m_on, m_off)


def test_multinomial_and_xgboost_goss(mesh8, monkeypatch):
    """K-class rounds share ONE GOSS draw (rows ranked by the class-L1
    gradient norm) and stay deterministic; XGBoost-hist rides the same
    stack and its sampled model differs from unsampled."""
    rng = np.random.default_rng(2)
    n = 2048
    x = rng.normal(size=n).astype(np.float32)
    y3 = np.where(x > 0.5, "a", np.where(x < -0.5, "b", "c"))
    fr3 = h2o.Frame.from_arrays(
        {"x": x, "x2": rng.normal(size=n).astype(np.float32), "y": y3})
    _set_goss(monkeypatch, True, "0.2", "0.3")
    m1 = GBM(ntrees=3, max_depth=3, seed=0).train(
        y="y", training_frame=fr3)
    m2 = GBM(ntrees=3, max_depth=3, seed=0).train(
        y="y", training_frame=fr3)
    _assert_trees_equal(m1, m2)
    assert m1.ntrees == 9                   # 3 rounds x 3 class trees
    fr = _bern_frame(n=2048, seed=3)
    mx_on = XGBoost(ntrees=3, max_depth=4, seed=1).train(
        y="y", training_frame=fr)
    _set_goss(monkeypatch, False)
    mx_off = XGBoost(ntrees=3, max_depth=4, seed=1).train(
        y="y", training_frame=fr)
    assert not all(np.array_equal(a, b) for a, b in
                   zip(_tree_arrays(mx_on), _tree_arrays(mx_off)))


def test_knob_validation(mesh8, monkeypatch):
    """Bad knobs and the sample_rate conflict fail loudly up front."""
    fr = _bern_frame(n=512, seed=4)
    _set_goss(monkeypatch, True, "0.9", "0.5")    # a + b > 1
    with pytest.raises(ValueError, match="GOSS"):
        GBM(ntrees=1, max_depth=2, seed=0).train(
            y="y", training_frame=fr)
    _set_goss(monkeypatch, True, "0.1", "0")      # b must be > 0
    with pytest.raises(ValueError, match="GOSS"):
        GBM(ntrees=1, max_depth=2, seed=0).train(
            y="y", training_frame=fr)
    _set_goss(monkeypatch, True)
    with pytest.raises(ValueError, match="sample_rate"):
        GBM(ntrees=1, max_depth=2, seed=0, sample_rate=0.8).train(
            y="y", training_frame=fr)


def test_compaction_overflow_warns(mesh8, monkeypatch, caplog):
    """A frame whose row order clusters the high-gradient rows into
    one shard overflows the static compaction capacity — the dropped
    contributions must surface as a LOUD warning (never silent), and
    training must still complete. A shuffled layout with the same
    knobs must not warn."""
    import logging

    n = 4096
    rng = np.random.default_rng(3)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = np.zeros(n, dtype=np.float32)
    y[3584:] = 10.0        # all the |g| mass in the LAST shard's rows
    cols = {f"f{i}": X[:, i] for i in range(4)}
    cols["y"] = y
    fr = h2o.Frame.from_arrays(cols)
    _set_goss(monkeypatch, True, "0.1", "0.05")
    with caplog.at_level(logging.WARNING, logger="h2o_kubernetes_tpu"):
        m = GBM(ntrees=1, max_depth=3, distribution="gaussian",
                seed=1).train(y="y", training_frame=fr)
    assert m.ntrees == 1
    assert any("GOSS compaction overflow" in r.message
               for r in caplog.records)
    caplog.clear()
    perm = rng.permutation(n)
    cols2 = {f"f{i}": X[perm, i] for i in range(4)}
    cols2["y"] = y[perm]
    fr2 = h2o.Frame.from_arrays(cols2)
    with caplog.at_level(logging.WARNING, logger="h2o_kubernetes_tpu"):
        GBM(ntrees=1, max_depth=3, distribution="gaussian",
            seed=1).train(y="y", training_frame=fr2)
    assert not any("GOSS compaction overflow" in r.message
                   for r in caplog.records)


def test_cv_and_compile_ahead_ride_along(mesh8, monkeypatch):
    """CV folds inherit the knob (each fold trains sampled) and the
    compile-ahead mirror pre-lowers the GOSS dispatch shape — the
    (round keys, goss keys) operand pair — without error."""
    fr = _bern_frame(n=2048, seed=6)
    _set_goss(monkeypatch, True, "0.2", "0.2")
    m = GBM(ntrees=3, max_depth=3, seed=1, nfolds=2,
            fold_assignment="modulo").train(y="y", training_frame=fr)
    assert np.isfinite(m.cross_validation_metrics()["auc"])
    est = GBM(ntrees=3, max_depth=3, seed=1)
    thunks = est.compile_ahead_lowerings("y", fr)
    assert thunks
    thunks[0]()        # the mirrored AOT shape must lower + compile
    # GOSS + sample_rate conflict returns no thunks (train() raises)
    est2 = GBM(ntrees=3, max_depth=3, seed=1, sample_rate=0.5)
    assert est2.compile_ahead_lowerings("y", fr) == []
