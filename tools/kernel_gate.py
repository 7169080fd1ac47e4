"""TPU kernel-compile gate — run on the chip, next to chip_smoke.py.

CPU CI can only exercise the Pallas kernels in interpret mode
(`ops/histogram._interpret`), and tests/test_chip_compile.py only
COMPILES them for a described chip; neither runs Mosaic's output.
This script does, on a TPU:

1. pallas-compiles the FACTORIZED histogram kernel (interpret=False is
   automatic on tpu) at a bench-like shape and asserts parity vs the
   segment_sum reference path, at the shallow levels too (1 to 14
   nodes x 256 bins at 28 columns and the 4,096-row tile; the op-alone
   timings of the kernel's forms are `tools/hist_forms.py`'s);
2. same for levels past the hi-block cap, which the same kernel
   serves in two and in four blocks of hi slots (`hist_blocked`), and
   the TreeSHAP serving kernel
   (`ops/shap_kernel.py`, to 1e-5 of the lowered-XLA
   `flat_shap_tab`, bitwise reported);
3. jit-compiles and runs the fused boost scan (binomial AND
   multinomial) end to end on small shapes.

Checks are NAMED and individually selectable: `--check NAME` (repeat
or comma-separate) runs just those — iterating one kernel's parity
without the full sweep — and `--list` prints the names. The `N/N PASS`
summary counts only what RAN, and a filtered run says so in the JSON
(`"filtered": [...]`) so a 2/2 can't masquerade as the full gate.

A check that raises is that check's failure (its error is in the
JSON); the others still run. Prints one JSON line
{"gate": "pass"|"fail", ...} LAST on stdout; exit code 0 on pass.
Without a TPU it exits non-zero before any check (interpret-mode
parity is tier-1's job, tests/test_histogram.py and
tests/test_shap_kernel.py).

Usage: python tools/kernel_gate.py [--check NAME ...] [--list]
"""

import argparse
import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CHECK_NAMES = [
    "fact_kernel", "fact_kernel_cap", "fact_kernel_shallow",
    "binblock_kernel",
    "binblock_kernel_4",
    "leaf_totals_kernel", "unit_hess_kernel",
    "boost_scan_binomial", "boost_scan_multinomial",
    "flat_scorer_parity", "flat_scorer_parity_multinomial",
    "shap_parity", "shap_kernel_parity", "efb_parity", "goss_parity",
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--check", action="append", default=None,
                    metavar="NAME",
                    help="run only this check (repeat or comma-"
                         "separate); default: all")
    ap.add_argument("--list", action="store_true",
                    help="print check names and exit")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(CHECK_NAMES))
        return 0
    selected = CHECK_NAMES
    if args.check:
        selected = [c.strip() for spec in args.check
                    for c in spec.split(",") if c.strip()]
        unknown = [c for c in selected if c not in CHECK_NAMES]
        if unknown:
            ap.error(f"unknown check(s) {unknown}; --list shows names")

    from h2o_kubernetes_tpu.runtime.backend import (
        enable_persistent_compile_cache, require_tpu)

    enable_persistent_compile_cache()
    require_tpu("kernel_gate")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from h2o_kubernetes_tpu.ops.histogram import (_FACT_MAX_NHI,
                                                  _hist_segment,
                                                  build_histogram,
                                                  expand_unit_hess)

    platform = jax.default_backend()
    rng = np.random.default_rng(0)
    checks = []

    def parity(name, rows, F, n_nodes, n_bins, tol=1e-5,
               unit_hess=False):
        binned = jnp.asarray(
            rng.integers(0, n_bins, size=(rows, F)).astype(np.uint8))
        rel = jnp.asarray(np.where(
            rng.uniform(size=rows) < 0.9,
            rng.integers(0, n_nodes, size=rows), -1).astype(np.int32))
        g = jnp.asarray(rng.normal(size=rows).astype(np.float32))
        h = jnp.asarray(rng.uniform(0.01, 1, size=rows).astype(
            np.float32))
        w = jnp.asarray((rng.uniform(size=rows) < 0.95).astype(
            np.float32))
        got = jax.jit(build_histogram, static_argnums=(5, 6, 7, 8))(
            binned, rel, g, h, w, n_nodes, n_bins, "pallas", unit_hess)
        live = (np.asarray(rel) >= 0) & (np.asarray(w) > 0)
        gw, hw = np.asarray(g) * np.asarray(w), np.asarray(h) * np.asarray(w)
        chans = [gw, np.asarray(w)] if unit_hess \
            else [gw, hw, np.asarray(w)]
        vals = np.where(live[:, None], np.stack(chans, axis=1), 0.0)
        want = _hist_segment(binned, jnp.where(jnp.asarray(live),
                                               rel, -1),
                             jnp.asarray(vals), n_nodes, n_bins)
        err = float(jnp.max(jnp.abs(got - jnp.asarray(want))) /
                    (jnp.max(jnp.abs(jnp.asarray(want))) + 1e-30))
        ok = err < tol
        checks.append({"check": name, "ok": ok, "rel_err": err})
        return ok

    # ---- shared lazy fixtures (built once, whichever checks run) ----
    import h2o_kubernetes_tpu as h2o
    from h2o_kubernetes_tpu.models import GBM

    _fix: dict = {}
    n = 4096
    x = rng.normal(size=n).astype(np.float32)

    def fix_binomial():
        """fr2/m2: tiny binomial GBM (boost_scan_binomial + goss)."""
        if "m2" not in _fix:
            y2 = np.where(x > 0, "p", "n")
            fr2 = h2o.Frame.from_arrays({"x": x, "y": y2})
            _fix["fr2"] = fr2
            _fix["m2"] = GBM(ntrees=3, max_depth=4, seed=0).train(
                y="y", training_frame=fr2)
        return _fix["fr2"], _fix["m2"]

    def fix_multinomial():
        """fr3/m3: tiny multinomial GBM (boost scan + flat scorer)."""
        if "m3" not in _fix:
            y3 = np.where(x > 0.5, "a",
                          np.where(x < -0.5, "b", "c"))
            fr3 = h2o.Frame.from_arrays({"x": x, "y": y3})
            _fix["fr3"] = fr3
            _fix["m3"] = GBM(ntrees=3, max_depth=3, seed=0).train(
                y="y", training_frame=fr3)
        return _fix["fr3"], _fix["m3"]

    def fix_rich():
        """frf/mf/Xf: NA + high-cardinality grouped-enum frame (flat
        scorer, shap_parity, shap_kernel_parity)."""
        if "mf" not in _fix:
            xna = x.copy()
            xna[::13] = np.nan
            gg = np.array([f"L{i}" for i in range(80)])[
                rng.integers(0, 80, size=n)]
            yf = np.where(np.nan_to_num(xna) > 0, "p", "n")
            frf = h2o.Frame.from_arrays({"x": xna, "g": gg, "y": yf})
            mf = GBM(ntrees=4, max_depth=4, nbins=64, seed=0).train(
                y="y", training_frame=frf)
            _fix["frf"], _fix["mf"] = frf, mf
            _fix["Xf"] = mf._design_matrix(frf)
        return _fix["frf"], _fix["mf"], _fix["Xf"]

    # ------------------------- checks --------------------------------

    def chk_fact_kernel():
        # one hi block: node·bins within 128·_FACT_MAX_NHI
        n_nodes_fact = 16
        assert -(-n_nodes_fact * 256 // 128) <= _FACT_MAX_NHI
        parity("fact_kernel", 100_000, 10, n_nodes_fact, 256)

    def chk_fact_kernel_cap():
        # one hi block AT the VMEM cap (n_hi == _FACT_MAX_NHI):
        # validates the [3·C·n_hi, T] stacked-term A fits VMEM on real
        # Mosaic, where interpret mode can't see allocation failures.
        # Every block of a deeper level has this shape's working set
        parity("fact_kernel_cap", 50_000, 2,
               _FACT_MAX_NHI * 128 // 256, 256)

    def chk_fact_kernel_shallow():
        # the shallow levels at the bench's width and row tile (4,096):
        # the root and 8 nodes x 256 bins, 14 (28 hi slots: not a power
        # of two), and 64 nodes x 64 bins x 2 channels (a forest's
        # level of 32 hi slots). Since PR 35 the lo one-hot
        # is held transposed and the product contracts the row axis of
        # both operands: Mosaic's lowering of that contraction, which
        # interpret mode does not run (`tools/hist_forms.py` times it
        # beside the form it replaced)
        for n_nodes, n_bins, unit in ((1, 256, False), (8, 256, False),
                                      (14, 256, False), (64, 64, True)):
            parity(f"fact_kernel_shallow[{n_nodes}x{n_bins}]", 100_000,
                   28, n_nodes, n_bins, unit_hess=unit)

    def chk_binblock_kernel():
        # twice the cap: the level is served in TWO blocks of hi slots
        # (`hist_blocked`), three channels
        n_nodes_deep = (_FACT_MAX_NHI * 128 // 256) * 2
        parity("binblock_kernel", 50_000, 4, n_nodes_deep, 256)

    def chk_binblock_kernel_4():
        # four hi blocks, two channels, 64 bins: one level past the
        # deepest of a depth-12 forest's tree
        parity("binblock_kernel_4", 50_000, 4,
               _FACT_MAX_NHI * 128 // 64 * 4, 64, unit_hess=True)

    def chk_leaf_totals_kernel():
        # single-bin totals shape (the final-level leaf reduction)
        parity("leaf_totals_kernel", 100_000, 1, 32, 1)

    def chk_unit_hess_kernel():
        # unit-hessian 2-channel kernel (gaussian/DRF fast path): must
        # compile on Mosaic and match the 3-channel build with h = 1
        rows_u, F_u, n_u, B_u = 100_000, 10, 16, 256
        binned_u = jnp.asarray(
            rng.integers(0, B_u, size=(rows_u, F_u)).astype(np.uint8))
        rel_u = jnp.asarray(rng.integers(0, n_u, size=rows_u).astype(
            np.int32))
        g_u = jnp.asarray(rng.normal(size=rows_u).astype(np.float32))
        w_u = jnp.asarray((rng.uniform(size=rows_u) < 0.95).astype(
            np.float32))
        ones_u = jnp.ones_like(w_u)
        want_u = jax.jit(build_histogram, static_argnums=(5, 6, 7))(
            binned_u, rel_u, g_u, ones_u, w_u, n_u, B_u, "pallas")
        got_u = expand_unit_hess(jax.jit(
            build_histogram, static_argnums=(5, 6, 7),
            static_argnames=("unit_hess",))(
            binned_u, rel_u, g_u, ones_u, w_u, n_u, B_u, "pallas",
            unit_hess=True))
        err_u = float(jnp.max(jnp.abs(got_u - want_u)) /
                      (jnp.max(jnp.abs(want_u)) + 1e-30))
        checks.append({"check": "unit_hess_kernel",
                       "ok": err_u < 1e-5, "rel_err": err_u})

    def chk_boost_scan_binomial():
        _, m2 = fix_binomial()
        checks.append({"check": "boost_scan_binomial",
                       "ok": len(m2.scoring_history) > 0})

    def chk_boost_scan_multinomial():
        _, m3 = fix_multinomial()
        checks.append({"check": "boost_scan_multinomial",
                       "ok": m3.ntrees == 9})

    def chk_flat_scorer_parity():
        # flattened serving scorer (models/tree/core.py flat_margin)
        # must match the binned heap re-descent BITWISE on chip — the
        # serving fast path and MOJO export both descend these arrays.
        # NA + categorical + high-cardinality grouped bins in one
        # frame.
        _, mf, Xf = fix_rich()
        flat_ok = bool(np.array_equal(
            np.asarray(mf._margins(Xf)),
            np.asarray(mf._margins_binned(Xf))))
        checks.append({"check": "flat_scorer_parity", "ok": flat_ok})

    def chk_flat_scorer_parity_multinomial():
        fr3, m3 = fix_multinomial()
        X3 = m3._design_matrix(fr3)
        flat3_ok = bool(np.array_equal(
            np.asarray(m3._margins(X3)),
            np.asarray(m3._margins_binned(X3))))
        checks.append({"check": "flat_scorer_parity_multinomial",
                       "ok": flat3_ok})

    def chk_shap_parity():
        # compiled TreeSHAP serving (models/tree/shap.flat_shap) must
        # match the f64 host recursion on chip AND hold the additivity
        # invariant on device — the path tables + unwind DP must
        # survive real lowering, not just CPU interpret. Same NA +
        # high-card grouped-enum frame as the flat-scorer check.
        frf, mf, Xf = fix_rich()
        Xf_np = np.asarray(Xf)[:n]
        contrib = mf.predict_contributions(frf)
        host_phi = np.stack([contrib.vec(c).to_numpy()
                             for c in contrib.names], axis=1)
        dev_phi = mf.contrib_numpy(Xf_np)
        shap_err = float(np.abs(dev_phi - host_phi).max())
        margins_f = np.asarray(mf._margins(Xf))[:n]
        add_err = float(np.abs(dev_phi.sum(axis=1) - margins_f).max())
        checks.append({"check": "shap_parity",
                       "ok": shap_err < 1e-4 and add_err < 1e-4,
                       "host_err": shap_err, "additivity_err": add_err})

    def chk_shap_kernel_parity():
        # chip-native TreeSHAP kernel (ops/shap_kernel.py) against the
        # lowered-XLA `flat_shap_tab` it hand-places — per virtual-
        # tree group at a pow2 serving shape, AND end-to-end through
        # contrib_numpy with the env knob forcing each impl on a fresh
        # model copy (the scorer cache keys on shape, not impl, so
        # each leg needs its own executables). The kernel's f32
        # accumulation order is fixed; on a TPU the compiler owns the
        # XLA scatter's, so the contract here is the stated tolerance
        # (1e-5, ops/shap_kernel.py docstring) and whether BITWISE
        # held is reported, not required.
        import pickle

        from h2o_kubernetes_tpu.models.tree.shap import flat_shap_tab
        from h2o_kubernetes_tpu.ops.shap_kernel import (
            flat_shap_tab_kernel, kernel_fits)

        frf, mf, Xf = fix_rich()
        groups, ctabs = mf._contrib_prepare()
        em = mf._contrib_enum_mask()
        Xp = jnp.asarray(np.asarray(Xf)[:1024])
        ngr = 0
        bitwise = True
        err = 0.0
        for g, ct in zip(groups, ctabs):
            if ct is None or not kernel_fits(g, ct, 1024):
                continue
            ngr += 1
            want = np.asarray(flat_shap_tab(g, ct, Xp, em))
            got = np.asarray(flat_shap_tab_kernel(g, ct, Xp, em))
            bitwise &= bool(np.array_equal(want, got))
            err = max(err, float(np.nanmax(np.abs(want - got))))

        def _leg(env):
            mc = pickle.loads(pickle.dumps(mf))
            os.environ["H2O_TPU_SHAP_KERNEL"] = env
            try:
                return mc.contrib_numpy(np.asarray(Xf)[:n])
            finally:
                os.environ.pop("H2O_TPU_SHAP_KERNEL", None)

        on, off = _leg("1"), _leg("0")
        e2e_err = float(np.abs(on - off).max())
        checks.append({"check": "shap_kernel_parity",
                       # ngr > 0: the fixture must exercise the kernel
                       "ok": bool(ngr > 0 and err <= 1e-5
                                  and e2e_err <= 1e-5),
                       "kernel_groups": ngr, "group_bitwise": bitwise,
                       "e2e_bitwise": bool(np.array_equal(on, off)),
                       "max_abs_err": err, "e2e_max_abs_err": e2e_err})

    def chk_efb_parity():
        # EFB parity on chip: bundled vs unbundled training must pick
        # identical splits and produce bitwise-identical predictions
        # on an exact-sum wide one-hot fixture (models/tree/efb.py —
        # the bundled histogram runs the SAME pallas kernel at bundled
        # width, and the decode/remainder math must survive real
        # Mosaic, not just interpret mode). Single gaussian round on a
        # dyadic response = every sum exact, so any deviation is a
        # bug, not float noise.
        ne = 4096
        ecols = {}
        cat_e = rng.integers(0, 16, size=(4, ne))
        for gi in range(4):
            for k in range(16):
                ecols[f"c{gi}_{k}"] = (cat_e[gi] == k).astype(
                    np.float32)
        ecols["c0_0"][::31] = np.nan
        ecols["dx"] = rng.normal(size=ne).astype(np.float32)
        ecols["ye"] = ((cat_e[0] == 1).astype(np.float32)
                       - (cat_e[1] == 2) + (ecols["dx"] > 0)).astype(
            np.float32)
        fr_e = h2o.Frame.from_arrays(ecols)

        def _efb_leg(env):
            os.environ["H2O_TPU_EFB"] = env
            try:
                return GBM(ntrees=1, max_depth=5, seed=0).train(
                    y="ye", training_frame=fr_e)
            finally:
                os.environ.pop("H2O_TPU_EFB", None)

        m_b = _efb_leg("1")
        m_u = _efb_leg("0")
        isp = np.asarray(m_u.trees.is_split)
        efb_ok = bool(np.array_equal(isp,
                                     np.asarray(m_b.trees.is_split)))
        for fld in ("split_feat", "split_bin", "na_left"):
            a = np.where(isp, np.asarray(getattr(m_u.trees, fld)), -9)
            b = np.where(isp, np.asarray(getattr(m_b.trees, fld)), -9)
            efb_ok &= bool(np.array_equal(a, b))
        efb_ok &= bool(np.array_equal(
            np.asarray(m_u.predict_raw(fr_e)),
            np.asarray(m_b.predict_raw(fr_e))))
        checks.append({"check": "efb_parity", "ok": efb_ok})

    def chk_goss_parity():
        # GOSS sampled boost program (ISSUE 13): the static-capacity
        # compaction (jnp.nonzero + gathers inside the shard_map
        # scan), the hashed per-row draws and the full-row re-descent
        # margin update must survive real lowering, not just CPU.
        # Pinned two ways: a+b=1 keeps every row at amplification
        # (1-a)/b = 1, so the SAMPLED program must reproduce the
        # unsampled m2 BITWISE; and a really-sampled config must be
        # seeded-deterministic while actually differing from
        # unsampled.
        fr2, m2 = fix_binomial()

        def _goss_leg(a, b):
            os.environ.update({"H2O_TPU_GOSS": "1",
                               "H2O_TPU_GOSS_TOP_A": a,
                               "H2O_TPU_GOSS_RAND_B": b})
            try:
                return GBM(ntrees=3, max_depth=4, seed=0).train(
                    y="y", training_frame=fr2)
            finally:
                for k in ("H2O_TPU_GOSS", "H2O_TPU_GOSS_TOP_A",
                          "H2O_TPU_GOSS_RAND_B"):
                    os.environ.pop(k, None)

        def _trees_equal(ma, mb):
            return all(np.array_equal(np.asarray(xa), np.asarray(xb))
                       for xa, xb in zip(jax.tree.flatten(ma.trees)[0],
                                         jax.tree.flatten(mb.trees)[0]))

        m_gid = _goss_leg("0.5", "0.5")
        goss_ok = _trees_equal(m2, m_gid)
        m_g1 = _goss_leg("0.2", "0.2")
        m_g2 = _goss_leg("0.2", "0.2")
        goss_ok &= _trees_equal(m_g1, m_g2)
        goss_ok &= not _trees_equal(m2, m_g1)
        checks.append({"check": "goss_parity", "ok": bool(goss_ok)})

    registry = {
        "fact_kernel": chk_fact_kernel,
        "fact_kernel_cap": chk_fact_kernel_cap,
        "fact_kernel_shallow": chk_fact_kernel_shallow,
        "binblock_kernel": chk_binblock_kernel,
        "binblock_kernel_4": chk_binblock_kernel_4,
        "leaf_totals_kernel": chk_leaf_totals_kernel,
        "unit_hess_kernel": chk_unit_hess_kernel,
        "boost_scan_binomial": chk_boost_scan_binomial,
        "boost_scan_multinomial": chk_boost_scan_multinomial,
        "flat_scorer_parity": chk_flat_scorer_parity,
        "flat_scorer_parity_multinomial":
            chk_flat_scorer_parity_multinomial,
        "shap_parity": chk_shap_parity,
        "shap_kernel_parity": chk_shap_kernel_parity,
        "efb_parity": chk_efb_parity,
        "goss_parity": chk_goss_parity,
    }
    assert list(registry) == CHECK_NAMES
    for name in CHECK_NAMES:
        if name in selected:
            try:
                registry[name]()
            except Exception as e:   # this check failed; run the rest
                traceback.print_exc()
                checks.append({"check": name, "ok": False,
                               "error": repr(e)[:600]})

    passed = sum(1 for c in checks if c["ok"])
    total = len(checks)
    ok = passed == total and total > 0
    sys.stderr.write(
        f"kernel_gate: {passed}/{total} PASS"
        + (" (filtered)" if args.check else "") + "\n")
    out = {"gate": "pass" if ok else "fail", "platform": platform,
           "passed": passed, "total": total, "checks": checks}
    if args.check:
        out["filtered"] = selected
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
