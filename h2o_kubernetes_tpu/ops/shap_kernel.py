"""Chip-native TreeSHAP: Pallas kernel for the `flat_shap_tab` path.

`models/tree/shap.flat_shap_tab` is the pattern-table fast path of the
compiled TreeSHAP server: per virtual-tree leaf it folds a D-bit hot
pattern over the transposed [F, rows] feature block, gathers the
precomputed per-pattern contribution column from `pattern_table`, and
scatter-accumulates each of the D slot rows into phi. Lowered by XLA
those are exactly the shapes the GBDT-on-accelerator literature says
want a hand-placed kernel (Booster, arXiv:2011.02022): contiguous
column-slice gathers plus per-slot [rows] vector-add scatters that the
TPU backend serializes.

This module is the hand-placed version, mirroring `ops/histogram.py`'s
integration pattern end to end:

- grid (rows/row_tile, T): row blocks are "parallel", virtual trees
  "arbitrary" (phi accumulates across the T dimension, initialised at
  t == 0 per row block).
- the per-tree scalar tables (feat/lo/hi/na_ok [L, D], bias) are
  staged in SMEM; the transposed feature block [F, rt] and the
  pattern table [L, D, P] live in VMEM.
- the pattern gather is a one-hot matmul — ct_l [D, P] × onehot [P, rt]
  with Precision.HIGHEST and f32 accumulation — which is EXACT
  selection (0/1 against f32), the same trick the histogram kernel
  rides the MXU with.
- the per-slot scatter is an ORDERED f32 accumulation (leaves outer,
  depth slots inner, trees in grid order), so results are
  deterministic: the feature row is fetched with a dynamic sublane
  slice (a matmul gather would poison on NaN features), and phi rows
  accumulate one dynamic slice at a time in slot order.

Contract with the XLA path: the kernel's order is fixed by the code
above; `flat_shap_tab`'s is whatever order XLA folds duplicate scatter
indices in. On XLA:CPU that is row-major update order — the same
order — so the two are BITWISE-equal there (tier-1 pins it, kernel in
interpret mode), given the barrier `Model._contrib_matrix` puts
between groups (without it XLA folds the cross-group add into a
one-trip group's scatter chain and reorders the XLA leg's own sum —
the jax-0.9.0 failure of tests/test_shap_kernel.py). On a TPU the
compiler owns the scatter's order, so there the contract is a stated
tolerance, 1e-5 (a tenth of the 1e-4 bound that ties both to the f64
host oracle); `tools/kernel_gate.py --check shap_kernel_parity`
enforces it on the chip and reports whether bitwise held.

`resolve_impl("auto")` picks the kernel on TPU and the lowered-XLA
`flat_shap_tab` elsewhere; `H2O_TPU_SHAP_KERNEL=1/0` forces/kills it
(the kill switch restores the XLA path bitwise — same executable, not
a lookalike). On non-TPU backends the kernel runs in interpret mode
(`ops/histogram._interpret`); Mosaic first accepted it in PR 22
(tests/test_chip_compile.py compiles it for a described v5e).

Like `hist_impl`, the knob is read when the serving program is TRACED:
a model's cached contributions executable keeps the impl it was traced
with until the scorer cache is evicted or the model is re-promoted.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .histogram import _dimsem, _interpret

__all__ = ["flat_shap_tab_kernel", "kernel_fits", "resolve_impl"]

# default row tile: [F+1, rt] phi + [F, rt] X + [P, rt] one-hot f32
# blocks stay comfortably inside VMEM at serving widths (F ≤ a few
# hundred, P = 2^D ≤ 2^14); pow2 so serving's bucketed batch shapes
# (_batch_bucket, ≥ 128) tile exactly.
_ROW_TILE = 512

# VMEM ceiling for the resident blocks of one grid step. ~16 MB/core
# on current chips; leave headroom for Mosaic's own temporaries.
_VMEM_BUDGET = 12 << 20

_MIN_ROWS = 128        # serving's _SCORE_MIN_BATCH — smaller batches
#                        never reach the device path un-padded


def resolve_impl(impl: str = "auto") -> str:
    """'auto'/'pallas'/'xla' -> 'pallas'|'xla'.

    auto consults H2O_TPU_SHAP_KERNEL (auto/1/0, pallas/xla aliases):
    0 is the kill switch (lowered-XLA `flat_shap_tab`, bitwise the
    pre-kernel path), 1 forces the kernel (interpret mode off-chip),
    auto picks the kernel only on a TPU backend. A typo must not
    silently demote the kernel, so junk values raise."""
    if impl == "auto":
        env = os.environ.get("H2O_TPU_SHAP_KERNEL", "auto")
        if env in ("1", "pallas"):
            return "pallas"
        if env in ("0", "xla"):
            return "xla"
        if env != "auto":
            raise ValueError(
                f"H2O_TPU_SHAP_KERNEL '{env}' is not one of auto/1/0")
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown shap impl '{impl}'")
    return impl


def kernel_fits(tables, ctab, rows: int | None = None) -> bool:
    """Static eligibility of ONE virtual-tree group for the kernel.

    Ineligible groups take the XLA path even under =1 — the env knob
    selects an implementation, it must not turn a large-P group (or a
    non-pow2 debug batch) into a trace error. The choice is visible:
    `Model.contrib_plan(rows)` names each group's impl. What this
    accepts, Mosaic compiles (checked at the largest accepted shape,
    L=32 D=11 at a 512-row tile, tests/test_chip_compile.py)."""
    if ctab is None:
        return False
    T, L, D, P = ctab.shape
    if rows is not None:
        if rows < _MIN_ROWS or rows & (rows - 1):
            return False
    rt = _ROW_TILE if rows is None else min(rows, _ROW_TILE)
    # resident f32 blocks of one grid step: ctab [L,D,P] + one-hot
    # [P,rt] + contrib [D,rt] + X [F,rt] + phi [F+1,rt]; F is bounded
    # by the X/phi terms — charge a generous 1024-feature stand-in
    # when the caller doesn't know rows/F yet.
    vmem = 4 * (L * D * P + P * rt + D * rt + 2 * 1024 * rt)
    return vmem <= _VMEM_BUDGET


def _shap_tab_kernel(feat_ref, lo_ref, hi_ref, na_ref, bias_ref,
                     xt_ref, ct_ref, phi_ref):
    """One (row-block, virtual-tree) grid step.

    feat/lo/hi/na: [1, L, D] SMEM scalar tables (one virtual tree);
    bias: [1, 1, 1] SMEM; xt: [F, rt] VMEM transposed canonical features;
    ct: [1, L, D, P] VMEM pattern table; phi: [F+1, rt] accumulator.
    """
    L, D = feat_ref.shape[1], feat_ref.shape[2]
    P = ct_ref.shape[3]
    F = phi_ref.shape[0] - 1
    rt = phi_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        phi_ref[:] = jnp.zeros_like(phi_ref)

    iota_p = lax.broadcasted_iota(jnp.int32, (P, rt), 0)
    dn = (((1,), (0,)), ((), ()))

    def leaf(l, carry):
        # D-bit hot-pattern fold. Padding slots (feat == -1) carry
        # lo=-inf / hi=NaN / na_ok=True, so x >= -inf is hot for any
        # real value and NaN features take the na_ok branch — the bit
        # is 1 either way, matching `_one_fractions` exactly; the
        # max(fidx, 0) clamp only picks WHICH garbage row is compared.
        pat = jnp.zeros((1, rt), dtype=jnp.int32)
        for d in range(D):
            fidx = feat_ref[0, l, d]
            x = xt_ref[pl.ds(jnp.maximum(fidx, 0), 1), :]
            hot = (x >= lo_ref[0, l, d]) & ~(x >= hi_ref[0, l, d])
            o = (jnp.isnan(x) & (na_ref[0, l, d] != 0)) | hot
            pat = pat + o.astype(jnp.int32) * (1 << d)
        # pattern gather as exact one-hot matmul: [D, P] x [P, rt]
        onehot = (iota_p == pat).astype(jnp.float32)
        contrib = lax.dot_general(ct_ref[0, l], onehot,
                                  dimension_numbers=dn,
                                  preferred_element_type=jnp.float32,
                                  precision=lax.Precision.HIGHEST)
        # ordered per-slot scatter: padding slots target the bias row
        # F (their ct column is identically 0), duplicates fold in
        # slot order — the XLA reference's row-major scatter order.
        for d in range(D):
            fidx = feat_ref[0, l, d]
            tgt = jnp.where(fidx < 0, F, fidx)
            phi_ref[pl.ds(tgt, 1), :] = (phi_ref[pl.ds(tgt, 1), :]
                                         + contrib[d:d + 1, :])
        return carry

    lax.fori_loop(0, L, leaf, 0)
    phi_ref[F:F + 1, :] = phi_ref[F:F + 1, :] + bias_ref[0, 0, 0]


@functools.partial(jax.jit, static_argnames=("row_tile",))
def flat_shap_tab_kernel(tables, ctab, X, enum_mask,
                         row_tile: int = _ROW_TILE):
    """[rows, F] × ShapTables × pattern table -> [rows, F+1] phi.

    Drop-in twin of `models/tree/shap.flat_shap_tab` (same canonical
    NaN-for-negative-enum rewrite, same ordered accumulation, bitwise
    output); caller guarantees `kernel_fits(tables, ctab, rows)`.
    """
    rows, F = X.shape
    T, L, D = tables.feat.shape
    rt = min(rows, row_tile)
    Xc = jnp.where(enum_mask[None, :] & (X < 0), jnp.float32(jnp.nan),
                   X)
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    phi = pl.pallas_call(
        _shap_tab_kernel,
        out_shape=jax.ShapeDtypeStruct((F + 1, rows), jnp.float32),
        grid=(rows // rt, T),
        in_specs=[
            smem((1, L, D), lambda r, t: (t, 0, 0)),          # feat
            smem((1, L, D), lambda r, t: (t, 0, 0)),          # lo
            smem((1, L, D), lambda r, t: (t, 0, 0)),          # hi
            smem((1, L, D), lambda r, t: (t, 0, 0)),          # na_ok
            # [T, 1, 1]: an SMEM block's last two dims must tile
            # (8, 128) or span the array — (1, 1) of [T, 1] does not
            smem((1, 1, 1), lambda r, t: (t, 0, 0)),          # bias
            pl.BlockSpec((F, rt), lambda r, t: (0, r)),       # Xᵀ
            pl.BlockSpec((1, L, D) + ctab.shape[3:],
                         lambda r, t: (t, 0, 0, 0)),          # ctab
        ],
        out_specs=pl.BlockSpec((F + 1, rt), lambda r, t: (0, r)),
        compiler_params=_dimsem("parallel", "arbitrary"),
        interpret=_interpret(),
        name="shap_tab", metadata={"kernel": "shap_tab"},
    )(tables.feat.astype(jnp.int32), tables.lo, tables.hi,
      tables.na_ok.astype(jnp.int32), tables.bias.reshape(T, 1, 1),
      Xc.T, ctab)
    return phi.T
