"""Bin codes against a plain numpy reference, to the bit (ISSUE 27).

`apply_bins` counts the edges at or below each value; the convention
the rest of the system rests on is `np.searchsorted(edges, x,
side="right")` (serving descends in value space with `x >= e[b]`,
`models/tree/core.py`; the MOJO scorer calls numpy's `searchsorted`,
`mojo.py`; the benchmark holds `cover_gap` at exactly 0). The reference
here is that call plus the NA/enum rules, written out per column.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.models.tree import binning
from h2o_kubernetes_tpu.models.tree.binning import (_bin_block_jit,
                                                    apply_bins,
                                                    apply_bins_jit,
                                                    bin_frame, fit_bins,
                                                    fused_fit_bins)

N_BINS = [4, 64, 256]


def _reference(X, E, is_enum, na_bin):
    out = np.empty(X.shape, np.uint8)
    for f in range(X.shape[1]):
        col = X[:, f]
        if is_enum[f]:
            b = np.clip(np.nan_to_num(col), 0, na_bin - 1).astype(np.int64)
            b[np.isnan(col) | (col < 0)] = na_bin
        else:
            b = np.searchsorted(E[f], col, side="right")
            b[np.isnan(col)] = na_bin
        out[:, f] = b
    return out


def _awkward_values(rng, E, n):
    """([n, F] float32, first free row): rows of NaN, ±inf, ±0.0,
    every edge itself (duplicates and the +inf pads included) and its
    float32 neighbours on both sides; from the first free row on,
    draws around each feature's finite edges."""
    F, width = E.shape
    fin = np.where(np.isfinite(E), E, 0.0)
    lo, hi = fin.min(axis=1) - 1.0, fin.max(axis=1) + 1.0
    X = rng.uniform(lo, hi, size=(n, F)).astype(np.float32)
    special = np.array([np.nan, np.inf, -np.inf, -0.0, 0.0], np.float32)
    X[: len(special)] = special[:, None]
    at = len(special)
    X[at: at + width] = E.T
    X[at + width: at + 2 * width] = np.nextafter(E.T, np.float32(-np.inf))
    X[at + 2 * width: at + 3 * width] = np.nextafter(E.T,
                                                     np.float32(np.inf))
    # XLA compares denormals as zeros, on the CPU and on the chip, in
    # the binary search as in the count; numpy does not. Zero's
    # neighbours here are the least normal numbers.
    tiny = np.finfo(np.float32).tiny
    denormal = (X != 0) & (np.abs(X) < tiny)
    X[denormal] = np.copysign(tiny, X[denormal])
    return X, at + 3 * width


def _numeric(rng, n_bins):
    """Three features of sorted edges + the +inf pad: one with an edge
    at exactly 0 and a run of duplicates, one plain, one mostly pad."""
    width = n_bins - 2
    E = np.full((3, width), np.inf, np.float32)
    n_fin = width - 1                      # fit_bins fills n_bins - 3
    e = np.sort(rng.standard_normal(n_fin)).astype(np.float32)
    e[n_fin // 2] = 0.0
    e[n_fin // 4: n_fin // 4 + min(5, n_fin // 4)] = e[n_fin // 4]
    E[0, :n_fin] = np.sort(e)
    E[1, :n_fin] = np.sort(rng.standard_normal(n_fin)).astype(np.float32)
    E[2, : max(1, n_fin // 8)] = np.sort(
        rng.standard_normal(max(1, n_fin // 8))).astype(np.float32)
    return E, np.zeros(3, bool)


def _enum(rng, n_bins):
    """Codes ARE bins: edges are never consulted (left at +inf)."""
    return np.full((2, n_bins - 2), np.inf, np.float32), np.ones(2, bool)


def _grouped_enum(rng, n_bins):
    """Past n_bins-1 levels, code ranges share bins through the numeric
    path: `_classify_features`' edges between codes."""
    width = n_bins - 2
    E = np.full((2, width), np.inf, np.float32)
    for f, card in enumerate((n_bins + 7, 5 * n_bins)):
        E[f, : n_bins - 3] = (np.arange(1, width, dtype=np.float32)
                              * card / width) - 0.5
    return E, np.zeros(2, bool)


def _all_na(rng, n_bins):
    """An all-NA column fits edges that are all +inf."""
    return np.full((2, n_bins - 2), np.inf, np.float32), np.zeros(2, bool)


KINDS = {"numeric": _numeric, "enum": _enum,
         "grouped_enum": _grouped_enum, "all_na": _all_na}


def _values(rng, kind, E, n_bins, n=1500):
    X, at = _awkward_values(rng, E, n)
    if kind in ("enum", "grouped_enum"):
        # integer codes as as_float() hands them over: negatives and
        # NaN are NA for an enum, codes past the last bin are clipped
        top = 6 * n_bins
        X[at:] = rng.integers(-3, top, size=X[at:].shape)
        X[at: at + 3, 0] = [-1.0, n_bins - 2, n_bins - 1]
    if kind == "all_na":
        X[at:, 0] = np.nan
    return X


@pytest.mark.parametrize("kind", list(KINDS))
@pytest.mark.parametrize("n_bins", N_BINS)
def test_apply_bins_is_searchsorted_right_to_the_bit(n_bins, kind):
    rng = np.random.default_rng(n_bins * 31 + len(kind))
    E, is_enum = KINDS[kind](rng, n_bins)
    X = _values(rng, kind, E, n_bins)
    na_bin = n_bins - 1
    want = _reference(X, E, is_enum, na_bin)
    assert want.max() <= na_bin
    Xd, Ed, md = jnp.asarray(X), jnp.asarray(E), jnp.asarray(is_enum)
    np.testing.assert_array_equal(
        np.asarray(apply_bins(Xd, Ed, md, na_bin)), want)
    # host edges, as a BinSpec unpickled from an older build hands them
    np.testing.assert_array_equal(
        np.asarray(apply_bins_jit(Xd, E, is_enum, na_bin)), want)
    cols = tuple(Xd[:, f] for f in range(X.shape[1]))
    np.testing.assert_array_equal(
        np.asarray(_bin_block_jit(cols, Ed, na_bin, md)), want)


def _frame(rng, n):
    x = rng.standard_normal(n).astype(np.float32)
    x[rng.random(n) < 0.05] = np.nan
    x[:6] = [np.inf, -np.inf, 0.0, -0.0, np.nan, 1.0]
    ties = rng.integers(0, 4, size=n).astype(np.float32)   # 4 values:
    ties[rng.random(n) < 0.3] = 0.0          # runs of duplicated edges
    ties[:2] = [-0.0, 0.0]
    return h2o.Frame.from_arrays(
        {"x": x, "ties": ties,
         "gone": np.full(n, np.nan, np.float32),           # all NA
         "c": np.array(["u", "v", "w"])[rng.integers(0, 3, size=n)],
         "hc": rng.integers(0, 700, size=n).astype(np.float32)},
        domains={"hc": [f"L{i}" for i in range(700)]})


@pytest.mark.parametrize("n_bins", N_BINS)
def test_frame_binning_is_searchsorted_right_to_the_bit(mesh8, n_bins,
                                                        monkeypatch):
    """Both ways a frame is binned for training — `fit_bins` +
    `bin_frame`, and the fused first dispatch — over the edges the fit
    itself produced: quantiles with ties, an all-+inf row, the
    range-grouped enum (700 levels past every n_bins here)."""
    fr = _frame(np.random.default_rng(n_bins), 3000)
    names = ["x", "ties", "gone", "c", "hc"]
    # two columns a block: three blocks
    monkeypatch.setattr(binning, "_BIN_BLOCK_BYTES",
                        2 * 4 * fr.vec("x").padded_len)
    spec = fit_bins(fr, names, n_bins=n_bins)
    assert spec.is_enum == [False, False, False, True, False]
    E = np.asarray(spec.edges_matrix())
    assert np.isinf(E[2]).all() and np.isfinite(E[4, : n_bins - 3]).all()
    X = np.asarray(fr.to_matrix(names))
    want = _reference(X, E, spec.is_enum, spec.na_bin)
    np.testing.assert_array_equal(np.asarray(bin_frame(fr, spec)), want)
    spec2, fused = fused_fit_bins(fr, names, n_bins=n_bins)
    np.testing.assert_array_equal(np.asarray(spec2.edges_matrix()), E)
    np.testing.assert_array_equal(np.asarray(fused), want)


def test_quantile_edges_stay_in_order_where_a_backend_rounds_them_apart(
        monkeypatch):
    """`nanquantile` interpolates lo·(1−t) + hi·t; a backend that rounds
    the two products apart (a v5e; XLA:CPU contracts them) reads
    2.9999998 or 3.0000002 for lo = hi = 3, so a column of few distinct
    values got a run of equal edges UNSORTED by an ulp, and the count
    of edges at or below a value (`apply_bins`) stopped being
    `searchsorted`: a split at bin b was no longer `x < edges[b]`
    (ISSUE 34: 1,984 rows of a node routed apart on the chip).
    `_column_quantiles` keeps the edges non-decreasing."""
    import jax

    real = jnp.nanquantile

    def rounded_apart(c, qs):
        q = real(c, qs)
        ulp = ((jnp.arange(q.shape[0]) * 7) % 3 - 1).astype(q.dtype)
        return q * (1 + 1.1920929e-07 * ulp)

    rng = np.random.default_rng(0)
    X = np.stack([rng.integers(0, 7, 4000), rng.integers(0, 2, 4000),
                  rng.integers(1, 7, 4000) / 3.0,
                  rng.normal(size=4000)], axis=1).astype(np.float32)
    monkeypatch.setattr(binning.jnp, "nanquantile", rounded_apart)
    broken = np.asarray(jax.vmap(
        lambda c: rounded_apart(c, jnp.linspace(0, 1, 32)[1:-1]))(X.T))
    assert (np.diff(broken, axis=1) < 0).any()        # the fault, emulated
    Q = np.asarray(binning._column_quantiles(jnp.asarray(X.T), 30))
    assert (np.diff(Q, axis=1) >= 0).all()
    codes = np.asarray(binning.apply_bins(
        jnp.asarray(X), jnp.asarray(Q), jnp.zeros(4, bool), 255))
    for f in range(4):
        assert (codes[:, f] == np.searchsorted(Q[f], X[:, f],
                                               side="right")).all()
        for b in (0, 7, 15, 29):
            assert ((codes[:, f] <= b) == (X[:, f] < Q[f, b])).all()
