"""Feature binning for histogram tree learners.

The reference bins feature values per split via DHistogram min/max +
equal-width bins recomputed every level (hex/tree/DHistogram.java,
SURVEY.md §2b C10); the bundled XGBoost path uses global quantile
sketches (tree_method=hist). On TPU, global quantile binning wins: it is
done ONCE per frame, turns every feature into a uint8 code, and makes
the per-level hot loop a pure integer scatter-add — static shapes, no
data-dependent rebinning. This follows the GBDT-on-accelerator
literature (PAPERS.md: XGBoost GPU, Booster) rather than the Java design.

Layout: B total bins per feature. Bin B-1 is reserved for NA. Numeric
features use quantile edges (≤ B-2 finite bins); categorical features
use their codes directly. What happens to an enum with many levels is
the job's ``categorical_encoding`` (`_classify_features`):
``label_encoder`` keeps B = nbins ≤ 256 and, past B-1 levels, lets
contiguous code ranges share bins (NOT what H2O-3 does below its
`nbins_cats`); ``enum`` gives every level of an enum with at most
`nbins_cats` levels its own bin, in a matrix wide enough to hold them
(B a power of two, 16-bit codes past 256), which is H2O-3's own
DHistogram layout for categoricals [U3].

Wide sparse frames additionally go through Exclusive Feature Bundling
at bin time (models/tree/efb.py, docs/SCALING.md "Wide sparse
frames"): mutually exclusive sparse features pack into single uint8
bundle columns, reusing this module's per-column `_bin_block_jit`
apply so the dense [rows, F] matrix — float32 OR uint8 — never
materializes; the fused prologue below stays the unbundled fast path
(narrow frames never pay the planning pass).
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

NA_BIN_OFFSET = 1  # last bin is NA


@dataclass
class BinSpec:
    """Binning model: per-feature quantile edges.

    `edges_dev` (the fast path, round 3) keeps the [F, B-2] edge matrix
    ON DEVICE — `fit_bins` no longer round-trips the quantiles through
    the host before the first training dispatch (AutoML/CV pay that
    per fold-model). `edges` remains for models saved by older builds
    (and pickles to host numpy either way via the persist layer)."""

    names: list[str]
    edges: list[np.ndarray] | None   # host per-feature edges (legacy)
    is_enum: list[bool]
    n_bins: int = 256                # total incl. NA bin
    edges_dev: object = None         # [F, B-2] device matrix (+inf pad)
    # "enum": every `is_enum` feature keeps one bin a level and splits
    # on a SET of levels (core._find_splits); "label_encoder": enums
    # split ordinally on their code. Class default, so specs pickled
    # before the field existed read as what they were.
    encoding: str = "label_encoder"

    @property
    def na_bin(self) -> int:
        return self.n_bins - 1

    @property
    def set_feats(self) -> tuple:
        """Per feature, whether it splits on a set of levels — the
        static `TreeParams.set_feats` of a job on this spec; () when
        none does (every `label_encoder` spec, every all-numeric
        frame)."""
        if self.encoding != "enum" or not any(self.is_enum):
            return ()
        return tuple(bool(e) for e in self.is_enum)

    def edges_matrix(self):
        """[F, B-2] edge matrix padded with +inf (for device binning)."""
        dev = getattr(self, "edges_dev", None)   # absent in old pickles
        if dev is not None:
            return dev
        if self.edges is None:
            raise ValueError(
                "BinSpec has neither edges_dev nor host edges — exactly "
                "one must be set (fit_bins sets edges_dev)")
        F = len(self.edges)
        width = self.n_bins - 2
        m = np.full((F, width), np.inf, dtype=np.float32)
        for i, e in enumerate(self.edges):
            m[i, : len(e)] = e
        return m


import functools

# above this many rows, quantile edges come from a fixed-key uniform
# row sample instead of a full-column sort. The reference's own hist
# path (XGBoost tree_method=hist; PAPERS.md GBDT-on-accelerator
# entries) bins from APPROXIMATE quantile sketches, not exact
# order statistics — a 64k sample gives ~256 draws per bin edge at
# n_bins=256, far inside the noise of where a split lands, while the
# per-column sort cost drops ~16x at 1M rows (fit_bins was ~200 ms of
# the 2.6 s bench train; sorts dominate it).
_QUANTILE_SAMPLE = 1 << 16


def _column_quantiles(cols_t: jax.Array, n_q: int) -> jax.Array:
    """[Fn, n] columns → [Fn, n_q] quantile edges, NON-DECREASING along
    a feature. `nanquantile` interpolates lo·(1−t) + hi·t, which for
    lo = hi = 3.0 reads 2.9999998 or 3.0000002 wherever the backend
    rounds the two products apart (a v5e does; XLA:CPU contracts them):
    on a column of few distinct values — counts, ratios, 0/1 — the run
    of equal edges then comes out UNSORTED by an ulp, and `apply_bins`'
    count of the edges at or below a value is no longer
    `searchsorted`, so a split at bin b is no longer `x < edges[b]` and
    every reader of a tree in value space (the flat scorer, MOJO, the
    benchmark's `cover_gap`) routes the rows that sit on that value
    apart from the grower (PERF.md section 6, PR 34: 1,984 rows of a
    node on the chip). The running maximum restores the order and
    moves no edge that was in order."""
    qs = jnp.linspace(0.0, 1.0, n_q + 2)[1:-1]
    Q = jax.vmap(lambda c: jnp.nanquantile(c, qs))(cols_t)
    return jax.lax.cummax(Q, axis=1)


@functools.partial(jax.jit, static_argnums=(1,))
@jax.named_scope("fit_quantiles")
def _device_quantiles(Xn: jax.Array, n_q: int) -> jax.Array:
    """Per-column quantile edges on device: [n, Fn] → [Fn, n_q].

    Device-side (round 3: no host round-trip before the first training
    dispatch). Sampling is the CALLER's job: fit_bins feeds this the
    `_sampled_feature_matrix` gather (≤ _QUANTILE_SAMPLE rows), the
    one place the fixed-key sample draw lives."""
    return _column_quantiles(Xn.T, n_q)


# per-column sample gather for the sketch path: fit_bins used to stack
# the FULL [n, Fn] f32 matrix just to sample 64k rows from it inside
# _device_quantiles — at 10M rows that transient alone is ~1.1 GB and
# was one of the ~5x-working-set peaks the chunked training path
# removes. Gathering the sample per column keeps the peak at O(sample).
@jax.jit
def _col_sample(c, idx):
    return c[idx]


def _sampled_feature_matrix(num_cols: list) -> jax.Array:
    """Stack numeric columns into the [min(n, S), Fn] matrix
    _device_quantiles sees — bitwise the same rows the old full-matrix
    path sampled (same fixed key, same with-replacement index draw
    over the PADDED length), without ever materializing [n, Fn]. The
    ONLY sample-draw site — edges for a given shape stay
    deterministic."""
    n = num_cols[0].shape[0]
    if n > _QUANTILE_SAMPLE:
        idx = jax.random.randint(jax.random.key(0x51BB),
                                 (_QUANTILE_SAMPLE,), 0, n)
        num_cols = [_col_sample(c, idx) for c in num_cols]
    return jnp.stack(num_cols, axis=1)


def bin_code_dtype(n_bins: int, nbins: int | None = None):
    """dtype of the codes of a matrix with ``n_bins`` bins a feature,
    NA bin included — THE one check of a job's bin counts (`fit_bins`,
    `fused_fit_bins` and `GBM.train` all ask here). ``nbins``, the
    numeric quantile bins a user asks for, stays within [4, 256]; the
    matrix itself may be wider where an enum keeps a bin a level
    (``categorical_encoding="enum"``): 8-bit codes to 256 bins, 16-bit
    codes to 65,536."""
    nbins = n_bins if nbins is None else nbins
    if not 4 <= nbins <= 256:
        raise ValueError(f"n_bins must be in [4, 256] (numeric quantile "
                         f"bins), got {nbins}")
    if not nbins <= n_bins <= 1 << 16:
        raise ValueError(f"a binned matrix holds at most 65536 bins a "
                         f"feature (16-bit bin codes), got {n_bins}")
    return jnp.uint8 if n_bins <= 256 else jnp.uint16


def resolve_encoding(categorical_encoding: str) -> str:
    """H2O-3's `categorical_encoding` as this program runs it:
    ``label_encoder`` (an enum splits ordinally on its code) or
    ``enum`` (one bin a level, splits on a set of levels). ``AUTO``
    still means ``label_encoder`` here — H2O-3's AUTO means ``enum``
    for GBM and DRF — until every serving format carries a set."""
    enc = str(categorical_encoding).lower()
    if enc in ("auto", "label_encoder", "labelencoder"):
        return "label_encoder"
    if enc == "enum":
        return "enum"
    raise ValueError(
        f"categorical_encoding must be one of AUTO, label_encoder, enum "
        f"(got {categorical_encoding!r})")


def set_features(frame, feature_names: list[str],
                 nbins_cats: int) -> tuple:
    """Per feature, whether ``categorical_encoding="enum"`` gives it
    set splits on this frame: an enum with at most ``nbins_cats``
    levels. () when no feature does — the job is then the one
    ``label_encoder`` runs. Column metadata only."""
    out = tuple(v.is_enum() and v.cardinality() <= nbins_cats
                for v in (frame.vec(n) for n in feature_names))
    return out if any(out) else ()


def matrix_bins(frame, feature_names: list[str], n_bins: int,
                nbins_cats: int | None) -> int:
    """Bins a feature (NA bin included) of the matrix a job bins to:
    ``n_bins`` as it always was without ``nbins_cats`` or without a
    set feature; else the least power of two that holds the widest
    feature with its NA bin — a numeric feature's ``n_bins`` (the NA
    bin among them), or an enum's levels up to ``nbins_cats`` and the
    NA bin."""
    if nbins_cats is None or \
            not set_features(frame, feature_names, nbins_cats):
        return n_bins
    widest = n_bins
    for name in feature_names:
        v = frame.vec(name)
        if v.is_enum():
            widest = max(widest, min(v.cardinality(), nbins_cats) + 1)
    return 1 << (widest - 1).bit_length()   # least 2^k >= widest


def _classify_features(frame, feature_names: list[str], n_bins: int,
                       nbins_cats: int | None = None
                       ) -> tuple[list[bool], list[int], list, np.ndarray,
                                  str]:
    """(is_enum, num_idx, num_cols, base_M, encoding) — the ONE
    feature-kind classification shared by fit_bins and the fused path.

    ``base_M`` is the host [F, B-2] +inf edge matrix (B the matrix's
    bins, `matrix_bins`) with the range-grouping edges of an enum that
    has more levels than bins of its own already filled. Enum rows
    never consult edges (apply_bins clips the code), so theirs stay at
    the +inf padding; a numeric feature's ``n_bins - 3`` quantile edges
    are filled by the caller whatever B is, so it bins as a job of
    ``n_bins`` bins always did.

    ``nbins_cats=None`` (``label_encoder``): B = ``n_bins``, and an enum
    past B-1 levels is folded into B-2 contiguous CODE RANGES, expressed
    through the numeric edge-count path (is_enum=False + synthetic edges
    between ranges; airlines Origin/Dest is ~300 levels). H2O-3 does
    this only past its `nbins_cats` (default 1024), so at 300 levels it
    is this program's own departure. With ``nbins_cats`` (``enum``):
    every enum of at most ``nbins_cats`` levels keeps one bin a level,
    code = bin, and only one past it is folded, into ``nbins_cats``
    ranges — H2O-3's DHistogram layout for categoricals ([U3]
    hex/tree/DHistogram). NA codes arrive as NaN from as_float and land
    in the NA bin either way."""
    B = matrix_bins(frame, feature_names, n_bins, nbins_cats)
    # an ``enum`` job is one where some feature takes set splits (its
    # matrix may be as wide as ``n_bins`` or wider); ranges an enum with
    # too many levels is folded into, and the most levels one keeps
    # without folding
    encoding = "enum" if nbins_cats is not None and \
        set_features(frame, feature_names, nbins_cats) else "label_encoder"
    groups, keep = (nbins_cats, nbins_cats) if encoding == "enum" \
        else (n_bins - 2, n_bins - 1)
    is_enum: list[bool] = []
    num_idx: list[int] = []
    num_cols = []
    base = np.full((len(feature_names), B - 2), np.inf, dtype=np.float32)
    for name in feature_names:
        v = frame.vec(name)
        if v.is_enum():
            card = v.cardinality()
            if card > keep:
                # groups-1 edges split the code space [0, card) into
                # `groups` near-equal ranges; the -0.5 puts each edge
                # BETWEEN codes
                e = (np.arange(1, groups, dtype=np.float32)
                     * card / groups) - 0.5
                base[len(is_enum), : groups - 1] = e
                is_enum.append(False)
                continue
            is_enum.append(True)
            continue
        num_idx.append(len(is_enum))
        num_cols.append(v.as_float())
        is_enum.append(False)
    return is_enum, num_idx, num_cols, base, encoding


def fit_bins(frame, feature_names: list[str], n_bins: int = 256,
             nbins_cats: int | None = None) -> BinSpec:
    """Compute quantile edges per numeric feature, fully device-side.
    ``nbins_cats``: the job's `categorical_encoding` is ``enum``
    (`_classify_features`); the spec's ``n_bins`` is then the matrix's
    (`matrix_bins`), not the ``n_bins`` numeric bins asked for.

    The edge matrix never visits the host: NaN quantiles (all-NA
    columns) become +inf on device, and duplicate quantiles (heavily
    tied columns) are kept — duplicated edges only produce empty bins,
    which is semantically identical to the round-2 host-side
    `np.unique` dedup (bin ids are labels; MOJO scoring uses the SAME
    matrix, so artifacts stay consistent)."""
    is_enum, num_idx, num_cols, base, encoding = _classify_features(
        frame, feature_names, n_bins, nbins_cats)
    bin_code_dtype(base.shape[1] + 2, n_bins)
    M = jnp.asarray(base)
    if num_cols:
        Q = _device_quantiles(_sampled_feature_matrix(num_cols),
                              n_bins - 3)
        Q = jnp.where(jnp.isnan(Q), jnp.inf, Q.astype(jnp.float32))
        M = M.at[jnp.asarray(num_idx, dtype=jnp.int32),
                 : n_bins - 3].set(Q)
    return _spec(feature_names, is_enum, M, encoding)


def _spec(feature_names, is_enum, M, encoding: str) -> BinSpec:
    """The BinSpec of a fit: the matrix's bins are the edge matrix's
    width and two (`_classify_features`, which says the encoding)."""
    return BinSpec(names=list(feature_names), edges=None,
                   is_enum=is_enum, n_bins=M.shape[1] + 2, edges_dev=M,
                   encoding=encoding)


# edges counted in one pass over the values: unrolled, a trip's compares
# fuse, so the count reads and writes its accumulator once a trip, not
# once an edge. A 16-column x 4,194,304-row block against 254 edges on
# the v5e chip: 301 ms at 1, 39 ms at 8, 11.5 ms at 32, 11.1 ms at 64
# (at twice the compile time), 41 ms unrolled whole; `searchsorted`'s
# `compare_all` 19 ms, its default binary search 6.3 s (PERF.md
# section 6, PR 27).
_EDGE_UNROLL = 32


@jax.named_scope("apply_bins")
def apply_bins(X: jax.Array, edges_matrix: jax.Array, enum_mask: jax.Array,
               na_bin: int) -> jax.Array:
    """Bin a [rows, F] float matrix → [rows, F] bin codes (jittable):
    uint8 up to 256 bins, uint16 past them (`bin_code_dtype`).

    Numeric: the number of that feature's quantile edges at or below
    the value — `searchsorted(edges, x, side="right")` to the bit (a
    value equal to an edge goes right of it; duplicated edges and the
    +inf padding count like any other edge; -0.0 == 0.0). It is counted,
    one edge of every feature at a time over the whole matrix, and not
    searched for: a binary search gathers one edge per element per
    step, which the chip runs ~500x slower than the compares, and the
    count needs no temporary but its int32 accumulator on any backend.
    Enum: the code IS the bin. NaN (or negative enum code) → NA bin.
    """

    def count_edge(k, acc):
        e = jax.lax.dynamic_index_in_dim(edges_matrix, k, axis=1,
                                         keepdims=False)
        return acc + (X >= e).astype(jnp.int32)

    num = jax.lax.fori_loop(0, edges_matrix.shape[1], count_edge,
                            jnp.zeros(X.shape, jnp.int32),
                            unroll=_EDGE_UNROLL)
    cat = jnp.clip(X, 0, na_bin - 1).astype(jnp.int32)
    b = jnp.where(enum_mask, cat, num)
    b = jnp.where(jnp.isnan(X) | (X < 0) & enum_mask, na_bin, b)
    return b.astype(jnp.uint8 if na_bin < 256 else jnp.uint16)


# module-level jitted form: a fresh jax.jit per train() call would
# retrace the binning program on every model fit (grid search / AutoML
# build many models per process)
apply_bins_jit = jax.jit(apply_bins, static_argnums=3)


# ---------------------------------------------------------------------------
# Binning straight from Frame columns (the chunked training data path)
# ---------------------------------------------------------------------------
#
# The round-5 tree train paths materialized the full [n, F] float32
# design matrix (data.X) only to bin it to uint8 — a transient ~5x the
# binned working set at 10M rows. `bin_frame` applies the bins
# column-BLOCK-wise directly from the Frame's device columns, so the
# largest float32 transient is one block; the uint8 matrix is the only
# full-width array that survives. Bitwise-identical to
# `apply_bins_jit(frame.to_matrix(names), ...)`: apply_bins is
# per-feature independent (vmap over columns), so blocking the column
# axis cannot change a single bin code.

# f32 bytes one column block may occupy while being binned
_BIN_BLOCK_BYTES = 256 << 20


def _bin_block_cols(padded_rows: int, F: int) -> int:
    return max(1, min(F, _BIN_BLOCK_BYTES // max(padded_rows * 4, 1)))


@functools.partial(jax.jit, static_argnums=(2,))
def _bin_block_jit(cols: tuple, edges_block, na_bin: int, enum_block):
    return apply_bins(jnp.stack(cols, axis=1), edges_block, enum_block,
                      na_bin)


@jax.jit
def _concat_blocks(*blocks):
    return jnp.concatenate(blocks, axis=1)


def bin_frame(frame, bin_spec: BinSpec) -> jax.Array:
    """[padded, F] uint8 bin codes from Frame columns, block-wise.

    All device dispatches are jitted (an eager op over committed
    multi-device arrays is the XLA:CPU rendezvous flake pattern)."""
    names = bin_spec.names
    edges = jnp.asarray(bin_spec.edges_matrix())
    enum_mask = jnp.asarray(np.array(bin_spec.is_enum))
    padded = frame.vec(names[0]).padded_len
    F = len(names)
    block = _bin_block_cols(padded, F)
    out = []
    for lo in range(0, F, block):
        hi = min(lo + block, F)
        cols = tuple(frame.vec(n).as_float() for n in names[lo:hi])
        out.append(_bin_block_jit(cols, edges[lo:hi], bin_spec.na_bin,
                                  enum_mask[lo:hi]))
    return out[0] if len(out) == 1 else _concat_blocks(*out)


# ---------------------------------------------------------------------------
# Fused first-dispatch binning (fit + apply in ONE program)
# ---------------------------------------------------------------------------
#
# The two-dispatch train prologue (fit_bins → Frame.binned) hides a
# blocking host round trip: Frame.binned fingerprints the EDGE BYTES
# for its cache key, so `np.asarray(edges)` must wait out the quantile
# computation and transfer it to the host before the bin apply can even
# dispatch, paid once per AutoML candidate and per CV fold.
# `fused_fit_bins` folds both halves into the frame's first training
# dispatch: one jitted program computes the quantile edges AND the
# first column block's codes, nothing touches the host, and the binned
# cache is keyed by (names, n_bins, frame content version) — valid
# because the edges are a pure function of the frame's content (the
# version counter bumps on Frame.__setitem__).  Bit-parity with the
# two-dispatch path (same sample gather, same quantile program, same
# apply_bins) is asserted by tests/test_scheduler.py.


@functools.partial(jax.jit, static_argnums=(5, 6))
def _fused_fit_bin_jit(base_M, num_idx, sample, cols: tuple,
                       enum_block, na_bin: int, n_q: int | None = None):
    """ONE dispatch: ``n_q`` quantile edges a numeric feature (all the
    edge matrix holds but its last, unless given: an ``enum`` job's
    matrix is wider than its numeric bins) from the sampled matrix +
    the bin codes of the first column block.  ``sample=None`` (no
    numeric features) skips the quantile half at trace time."""
    M = base_M
    if sample is not None:
        with jax.named_scope("fit_quantiles"):
            if n_q is None:
                n_q = M.shape[1] - 1              # n_bins - 3
            Q = _column_quantiles(sample.T, n_q)
            Q = jnp.where(jnp.isnan(Q), jnp.inf, Q.astype(jnp.float32))
            M = M.at[num_idx, : n_q].set(Q)
    binned = apply_bins(jnp.stack(cols, axis=1), M[: len(cols)],
                        enum_block, na_bin)
    return M, binned


def fused_fit_bins(frame, feature_names: list[str], n_bins: int = 256,
                   nbins_cats: int | None = None
                   ) -> tuple[BinSpec, jax.Array]:
    """(BinSpec, [padded, F] bin codes) in one fused first dispatch;
    ``nbins_cats`` as `fit_bins` takes it.

    Cache: hits the owning frame's ``_binned_cache`` under a
    content-version fit key WITHOUT any device sync, so a second model
    on the same frame/nbins (every AutoML plan entry after the first)
    pays neither the quantile fit nor the bin apply.  The classic
    fingerprint path (Frame.binned) remains for specs that did not come
    from fitting THIS frame (checkpoint continuation)."""
    cache = frame.__dict__.setdefault("_binned_cache", {})
    key = ("fitbin", tuple(feature_names), n_bins, nbins_cats,
           frame.__dict__.get("_version", 0))
    hit = cache.pop(key, None)
    if hit is not None:
        cache[key] = hit              # true LRU: a hit refreshes recency
        return hit
    is_enum, num_idx, num_cols, base, encoding = _classify_features(
        frame, feature_names, n_bins, nbins_cats)
    na_bin = base.shape[1] + 1
    bin_code_dtype(na_bin + 1, n_bins)
    F = len(feature_names)
    padded = frame.vec(feature_names[0]).padded_len
    sample = _sampled_feature_matrix(num_cols) if num_cols else None
    block = _bin_block_cols(padded, F)
    enum_arr = np.array(is_enum)
    cols0 = tuple(frame.vec(nm).as_float()
                  for nm in feature_names[:block])
    M, first = _fused_fit_bin_jit(
        jnp.asarray(base), jnp.asarray(num_idx, dtype=jnp.int32),
        sample, cols0, jnp.asarray(enum_arr[:block]), na_bin,
        None if na_bin == n_bins - 1 else n_bins - 3)
    outs = [first]
    for lo in range(block, F, block):
        hi = min(lo + block, F)
        cols = tuple(frame.vec(nm).as_float()
                     for nm in feature_names[lo:hi])
        outs.append(_bin_block_jit(cols, M[lo:hi], na_bin,
                                   jnp.asarray(enum_arr[lo:hi])))
    binned = outs[0] if len(outs) == 1 else _concat_blocks(*outs)
    spec = _spec(feature_names, is_enum, M, encoding)
    while len(cache) >= 2:                  # tiny LRU: drop oldest
        cache.pop(next(iter(cache)))
    cache[key] = (spec, binned)
    return spec, binned


def bin_frame_host_chunks(frame, bin_spec: BinSpec,
                          chunk_rows: int) -> list[np.ndarray]:
    """Row-chunked HOST-resident uint8 binned matrix (out-of-core mode).

    Bins one column at a time on device (peak device transient: one f32
    column + one uint8 column), fetches it, and scatters the bytes into
    per-chunk [chunk_rows, F] buffers. Rows past the padded length in
    the final chunk get the NA bin and are dead (w=0) downstream.
    Chunk c's rows are EXACTLY rows [c*chunk_rows, (c+1)*chunk_rows) of
    `bin_frame`'s output — the chunk-parity tests rely on it."""
    names = bin_spec.names
    edges = jnp.asarray(bin_spec.edges_matrix())
    enum_mask = np.array(bin_spec.is_enum)
    padded = frame.vec(names[0]).padded_len
    F = len(names)
    n_chunks = -(-padded // chunk_rows)
    bufs = [np.full((chunk_rows, F), bin_spec.na_bin, dtype=np.uint8)
            for _ in range(n_chunks)]
    for j, name in enumerate(names):
        col = frame.vec(name).as_float()
        b = np.asarray(_bin_block_jit(
            (col,), edges[j: j + 1], bin_spec.na_bin,
            jnp.asarray(enum_mask[j: j + 1])))[:, 0]
        for c in range(n_chunks):
            lo = c * chunk_rows
            hi = min(lo + chunk_rows, padded)
            bufs[c][: hi - lo, j] = b[lo:hi]
    return bufs
