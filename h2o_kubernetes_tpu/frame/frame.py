"""Frame: distributed columnar table with HBM-resident sharded columns.

The reference's Fluid-Vector store (water/fvec: Frame → Vec → Chunk,
SURVEY.md §2b C5) keeps each column as a chain of compressed Chunks spread
over the node ring via the DKV. The TPU-native design collapses all of
that: a column IS one `jax.Array`, row-sharded over the mesh ROWS axis.
There is no chunk zoo — XLA memory layouts replace per-chunk compression —
and no DKV — addressing is the NamedSharding.

Column kinds (mirroring H2O Vec types):
  numeric — float32, NA = NaN
  int     — float32 storage too (H2O stores ints in compressed chunks but
            exposes doubles at the API; we keep one numeric device dtype)
  enum    — int32 category codes + host-side `domain` (vocab), NA = -1
  time    — float64 epoch-millis, NA = NaN
  string  — host-resident list (no device array; used for vocab building)

Rows are padded to a multiple of the ROWS-axis size; padding is encoded as
NA so NA-aware reductions ignore it. `nrows` is the logical row count.

Rollups (lazy cached per-Vec min/max/mean/σ/NA-count — the analog of
water/fvec/RollupStats.java, SURVEY.md §2b C6) are computed by one MRTask
`doall` on first access and invalidated on mutation.
"""

from __future__ import annotations

import os
from typing import Any, Iterable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import mesh as meshlib
from ..runtime.mrtask import doall, pad_rows, put_rows, shard_rows
from ..runtime.telemetry import phase_span

NA_ENUM = -1  # NA/pad sentinel for enum codes


# jitted row gather for Vec.select_rows: an eager fancy-index on a
# committed multi-device array is the XLA:CPU rendezvous flake pattern;
# pad rows resolve to the NA sentinel so they behave like shard_rows pads
_gather_rows_jit = jax.jit(
    lambda data, idx, valid, na: jnp.where(valid, data[idx], na))


def _device_gather_min() -> int:
    """Row threshold for the on-device select_rows gather (below it
    the host path wins — the jitted gather traces once per result
    shape, and CV fold slices on toy frames would pay a compile each).
    H2O_TPU_DEVICE_GATHER_MIN overrides (tests force 0)."""
    try:
        return int(os.environ.get("H2O_TPU_DEVICE_GATHER_MIN", "65536"))
    except ValueError:
        return 65536


def _rollup_map(x):
    """Per-shard rollup stats (module-level so doall can cache the
    jitted callable across Vecs — CV fold frames re-derive rollups)."""
    ok = ~jnp.isnan(x)
    xz = jnp.where(ok, x, 0.0)
    return dict(
        cnt=jnp.sum(ok, dtype=jnp.float32),
        sum=jnp.sum(xz, dtype=jnp.float32),
        sumsq=jnp.sum(xz * xz),
        min=jnp.min(jnp.where(ok, x, jnp.inf)),
        max=jnp.max(jnp.where(ok, x, -jnp.inf)),
        zeros=jnp.sum(ok & (x == 0.0), dtype=jnp.float32),
    )


class Vec:
    """One column: a row-sharded device array plus host-side metadata."""

    def __init__(self, data: jax.Array, nrows: int, kind: str = "numeric",
                 domain: list[str] | None = None, name: str = "",
                 origin: float = 0.0):
        self.data = data          # padded, sharded over ROWS
        self.nrows = nrows
        self.kind = kind          # numeric | enum | time
        self.domain = domain
        self.name = name
        # time columns store float32 millis RELATIVE to `origin` (a float64
        # epoch-ms) — at absolute 2026 epoch magnitudes a float32 ulp is
        # ~131s, so the shift is what keeps timestamps exact.
        self.origin = origin
        self._rollups: dict[str, float] | None = None

    # -- construction -------------------------------------------------------

    @staticmethod
    def _host_rows(x: np.ndarray, domain=None, kind: str | None = None):
        """The host half of `from_numpy`: (the column in its storage
        dtype, kind, origin, the value its pad rows take)."""
        if kind is None:
            if domain is not None:
                kind = "enum"
            elif x.dtype.kind == "M":
                kind = "time"
            else:
                kind = "numeric"
        if kind == "enum":
            if x.dtype.kind == "f":  # pre-encoded float codes: NaN is NA
                x = np.where(np.isnan(x), NA_ENUM, x)
            return x.astype(np.int32, copy=_put_aliases()), kind, 0.0, NA_ENUM
        if kind == "time":
            if x.dtype.kind == "M":
                ms = x.astype("datetime64[ms]").astype(np.float64)
                ms[np.isnat(x)] = np.nan  # NaT would otherwise become 2^63-
            else:
                ms = x.astype(np.float64)
            origin = float(np.nanmin(ms)) if len(ms) else 0.0
            return (ms - origin).astype(np.float32), kind, origin, np.nan
        return x.astype(np.float32, copy=_put_aliases()), kind, 0.0, np.nan

    @staticmethod
    def from_numpy(x: np.ndarray, name: str = "", domain=None,
                   kind: str | None = None) -> "Vec":
        x = np.asarray(x)
        arr, kind, origin, pad = Vec._host_rows(x, domain, kind)
        return Vec(_shard_settled(arr, pad, x), nrows=len(x), kind=kind,
                   domain=domain, name=name, origin=origin)

    # -- basics -------------------------------------------------------------

    def __len__(self) -> int:
        return self.nrows

    @property
    def padded_len(self) -> int:
        return self.data.shape[0]

    def is_enum(self) -> bool:
        return self.kind == "enum"

    def cardinality(self) -> int:
        return len(self.domain) if self.domain is not None else -1

    def as_float(self) -> jax.Array:
        """Device column as float32 with NA→NaN (pads included as NaN).

        Time columns come back as ABSOLUTE epoch-ms (origin added, f32
        rounded — fine for binning/modeling; use to_numpy()/rollups()
        for exact timestamps).
        """
        if self.kind == "enum":
            d = self.data
            return jnp.where(d == NA_ENUM, jnp.nan, d.astype(jnp.float32))
        if self.kind == "time":
            return (self.data + np.float32(self.origin)).astype(jnp.float32)
        return self.data.astype(jnp.float32)

    def to_numpy(self) -> np.ndarray:
        a = np.asarray(self.data)[: self.nrows]
        if self.kind == "time":
            return a.astype(np.float64) + self.origin
        return a

    # -- rollups ------------------------------------------------------------

    def _compute_rollups(self) -> dict[str, float]:
        if self.nrows == 0:
            return dict(min=float("nan"), max=float("nan"),
                        mean=float("nan"), sigma=0.0, nacnt=0, zeros=0,
                        rows=0)
        if self.kind == "time":
            col = self.data  # origin-relative: full precision; shift below
        elif self.kind == "enum":
            col = self.as_float()
        else:
            col = self.data.astype(jnp.float32)

        r = doall(_rollup_map, col,
                  reduce=dict(cnt="sum", sum="sum", sumsq="sum",
                              min="min", max="max", zeros="sum"),
                  cache_key="vec_rollups")
        r = {k: float(v) for k, v in r.items()}
        n = r["cnt"]
        mean = r["sum"] / n if n > 0 else float("nan")
        var = r["sumsq"] / n - mean * mean if n > 1 else 0.0
        sigma = float(np.sqrt(max(var * n / (n - 1), 0.0))) if n > 1 else 0.0
        shift = self.origin if (self.kind == "time" and n) else 0.0
        return dict(  # time stats shift back to absolute epoch-ms;
            min=(r["min"] + shift) if n else float("nan"),  # sigma invariant
            max=(r["max"] + shift) if n else float("nan"),
            mean=mean + shift, sigma=sigma,
            nacnt=int(self.nrows - n), zeros=int(r["zeros"]), rows=int(n),
        )

    def rollups(self) -> dict[str, float]:
        if self._rollups is None:
            self._rollups = self._compute_rollups()
        return self._rollups

    def invalidate(self) -> None:
        self._rollups = None

    def min(self): return self.rollups()["min"]
    def max(self): return self.rollups()["max"]
    def mean(self): return self.rollups()["mean"]
    def sigma(self): return self.rollups()["sigma"]
    def nacnt(self): return self.rollups()["nacnt"]

    # -- row/type ops --------------------------------------------------------

    def select_rows(self, idx: np.ndarray) -> "Vec":
        """New Vec of rows at `idx` — gathered ON DEVICE.

        The round-5 path round-tripped the whole column through the
        host per selection (one fetch + re-shard per fold slice for
        sliced CV). Now the gather is a jitted `jnp.take` inside the
        source sharding followed by ONE reshard (device-to-device
        `device_put`); the host only ever holds the index vector.
        Values pass through bit-exactly (time columns keep their
        origin, so the stored f32 offsets are untouched). CV and
        similar row-masked training paths should still prefer weight
        masks, which skip even the reshard (see models/cv.py).
        """
        idx = np.asarray(idx)
        if idx.dtype == bool:
            idx = np.flatnonzero(idx)
        elif not np.issubdtype(idx.dtype, np.integer):
            # match numpy fancy-index semantics: float indices are an
            # error, not a silent truncation
            raise IndexError(
                f"select_rows: indices must be integers or booleans, "
                f"got {idx.dtype}")
        idx = idx.astype(np.int64)
        n = len(idx)
        # normalize negative indices and bounds-check like numpy (the
        # device gather clamps silently, which would corrupt selections)
        idx = np.where(idx < 0, idx + self.nrows, idx)
        if n and (idx.min() < 0 or idx.max() >= self.nrows):
            raise IndexError(
                f"select_rows: index out of range for {self.nrows} rows")
        mesh = meshlib.global_mesh()
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(mesh, P(meshlib.ROWS))
        if n == 0 or n < _device_gather_min() \
                or not sharding.is_fully_addressable:
            # small selections and multi-host (DCN) meshes take the
            # host path: device_put cannot target other processes'
            # devices, and below the threshold the jitted gather's
            # trace cost (one per result shape — CV fold sizes vary)
            # outweighs the host round trip it removes
            a = np.asarray(self.data)[: self.nrows][idx]
            if self.kind == "time":
                return Vec.from_numpy(a.astype(np.float64) + self.origin,
                                      self.name, kind="time")
            return Vec.from_numpy(a, self.name, domain=self.domain,
                                  kind=self.kind)
        shards = mesh.shape[meshlib.ROWS]
        m = ((n + shards - 1) // shards) * shards
        na = NA_ENUM if self.kind == "enum" else np.nan
        idx_p = np.zeros(m, dtype=np.int32)
        idx_p[:n] = idx
        valid = np.zeros(m, dtype=bool)
        valid[:n] = True
        out = _gather_rows_jit(self.data, jnp.asarray(idx_p),
                               jnp.asarray(valid),
                               jnp.asarray(na, dtype=self.data.dtype))
        out = jax.device_put(out, sharding)      # the ONE reshard
        return Vec(out, nrows=n, kind=self.kind, domain=self.domain,
                   name=self.name, origin=self.origin)

    def asfactor(self) -> "Vec":
        """Numeric → enum, domain = sorted distinct values (h2o asfactor)."""
        if self.is_enum():
            return self
        a = self.to_numpy()
        ok = ~np.isnan(a)
        vals = np.unique(a[ok])
        domain = [_num_str(v) for v in vals]
        codes = np.full(len(a), NA_ENUM, dtype=np.int32)
        codes[ok] = np.searchsorted(vals, a[ok]).astype(np.int32)
        return Vec.from_numpy(codes, self.name, domain=domain)

    def asnumeric(self) -> "Vec":
        """Enum → numeric: parse domain labels as numbers where possible,
        else fall back to the codes (h2o asnumeric semantics)."""
        if not self.is_enum():
            return self
        a = self.to_numpy()
        if not self.domain:  # all-NA enum column
            return Vec.from_numpy(np.full(len(a), np.nan, np.float32),
                                  self.name)
        try:
            lut = np.array([float(d) for d in self.domain], dtype=np.float32)
        except ValueError:
            lut = np.arange(len(self.domain), dtype=np.float32)
        out = np.where(a >= 0, lut[np.maximum(a, 0)], np.nan)
        return Vec.from_numpy(out.astype(np.float32), self.name)

    # -- elementwise algebra (the Rapids expression surface) -----------------
    # Reference: H2O's Rapids AST ops (water/rapids/ast/prims/math,
    # operators [U3]) exposed through h2o-py Frame/Vec operators. Here an
    # expression is just jnp math on the padded sharded column — XLA fuses
    # chains of these into one kernel; NA (NaN) propagates; pads stay NaN
    # so downstream filters/rollups ignore them.

    def _operand(self, other, op: str = "arithmetic") -> jax.Array | float:
        if isinstance(other, Vec):
            if other.nrows != self.nrows:
                raise ValueError("Vec length mismatch "
                                 f"({other.nrows} vs {self.nrows})")
            if other.is_enum():
                raise TypeError(
                    f"{op} is not applicable to enum column "
                    f"'{other.name}' (use asnumeric() first)")
            return other.as_float()
        if isinstance(other, (bool, int, float, np.floating, np.integer)):
            return float(other)
        raise TypeError(f"cannot combine Vec with {type(other).__name__}")

    def _arith(self, other, fn, name="") -> "Vec":
        if self.is_enum():
            # h2o-py raises for math on factors; as_float() would expose
            # the CODES and silently compute nonsense
            raise TypeError(f"arithmetic is not applicable to enum column "
                            f"'{self.name}' (use asnumeric() first)")
        out = fn(self.as_float(), self._operand(other))
        return Vec(out.astype(jnp.float32), self.nrows, name=name or
                   self.name)

    def __add__(self, o): return self._arith(o, jnp.add)
    def __radd__(self, o): return self._arith(o, lambda a, b: b + a)
    def __sub__(self, o): return self._arith(o, jnp.subtract)
    def __rsub__(self, o): return self._arith(o, lambda a, b: b - a)
    def __mul__(self, o): return self._arith(o, jnp.multiply)
    def __rmul__(self, o): return self._arith(o, lambda a, b: b * a)
    def __truediv__(self, o): return self._arith(o, jnp.divide)
    def __rtruediv__(self, o): return self._arith(o, lambda a, b: b / a)
    def __pow__(self, o): return self._arith(o, jnp.power)
    def __mod__(self, o): return self._arith(o, jnp.mod)
    def __floordiv__(self, o): return self._arith(o, jnp.floor_divide)
    def __neg__(self): return self._arith(0.0, lambda a, _: -a)

    def _cmp(self, other, fn) -> "Vec":
        if isinstance(other, str):
            # enum == "label": compare codes against the domain index
            # (h2o-py `fr["c"] == "cat"`); unknown label matches nothing
            if not self.is_enum():
                raise TypeError(
                    f"'{self.name}': string comparison needs an enum column")
            code = (self.domain or []).index(other) \
                if other in (self.domain or []) else -2
            a = self.data.astype(jnp.float32)
            a = jnp.where(self.data == NA_ENUM, jnp.nan, a)
            b = float(code)
        else:
            if self.is_enum():
                raise TypeError(
                    f"numeric comparison is not applicable to enum column "
                    f"'{self.name}' (compare against a level string)")
            a, b = self.as_float(), self._operand(other, "comparison")
        res = fn(a, b).astype(jnp.float32)
        bad = jnp.isnan(a) | jnp.isnan(jnp.asarray(b, dtype=jnp.float32))
        out = jnp.where(bad, jnp.nan, res)   # NA compares to NA (h2o)
        return Vec(out, self.nrows, name=self.name)

    def __lt__(self, o): return self._cmp(o, jnp.less)
    def __le__(self, o): return self._cmp(o, jnp.less_equal)
    def __gt__(self, o): return self._cmp(o, jnp.greater)
    def __ge__(self, o): return self._cmp(o, jnp.greater_equal)
    def __eq__(self, o): return self._cmp(o, jnp.equal)       # noqa: E731
    def __ne__(self, o): return self._cmp(o, jnp.not_equal)   # noqa: E731
    __hash__ = None  # mirrors h2o-py: Vecs are expressions, not dict keys

    def _bool(self) -> jax.Array:
        """Truth mask with NA→False (filter semantics)."""
        a = self.as_float()
        return jnp.where(jnp.isnan(a), 0.0, a) != 0.0

    def __and__(self, o):
        if not isinstance(o, Vec):
            raise TypeError("& needs two Vecs")
        out = (self._bool() & o._bool()).astype(jnp.float32)
        return Vec(out, self.nrows, name=self.name)

    def __or__(self, o):
        if not isinstance(o, Vec):
            raise TypeError("| needs two Vecs")
        out = (self._bool() | o._bool()).astype(jnp.float32)
        return Vec(out, self.nrows, name=self.name)

    def __invert__(self):
        return Vec((~self._bool()).astype(jnp.float32), self.nrows,
                   name=self.name)

    def _math(self, fn) -> "Vec":
        if self.is_enum():
            raise TypeError(f"math is not applicable to enum column "
                            f"'{self.name}' (use asnumeric() first)")
        return Vec(fn(self.as_float()).astype(jnp.float32), self.nrows,
                   name=self.name)

    def log(self): return self._math(jnp.log)
    def log1p(self): return self._math(jnp.log1p)
    def exp(self): return self._math(jnp.exp)
    def sqrt(self): return self._math(jnp.sqrt)
    def abs(self): return self._math(jnp.abs)
    def floor(self): return self._math(jnp.floor)
    def ceil(self): return self._math(jnp.ceil)
    def sign(self): return self._math(jnp.sign)

    def unique(self):
        """Distinct non-NA values as a 1-column Frame (h2o unique)."""
        from .munge import unique as _unique
        return _unique(self)

    def isna(self) -> "Vec":
        """1.0 where the value is NA (h2o isna — NA itself maps to 1)."""
        if self.kind == "enum":
            out = (self.data == NA_ENUM).astype(jnp.float32)
        else:
            out = jnp.isnan(self.data).astype(jnp.float32)
        # re-mark pad rows as NaN so they never count as real NA rows
        idx = jnp.arange(self.padded_len)
        out = jnp.where(idx < self.nrows, out, jnp.nan)
        return Vec(out, self.nrows, name=self.name)


def _num_str(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else str(v)


class Frame:
    """An ordered collection of equal-length Vecs (row-aligned shards)."""

    def __init__(self, vecs: Mapping[str, Vec] | None = None):
        self._vecs: dict[str, Vec] = dict(vecs or {})
        ns = {v.nrows for v in self._vecs.values()}
        if len(ns) > 1:
            raise ValueError(f"ragged columns: nrows {ns}")
        # binned-matrix cache (Frame.binned / binning.fused_fit_bins):
        # {key: uint8 device array | (BinSpec, uint8 device array)}
        self._binned_cache: dict = {}
        # content version for the fused-binning fit keys: edges are a
        # pure function of (columns, names, n_bins), so a cache entry is
        # valid exactly while the version holds (binning.fused_fit_bins)
        self._version: int = 0

    # -- construction -------------------------------------------------------

    @staticmethod
    def from_arrays(cols: Mapping[str, Any],
                    domains: Mapping[str, list[str]] | None = None) -> "Frame":
        """Build from {name: array-like}. Object/str columns become enums."""
        domains = dict(domains or {})
        vecs: dict[str, Vec] = {}
        # spans (telemetry.phase_span), per column: the host's part
        # (`frame.encode`), its transfer queued and the column before it
        # waited for (`frame.put`); then the last one (`frame.settle`)
        with phase_span("frame.from_arrays", columns=len(cols)) as root:
            for name, col in cols.items():
                with phase_span("frame.encode", column=name) as enc:
                    arr = arrived = np.asarray(col)
                    domain, path = domains.get(name), None
                    if arr.dtype.kind in "OUS":   # strings -> enum codes
                        arr, domain, path = _factorize(arr, domain=domain)
                    elif arr.dtype.kind == "b" and domain is None:
                        arr = arr.astype(np.float32)
                    host, kind, origin, pad = Vec._host_rows(arr, domain)
                    host = pad_rows(host, pad_value=pad)
                    enc.update(_encoded(arrived.dtype, path, host))
                with phase_span("frame.put", kind="enqueue", bytes=host.nbytes,
                                shards=meshlib.n_row_shards()):
                    vecs[name] = Vec(_put_after(host, vecs), nrows=len(arr),
                                     kind=kind, domain=domain, name=name,
                                     origin=origin)
            with phase_span("frame.settle", kind="wait"):
                _settle(vecs)
            root["rows"] = len(arr) if cols else 0
        return Frame(vecs)

    @staticmethod
    def from_pandas(df) -> "Frame":
        return Frame.from_arrays({c: df[c].to_numpy() for c in df.columns})

    # -- basics -------------------------------------------------------------

    @property
    def names(self) -> list[str]:
        return list(self._vecs)

    @property
    def nrows(self) -> int:
        return next(iter(self._vecs.values())).nrows if self._vecs else 0

    @property
    def ncols(self) -> int:
        return len(self._vecs)

    @property
    def shape(self) -> tuple[int, int]:
        return (self.nrows, self.ncols)

    def vec(self, name: str) -> Vec:
        return self._vecs[name]

    def __getitem__(self, key):
        if isinstance(key, str):
            return self._vecs[key]
        if isinstance(key, Vec):
            # boolean row filter: fr[fr["x"] > 0] — NA mask rows drop
            # (h2o-py Rapids row-slice semantics)
            if key.nrows != self.nrows:
                raise ValueError("filter mask length != nrows")
            return self.select_rows(np.asarray(key._bool())[: self.nrows])
        if isinstance(key, (list, tuple)):
            return Frame({k: self._vecs[k] for k in key})
        raise TypeError(f"bad key {key!r}")

    def __setitem__(self, name: str, vec: Vec):
        if self._vecs and vec.nrows != self.nrows:
            raise ValueError("nrows mismatch")
        self._vecs[name] = vec
        # column set changed: binned stale (setdefault: frames from old
        # pickles predate the cache attribute); the version bump also
        # invalidates any fused-binning fit key a live BinSpec carries
        self.__dict__.setdefault("_binned_cache", {}).clear()
        self.__dict__["_version"] = self.__dict__.get("_version", 0) + 1

    def __contains__(self, name: str) -> bool:
        return name in self._vecs

    def drop(self, names: str | Sequence[str]) -> "Frame":
        if isinstance(names, str):
            names = [names]
        return Frame({k: v for k, v in self._vecs.items() if k not in names})

    # -- device views -------------------------------------------------------

    def columns(self, names: Iterable[str] | None = None) -> list[Vec]:
        return [self._vecs[n] for n in
                (self.names if names is None else names)]

    def to_matrix(self, names: Iterable[str] | None = None) -> jax.Array:
        """[padded_rows, k] float32 matrix (enums as raw codes, NA→NaN)."""
        cols = [v.as_float() for v in self.columns(names)]
        return jnp.stack(cols, axis=1)

    def binned(self, bin_spec) -> jax.Array:
        """[padded_rows, F] uint8 bin codes for this frame under
        ``bin_spec`` (models/tree/binning.BinSpec), cached per frame.

        This is the chunked training data path's device working set:
        the tree learners train from it directly — the full-width
        float32 ``to_matrix`` is never materialized (binning happens
        column-block-wise straight from the Frame columns, see
        binning.bin_frame). Bitwise-identical to
        ``apply_bins_jit(self.to_matrix(bin_spec.names), ...)``.

        The cache key includes a content fingerprint of the edge
        matrix, so a checkpoint's BinSpec (edges fit on ANOTHER frame)
        never collides with this frame's own fit. Mutating the frame
        (``__setitem__``) invalidates. At most two entries are kept
        (e.g. a 256-bin GBM and a 64-bin DRF working set side by side).
        """
        import hashlib

        from ..models.tree.binning import bin_frame

        edges = np.asarray(bin_spec.edges_matrix())
        fp = hashlib.sha1(edges.tobytes()
                          + np.array(bin_spec.is_enum).tobytes()
                          ).hexdigest()[:16]
        key = (tuple(bin_spec.names), bin_spec.n_bins, fp)
        cache = self.__dict__.setdefault("_binned_cache", {})
        hit = cache.pop(key, None)
        if hit is not None:
            cache[key] = hit          # true LRU: a hit refreshes recency
            return hit
        out = bin_frame(self, bin_spec)
        while len(cache) >= 2:                  # tiny LRU: drop oldest
            cache.pop(next(iter(cache)))
        cache[key] = out
        return out

    def valid_mask(self) -> jax.Array:
        """float32 [padded_rows]: 1.0 for logical rows, 0.0 for padding."""
        if not self._vecs:
            raise ValueError("valid_mask() on an empty Frame")
        v = next(iter(self._vecs.values()))
        mask = (np.arange(v.padded_len) < v.nrows).astype(np.float32)
        return shard_rows(mask)   # multi-host-safe placement

    def to_pandas(self):
        import pandas as pd
        out = {}
        for n, v in self._vecs.items():
            a = v.to_numpy()
            if v.is_enum():
                dom = np.asarray(list(v.domain) + [None], dtype=object)
                col = dom[np.where(a >= 0, a, len(dom) - 1)]
                out[n] = col
            else:
                out[n] = a
        return pd.DataFrame(out)

    def summary(self) -> dict[str, dict[str, float]]:
        return {n: v.rollups() for n, v in self._vecs.items()}

    # -- row ops -------------------------------------------------------------

    def select_rows(self, idx) -> "Frame":
        """New Frame of rows at `idx` (int index array or bool mask)."""
        idx = np.asarray(idx)
        if idx.dtype == bool:
            if len(idx) != self.nrows:
                raise ValueError("mask length != nrows")
            idx = np.flatnonzero(idx)
        return Frame({n: v.select_rows(idx) for n, v in self._vecs.items()})

    def head(self, n: int = 10) -> "Frame":
        return self.select_rows(np.arange(min(n, self.nrows)))

    def split_frame(self, ratios: Sequence[float] = (0.75,),
                    seed: int = -1) -> list["Frame"]:
        """Random row split into len(ratios)+1 frames (h2o split_frame).

        Same sampling scheme as the reference's FrameSplitter: one uniform
        draw per row against the cumulative ratio boundaries.
        """
        if sum(ratios) >= 1.0:
            raise ValueError("ratios must sum to < 1")
        rng = np.random.default_rng(None if seed < 0 else seed)
        u = rng.random(self.nrows)
        bounds = np.cumsum(list(ratios) + [1.0])
        part = np.searchsorted(bounds, u, side="right")
        return [self.select_rows(part == k) for k in range(len(bounds))]

    def rbind(self, other: "Frame") -> "Frame":
        """Stack rows of two column-compatible frames."""
        if self.names != other.names:
            raise ValueError("rbind: column names differ")
        out: dict[str, Vec] = {}
        for n in self.names:
            a, b = self._vecs[n], other._vecs[n]
            if a.kind != b.kind:
                raise ValueError(f"rbind: column '{n}' kinds differ "
                                 f"({a.kind} vs {b.kind})")
            if a.is_enum() and list(a.domain) != list(b.domain):
                dom = sorted(set(a.domain) | set(b.domain))
                pos = {d: i for i, d in enumerate(dom)}
                lut_a = np.array([pos[d] for d in a.domain] + [NA_ENUM],
                                 dtype=np.int32)
                lut_b = np.array([pos[d] for d in b.domain] + [NA_ENUM],
                                 dtype=np.int32)
                ca, cb = a.to_numpy(), b.to_numpy()
                cat = np.concatenate([lut_a[np.where(ca < 0, len(lut_a) - 1, ca)],
                                      lut_b[np.where(cb < 0, len(lut_b) - 1, cb)]])
                out[n] = Vec.from_numpy(cat, n, domain=dom)
            else:
                cat = np.concatenate([a.to_numpy(), b.to_numpy()])
                out[n] = Vec.from_numpy(cat, n, domain=a.domain, kind=a.kind)
        return Frame(out)

    def group_by(self, by) -> "Any":
        """h2o-py GroupBy builder: fr.group_by("c").sum("x").get_frame()."""
        from .munge import GroupBy
        return GroupBy(self, by)

    def merge(self, other: "Frame", by=None, all_x: bool = False) -> "Frame":
        """Join on key columns (h2o merge: inner, or left when all_x)."""
        from .munge import merge as _merge
        return _merge(self, other, by=by, all_x=all_x)

    def impute(self, column: str, method: str = "mean", by=None):
        """Fill NAs in place (h2o.impute: mean/median/mode, by-groups)."""
        from .munge import impute as _impute
        return _impute(self, column, method=method, by=by)

    def table(self, col: str, col2: str | None = None) -> "Frame":
        """Frequency table of 1-2 categorical columns (h2o table)."""
        from .munge import table as _table
        return _table(self, col, col2)

    def quantile(self, prob=None) -> "Frame":
        """Per-numeric-column quantiles (h2o quantile defaults)."""
        from .munge import quantile as _quantile
        return _quantile(self) if prob is None else _quantile(self, prob)

    def sort(self, by, ascending: bool = True) -> "Frame":
        """Rows ordered by the given column(s) (h2o sort; stable,
        NA rows last either direction)."""
        keys = [by] if isinstance(by, str) else list(by)
        cols = []
        for k in reversed(keys):   # lexsort: last key is primary
            v = self._vecs[k]
            a = v.to_numpy().astype(np.float64)
            na = (a < 0) if v.is_enum() else np.isnan(a)
            # descending: negate the key rather than reversing the
            # permutation — keeps the sort stable and NA rows last
            key = a if ascending else -a
            cols.append(np.where(na, np.inf, key))
        return self.select_rows(np.lexsort(cols))

    def cbind(self, other: "Frame") -> "Frame":
        """Adjoin columns of an equal-length frame (suffix dups like h2o)."""
        if other.nrows != self.nrows:
            raise ValueError("cbind: nrows differ")
        out = dict(self._vecs)
        for n, v in other._vecs.items():
            name = n
            while name in out:
                name += "0"   # h2o suffixes duplicate names
            out[name] = v
        return Frame(out)


def _factorize(arr: np.ndarray, domain: list[str] | None = None
               ) -> tuple[np.ndarray, list[str], str]:
    """String column → (int32 codes, sorted vocab, the path taken, as
    the `frame.encode` span says it).

    NA is only true missingness: None / float NaN cells in object arrays
    and empty strings. Literal tokens like "NA" or "nan" stay categories —
    parse-time NA-token handling is the CSV reader's job, not ours.

    Fixed-width strings with no given domain go through a table over
    their code points (`encode.factorize_table`: no sort, no copy of the
    strings) wherever it serves; what follows is the general case.
    """
    given = domain is not None
    if not given and arr.dtype.kind in "US":
        from .encode import factorize_table

        served = factorize_table(arr)
        if served is not None:
            return *served, "factorize_table"
    if arr.dtype.kind == "O":
        isna = np.array([x is None or x != x for x in arr], dtype=bool)
    else:
        isna = np.zeros(len(arr), dtype=bool)
    s = np.where(isna, "", arr.astype(str))
    isna |= s == ""
    if domain is None:
        uniq, inv = np.unique(s[~isna], return_inverse=True)
        domain = [str(d) for d in uniq]
        codes = np.full(len(s), NA_ENUM, dtype=np.int32)
        codes[~isna] = inv.astype(np.int32)
    else:
        lookup = {d: i for i, d in enumerate(domain)}
        codes = np.array([lookup.get(x, NA_ENUM) for x in s], dtype=np.int32)
        codes[isna] = NA_ENUM
    return codes, domain, "factorize_domain" if given else "factorize"


def _encoded(arrived: np.dtype, factorized: str | None,
             host: np.ndarray) -> dict:
    """What a `frame.encode` span says of its column: the dtype it
    arrived in, the path it took to its storage dtype (`_factorize`'s
    for a string column), the bytes that go to the device. (Down here,
    and called from one line of `Frame.from_arrays`, so that no line of
    a traced operation of this file moves: a program's cache key holds
    its operations' lines.)"""
    path = factorized or ("as_is" if host.dtype == arrived else "cast")
    return {"dtype": str(arrived), "path": path, "bytes": host.nbytes}


def _put_aliases() -> bool:
    """Whether the device array `put_rows` makes goes on reading the
    host array it was given for as long as it lives. The CPU backend's
    `jnp.asarray` keeps an aligned numpy buffer as it is, so there
    `Vec._host_rows` copies a column that needs no conversion, as it did
    everywhere; an accelerator reads the array until its transfer is
    done (after `device_put` has returned: seen on the chip), so there
    the column goes as it arrived and whoever put it waits: `_settle`,
    `_shard_settled`."""
    return jax.default_backend() == "cpu"


def _settle(sent: Mapping[str, Vec]) -> None:
    """Wait until the last column of `sent` is on the device: its host
    array has been read, and the whole-column copy `put_rows` stages on
    the default device of a sharded frame is gone."""
    if sent:
        jax.block_until_ready(next(reversed(sent.values())).data)


def _put_after(host: np.ndarray, sent: Mapping[str, Vec]) -> jax.Array:
    """`put_rows(host)`, then wait for the column queued before it: a
    transfer overlaps the next column's queuing and no more, so a frame
    of any width stages two columns at most (the host's own copy of a
    column used to pace the transfers; with no copy nothing else does)."""
    data = put_rows(host)
    _settle(sent)
    return data


def _shard_settled(host: np.ndarray, pad, given: np.ndarray) -> jax.Array:
    """`shard_rows(host)`, waited for where `host` is the caller's own
    array and not a conversion of it."""
    data = shard_rows(host, pad_value=pad)
    if np.may_share_memory(host, given):
        jax.block_until_ready(data)
    return data
