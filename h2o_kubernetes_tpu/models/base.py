"""ModelBuilder/Model base plumbing shared by every algorithm.

The analog of the reference's hex.ModelBuilder + hex.Model pair
(h2o-core hex/ModelBuilder.java — parameter validation, response
handling, training dispatch; SURVEY.md §2b C9/C10): resolves feature/
response columns from a Frame, infers the distribution, and gives every
model a uniform predict / model_performance surface.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import metrics as M
from ..frame import Frame, Vec
from ..runtime import mesh as meshlib

# jitted single-column overwrite for partial_plot sweeps: an EAGER
# .at[].set on a committed multi-device array is the XLA:CPU rendezvous
# flake pattern the fused train paths were purged of
_set_col_jit = jax.jit(
    lambda X, j, v: X.at[:, j].set(v), static_argnums=1)


@dataclass
class TrainData:
    """Device-ready training inputs resolved from a Frame.

    ``X`` is None when resolved with ``materialize_x=False`` — the
    histogram tree learners bin straight from the Frame columns
    (Frame.binned) and never touch a full float32 design matrix;
    gradients come from y/w/offset alone."""

    feature_names: list[str]
    X: jax.Array | None          # [padded, F] float32, NA→NaN, sharded
    y: jax.Array                 # [padded] float32 (class id for enums)
    w: jax.Array                 # [padded] float32 weights, 0 on padding
    nrows: int
    nclasses: int                # 1 for regression
    response_domain: list[str] | None
    distribution: str            # gaussian | bernoulli | multinomial | ...
    feature_domains: dict[str, list[str]] = field(default_factory=dict)
    offset: jax.Array | None = None   # [padded] float32, 0 on padding/NA


def _feature_names(frame: Frame, x: Sequence[str] | None,
                   ignored: set[str]) -> list[str]:
    """Resolve + validate feature columns (shared by resolve_xy/resolve_x)."""
    names = list(x) if x else [n for n in frame.names if n not in ignored]
    if x:
        # an explicit x must not smuggle back a column the caller set
        # aside: the response leaks the label, a weights/offset column
        # double-counts, a fold column encodes holdout membership
        clash = ignored.intersection(names)
        if clash:
            raise ValueError(
                f"column(s) {sorted(clash)} are the response/weights/"
                "offset/fold or ignored_columns and cannot also be "
                "features (remove them from x)")
    for n in names:
        if n not in frame:
            raise ValueError(f"feature column '{n}' not in frame")
        if frame.vec(n).kind not in ("numeric", "enum", "time"):
            raise ValueError(f"column '{n}' of kind {frame.vec(n).kind} "
                             "cannot be a feature")
    return names


def resolve_response(frame: Frame, y: str, distribution: str = "auto"
                     ) -> tuple[str, int, list[str] | None]:
    """(distribution, nclasses, response domain) from the response
    column's METADATA alone (kind, cardinality): no device work, so
    compile-ahead asks it what `resolve_xy` will answer."""
    yv = frame.vec(y)
    nclasses, domain = 1, None
    if distribution.startswith("rank:"):
        # graded relevance stored as an enum: its codes ARE the grades
        # — one output, never the multinomial path
        return distribution, 1, None
    if yv.is_enum():
        domain = yv.domain
        nclasses = yv.cardinality()
        if nclasses < 2:
            raise ValueError(f"response '{y}' has {nclasses} classes")
    if distribution == "auto":
        if nclasses == 2:
            distribution = "bernoulli"
        elif nclasses > 2:
            distribution = "multinomial"
        else:
            distribution = "gaussian"
    if distribution in ("bernoulli", "multinomial") and nclasses == 1:
        raise ValueError(f"{distribution} needs a categorical response; "
                         f"'{y}' is numeric (use .asfactor()-style enum)")
    return distribution, nclasses, domain


def resolve_xy(frame: Frame, y: str, x: Sequence[str] | None = None,
               ignored: Sequence[str] | None = None,
               weights_column: str | None = None,
               distribution: str = "auto",
               offset_column: str | None = None,
               materialize_x: bool = True) -> TrainData:
    from ..runtime.health import require_healthy

    require_healthy()   # fail fast before training on a broken cloud
    if y not in frame:
        raise ValueError(f"response column '{y}' not in frame")
    ignored = set(ignored or [])
    ignored.add(y)
    if weights_column:
        ignored.add(weights_column)
    if offset_column:
        # offset is a fixed per-row margin term, never a feature
        # (hex/ModelBuilder offset_column handling [U3])
        if offset_column not in frame:
            raise ValueError(
                f"offset column '{offset_column}' not in frame")
        if frame.vec(offset_column).is_enum():
            raise ValueError(
                f"offset column '{offset_column}' must be numeric")
        ignored.add(offset_column)
    names = _feature_names(frame, x, ignored)
    distribution, nclasses, domain = resolve_response(frame, y,
                                                      distribution)

    X = frame.to_matrix(names) if materialize_x else None
    y_arr = frame.vec(y).as_float()
    w = frame.valid_mask()
    if weights_column:
        w = w * frame.vec(weights_column).as_float()
    # response NAs are dropped by zeroing their weight (reference drops
    # such rows during ModelBuilder init)
    w = jnp.where(jnp.isnan(y_arr), 0.0, w)
    y_arr = jnp.nan_to_num(y_arr)
    off = None
    if offset_column:
        off = frame.vec(offset_column).as_float()
        # NA offset rows cannot contribute a defined margin — dropped
        # like NA responses
        w = jnp.where(jnp.isnan(off), 0.0, w)
        off = jnp.nan_to_num(off)
    fdoms = {n: list(frame.vec(n).domain) for n in names
             if frame.vec(n).is_enum()}
    return TrainData(names, X, y_arr, w, frame.nrows, nclasses, domain,
                     distribution, fdoms, off)


def resolve_x(frame: Frame, x: Sequence[str] | None = None,
              ignored: Sequence[str] | None = None) -> TrainData:
    """Unsupervised variant of resolve_xy: features only, y is a dummy.

    Returned TrainData has y=0, nclasses=1 — usable with build_datainfo
    for one-hot expansion/standardization (KMeans/PCA do the same via
    DataInfo in the reference, hex/kmeans & hex/pca)."""
    from ..runtime.health import require_healthy

    require_healthy()   # same fail-fast gate as the supervised path
    ignored = set(ignored or [])
    names = _feature_names(frame, x, ignored)
    X = frame.to_matrix(names)
    w = frame.valid_mask()
    fdoms = {n: list(frame.vec(n).domain) for n in names
             if frame.vec(n).is_enum()}
    zeros = jnp.zeros(X.shape[0], dtype=jnp.float32)
    return TrainData(names, X, zeros, w, frame.nrows, 1, None,
                     "gaussian", fdoms)


# ---------------------------------------------------------------------------
# Jitted-scorer cache (the compiled serving fast path)
# ---------------------------------------------------------------------------
#
# Serving traffic scores the SAME model at a handful of batch shapes
# thousands of times.  Each model carries one pair of jitted scorer
# callables (plain / with-offset) on the instance (dropped from pickles),
# and warm shapes are tracked per (model key, input schema, padded batch
# shape) so a warm call is zero-compile and zero-retrace: jax.jit keys
# its executable cache on the callable identity + input shapes, batch
# sizes are bucketed to powers of two (score_numpy pads), and compiles
# land in the round-4 persistent XLA cache (runtime/backend.py) so even
# a fresh process warm-starts from disk.
#
# Multi-tenant residency (docs/SERVING.md "Multi-tenant serving"): the
# cache is BYTE-budgeted, not count-capped.  Every resident model is
# charged its live trace + LUT + flat-array device bytes
# (_serving_resident_bytes); past H2O_TPU_SCORER_CACHE_BYTES the
# least-recently-scored model's executables AND device arrays are
# dropped (_serving_evict) while its host-side state (heap trees /
# artifact arrays) stays loaded.  The next score re-promotes: the
# re-trace recompiles the SAME HLO (same constants rebuilt from the
# same host arrays), so with the persistent XLA cache enabled an
# eviction costs a disk cache-hit, never a cold compile — and scores
# are bitwise-identical across evict→promote (tests/test_multitenant).

_SCORE_MIN_BATCH = 128          # smallest padded-batch bucket

_SCORER_STATS = {"hits": 0, "misses": 0, "models": 0, "evictions": 0,
                 "promotions": 0}
# guards cache-entry/jit creation + stats: an HTTP handler thread and
# the REST micro-batcher thread can first-score one model concurrently
_SCORER_LOCK = threading.Lock()

# LRU over models holding a live jitted-scorer cache, plus each
# resident model's byte charge. Without a budget a long-lived REST
# server serving a tenant population grows the set of per-model jitted
# callables (and the flat constant arrays each executable embeds)
# without bound; evicting the least-recently-scored model frees its
# executables + device arrays while the model itself stays loaded.
import collections
import os
import weakref

_SCORER_LRU: "collections.OrderedDict[int, weakref.ref]" = \
    collections.OrderedDict()
_SCORER_BYTES: dict[int, int] = {}      # id(model) -> charged bytes

# per-executable overhead beyond embedded constants + I/O buffers:
# generated code, thunk schedules, jax bookkeeping. Deliberately a
# round conservative constant — the accounting is a budget, not a
# profiler.
_TRACE_OVERHEAD = 64 * 1024
_LUT_BYTES_PER_ENTRY = 80       # dict slot + boxed float + key str


def _scorer_cache_cap() -> int:
    """H2O_TPU_SCORER_CACHE_MAX — optional resident-model COUNT cap on
    top of the byte budget (<= 0 = off, the default since the byte
    budget took over residency control). Read per call so a live
    server can be re-tuned without a restart."""
    try:
        cap = int(os.environ.get("H2O_TPU_SCORER_CACHE_MAX", "0"))
    except ValueError:
        cap = 0
    return max(0, cap)


def _scorer_cache_budget() -> int:
    """H2O_TPU_SCORER_CACHE_BYTES (default 1 GiB) — the resident-bytes
    budget over every model's live serving state; <= 0 = unbounded."""
    try:
        b = int(float(os.environ.get("H2O_TPU_SCORER_CACHE_BYTES",
                                     str(2 ** 30))))
    except ValueError:
        b = 2 ** 30
    return b


def scorer_cache_stats() -> dict[str, int]:
    """Shape-level cache counters: a `miss` is a (model, schema, padded
    batch) triple seen for the first time — i.e. an expected XLA
    trace/compile; warm traffic must add only `hits` (the bench's
    recompile check asserts exactly that). `promotions` is the subset
    of misses that re-traced a shape a previous eviction dropped —
    expected churn under a byte budget, not an SLO violation (the
    /3/Stats warm_cache_misses contract subtracts them). `evictions`
    counts models whose live serving state was dropped by the byte
    budget (H2O_TPU_SCORER_CACHE_BYTES) or the optional count cap
    (H2O_TPU_SCORER_CACHE_MAX); `models` counts cache CREATIONS (the
    historical total), while `resident` counts models holding live
    executables right now, charged `resident_bytes` against
    `budget_bytes`."""
    with _SCORER_LOCK:
        out = dict(_SCORER_STATS)
        resident, rbytes = 0, 0
        for vid, ref in _SCORER_LRU.items():
            # skip GC'd models' stale charges: a re-pushed model_id's
            # old instance may linger in _SCORER_BYTES until the next
            # _cached_score purge, and counting it could report
            # resident_bytes over budget for models that no longer
            # exist (a spurious budget_exceeded in the drills)
            if ref() is not None:
                resident += 1
                rbytes += _SCORER_BYTES.get(vid, 0)
        out["resident"] = resident
        out["resident_bytes"] = rbytes
        out["budget_bytes"] = _scorer_cache_budget()
    return out


def model_scorer_counters(model) -> dict[str, int]:
    """Per-model cache counters (hits/misses/promotions). They live on
    the MODEL (host-side) and survive eviction, so /3/Stats can report
    warm_cache_misses = (misses - promotions) - warm-up baseline: a
    re-trace caused by byte-budget eviction re-baselines out instead
    of reading as an SLO-violating first-request compile."""
    return dict(model.__dict__.get("_scorer_counters")
                or {"hits": 0, "misses": 0, "promotions": 0})


def evict_scorer_cache(model=None) -> int:
    """Ops/test hook: drop one model's live serving state (or EVERY
    resident model's when ``model`` is None) exactly as the byte
    budget would — executables + device arrays go, host-side state
    stays, the next score re-promotes through the persistent XLA
    cache. Returns the number of models evicted."""
    with _SCORER_LOCK:
        victims = []
        if model is None:
            for vid, ref in list(_SCORER_LRU.items()):
                del _SCORER_LRU[vid]
                _SCORER_BYTES.pop(vid, None)
                m = ref()
                if m is not None:
                    victims.append(m)
        elif _SCORER_LRU.pop(id(model), None) is not None:
            _SCORER_BYTES.pop(id(model), None)
            victims.append(model)
        for m in victims:
            m._serving_evict()
            _SCORER_STATS["evictions"] += 1
    return len(victims)


# the scorer cache registers with the process-wide metrics registry
# where it lives: /3/Stats and GET /metrics both render this group
# (runtime/telemetry.py — the fleet-telemetry single source of truth)
from ..runtime.telemetry import register_group as _register_tel_group

_register_tel_group("scorer_cache", scorer_cache_stats)


def _batch_bucket(n: int) -> int:
    """Next power-of-two batch size >= max(n, _SCORE_MIN_BATCH)."""
    b = _SCORE_MIN_BATCH
    while b < n:
        b *= 2
    return b


class Model:
    """Base trained model: predict() + model_performance()."""

    algo = "base"
    # True on models whose _score_matrix is end-to-end jittable
    # (GBM/DRF/XGBoost/GLM/DeepLearning): predict/score_numpy route
    # through the jitted-scorer cache instead of eager op dispatch
    _serving_jit = False

    def __init__(self, data: TrainData):
        self.feature_names = data.feature_names
        self.feature_domains = data.feature_domains
        self.nclasses = data.nclasses
        self.response_domain = data.response_domain
        self.distribution = data.distribution
        self.scoring_history: list[dict[str, Any]] = []
        self.cv = None                    # CVResult when trained with nfolds
        self.validation_metrics: dict[str, float] | None = None
        self.offset_column: str | None = None   # set by offset-aware trains

    # -- h2o-py-style CV accessors (H2OEstimator.cross_validation_*) -------

    def cross_validation_models(self):
        return self.cv.models if self.cv else None

    def cross_validation_holdout_predictions(self):
        return self.cv.holdout_predictions if self.cv else None

    def cross_validation_metrics(self) -> dict[str, float] | None:
        return self.cv.metrics if self.cv else None

    def cross_validation_metrics_summary(self):
        return self.cv.metrics_summary if self.cv else None

    # subclasses implement: _score_matrix(X) -> margin/probs array
    def _score_matrix(self, X: jax.Array) -> jax.Array:
        raise NotImplementedError

    # -- compiled serving fast path -----------------------------------------

    def __getstate__(self):
        # jitted scorer callables are process-local, and the flattened
        # ensemble is derivable from the trees (GBMModel._flat rebuilds
        # it lazily): pickling either would bloat artifacts and make
        # save-before-predict vs save-after-predict differ
        d = dict(self.__dict__)
        d.pop("_scorer_cache", None)
        d.pop("_flat_trees", None)
        d.pop("_serving_luts", None)    # rest.py enum-code LUT cache
        d.pop("_scorer_counters", None)  # process-local accounting
        d.pop("_evicted_shapes", None)
        d.pop("_shap_tables", None)      # device TreeSHAP path tables
        d.pop("_shap_tables_np", None)   # (host caches; rebuildable)
        d.pop("_shap_ctab", None)
        d.pop("_shap_ctab_np", None)
        return d

    def _serving_prepare(self) -> None:
        """Hook: materialize host-built serving state (e.g. the GBM
        flattened ensemble) OUTSIDE the jit trace — device constants
        created while tracing would leak as tracers."""

    def _serving_evict(self) -> None:
        """Drop every piece of serving state that is rebuildable from
        this model's host-side state: the jitted executables, the
        device-resident flat arrays, and the enum-code LUTs. The warm
        shape set is remembered (host-side) so the re-trace on the next
        score is accounted a `promotion`, not a fresh miss."""
        ent = self.__dict__.pop("_scorer_cache", None)
        if ent is not None and ent.get("shapes"):
            self.__dict__.setdefault(
                "_evicted_shapes", set()).update(ent["shapes"])
        self.__dict__.pop("_flat_trees", None)
        self.__dict__.pop("_serving_luts", None)
        # device TreeSHAP tables go too (host _shap_*_np stays, like
        # the heap trees: the re-promote rebuilds the SAME device
        # constants -> same HLO -> a persistent-cache hit)
        self.__dict__.pop("_shap_tables", None)
        self.__dict__.pop("_shap_ctab", None)

    def _serving_resident_bytes(self) -> int:
        """Estimated bytes this model's live serving state pins:
        device flat arrays + enum-code LUTs + one executable per
        traced shape. XLA:CPU embeds closed-over constants per
        compiled executable, so each traced batch bucket is charged
        its own copy of the flat arrays plus its padded I/O buffers —
        deliberately conservative: the budget is for capacity
        planning, not byte-exact profiling."""
        flat = 0
        ft = self.__dict__.get("_flat_trees")
        if ft is not None:
            for leaf in jax.tree_util.tree_leaves(ft):
                flat += int(getattr(leaf, "nbytes", 0) or 0)
        for name in ("_shap_tables", "_shap_ctab"):
            st = self.__dict__.get(name)
            if st is not None:
                # contributions executables embed the path/pattern
                # tables as closed-over constants, like the flat arrays
                for leaf in jax.tree_util.tree_leaves(st):
                    flat += int(getattr(leaf, "nbytes", 0) or 0)
        total = flat
        for lut in (self.__dict__.get("_serving_luts") or {}).values():
            total += _LUT_BYTES_PER_ENTRY * len(lut)
        ent = self.__dict__.get("_scorer_cache")
        if ent:
            K = max(int(getattr(self, "nclasses", 1) or 1), 1)
            for F, batch, _off in ent["shapes"]:
                total += flat + 4 * batch * (F + K) + _TRACE_OVERHEAD
        return total

    def _cached_score(self, X: jax.Array,
                      offset: jax.Array | None = None) -> jax.Array:
        return self._cached_apply(X, offset, "score")

    def _cached_apply(self, X: jax.Array, offset: jax.Array | None,
                      kind: str) -> jax.Array:
        """Dispatch through this model's jitted serving executables,
        tracking warm shapes per (model, schema, padded batch,
        offset?/kind) key and charging this model's resident bytes
        against the cache budget. ``kind`` selects the program:
        "score" -> _score_matrix, "contrib" -> _contrib_matrix (the
        TreeSHAP serving kernel) — both live in the ONE per-model
        cache entry, so eviction/promotion/byte accounting treat a
        model's whole serving footprint as a unit."""
        self._serving_prepare()
        if kind == "contrib":
            self._contrib_prepare()
        with _SCORER_LOCK:
            ent = self.__dict__.get("_scorer_cache")
            if ent is None:
                ent = {"shapes": set()}
                self._scorer_cache = ent
                _SCORER_STATS["models"] += 1
            ctr = self.__dict__.get("_scorer_counters")
            if ctr is None:
                ctr = {"hits": 0, "misses": 0, "promotions": 0}
                self._scorer_counters = ctr
            mid = id(self)
            _SCORER_LRU[mid] = weakref.ref(self)
            _SCORER_LRU.move_to_end(mid)
            skey = (X.shape[1], X.shape[0],
                    "contrib" if kind == "contrib"
                    else offset is not None)
            if skey in ent["shapes"]:
                _SCORER_STATS["hits"] += 1
                ctr["hits"] += 1
            else:
                ent["shapes"].add(skey)
                _SCORER_STATS["misses"] += 1
                ctr["misses"] += 1
                ev = self.__dict__.get("_evicted_shapes")
                if ev and skey in ev:
                    # re-trace of a shape a byte-budget eviction
                    # dropped: a PROMOTION — with the persistent XLA
                    # cache on, its compile is a disk hit (the same
                    # constants rebuilt from the same host arrays
                    # lower to the same HLO), never a cold compile
                    ev.discard(skey)
                    _SCORER_STATS["promotions"] += 1
                    ctr["promotions"] += 1
                # byte accounting + eviction on the MISS branch only:
                # a model's charge changes only when a new shape is
                # traced (device arrays + LUTs are in place before the
                # first score), so the warm hit path pays none of this
                # O(resident models + traced shapes) work under the
                # one lock every scoring thread shares. Purge GC'd
                # models, re-charge this model, then evict least-
                # recently-scored models until the population fits
                # the byte budget (and the optional count cap). The
                # model being scored is never its own victim — a
                # single over-budget model keeps serving.
                for vid in [v for v, r in _SCORER_LRU.items()
                            if r() is None]:
                    del _SCORER_LRU[vid]
                    _SCORER_BYTES.pop(vid, None)
                _SCORER_BYTES[mid] = self._serving_resident_bytes()
                cap = _scorer_cache_cap()
                budget = _scorer_cache_budget()
                while len(_SCORER_LRU) > 1 and (
                        (cap and len(_SCORER_LRU) > cap)
                        or (budget > 0
                            and sum(_SCORER_BYTES.values()) > budget)):
                    vid, ref = next(iter(_SCORER_LRU.items()))
                    if vid == mid:
                        break
                    del _SCORER_LRU[vid]
                    _SCORER_BYTES.pop(vid, None)
                    victim = ref()
                    if victim is None:
                        continue  # model already GC'd: just reclaim
                    victim._serving_evict()
                    _SCORER_STATS["evictions"] += 1
            key = "fn_contrib" if kind == "contrib" else \
                ("fn_off" if offset is not None else "fn")
            fn = ent.get(key)
            if fn is None:
                if kind == "contrib":
                    fn = jax.jit(lambda X: self._contrib_matrix(X))
                elif offset is not None:
                    fn = jax.jit(
                        lambda X, off: self._score_matrix(X, offset=off))
                else:
                    fn = jax.jit(lambda X: self._score_matrix(X))
                ent[key] = fn
        # the (possibly multi-second) trace/compile happens OUTSIDE the
        # lock — jax's own caches are thread-safe; only our bookkeeping
        # needs mutual exclusion
        if kind != "contrib" and offset is not None:
            return fn(X, offset)
        return fn(X)

    def _score(self, X: jax.Array,
               offset: jax.Array | None = None) -> jax.Array:
        """Eager _score_matrix — in-process predict() numerics never
        depend on serving state (a jitted scorer can fuse float ops
        differently, so flipping paths mid-process would let invisible
        REST traffic perturb low-order bits of predict()).

        The jitted-scorer cache belongs to the SERVING entry only
        (score_numpy, which the REST routes ride): one model, many
        requests — worth a per-model trace.  Training-time scoring (CV
        folds, AutoML candidates, validation rounds: many models, a
        call or two each) stays here, where eager tree scoring still
        rides the MODULE-level flat_margin jit that same-shaped fold
        models share."""
        if offset is not None:
            return self._score_matrix(X, offset=offset)
        return self._score_matrix(X)

    # -- compiled TreeSHAP serving (predict_contributions fast path) --------

    def contrib_support(self) -> "str | None":
        """None when this model can serve per-row TreeSHAP
        contributions, else the actionable precondition message — THE
        shared gate for ``predict_contributions``, the serving entry
        ``contrib_numpy``, and the REST route's clean 400 (tree models
        override with the real precondition list)."""
        return (f"model '{self.algo}' does not support "
                "predict_contributions (tree ensembles only)")

    def _shap_sources(self):
        """Hook: (FlatTrees numpy, flat cover numpy) for the TreeSHAP
        path tables — GBMModel flattens its heap trees, a registry
        FlatTreeScorer reads its kept artifact parts."""
        raise NotImplementedError

    def _contrib_enum_mask(self):
        """Hook: the device enum mask the contributions kernel
        canonicalizes NAs with."""
        raise NotImplementedError

    def _contrib_scale_init(self) -> tuple[float, float]:
        """Hook: (scale, init) applied to the raw kernel output."""
        raise NotImplementedError

    def _contrib_prepare(self):
        """Materialize the device TreeSHAP state OUTSIDE the jit
        trace: per-leaf path tables (models/tree/shap.py) plus — when
        it fits the byte gate — the per-pattern contribution table
        that turns the kernel into bit-tests + one gather. Host numpy
        copies are cached separately so a byte-budget eviction (which
        drops only the device arrays) re-promotes with identical
        constants: same HLO, a persistent-cache hit, bitwise-identical
        output."""
        st = self.__dict__.get("_shap_tables")
        ct = self.__dict__.get("_shap_ctab")
        if st is not None and ct is not None:
            return st, ct
        stn = self.__dict__.get("_shap_tables_np")
        if stn is None:
            from .tree.shap import (_PATTERN_TABLE_MAX_BYTES,
                                    build_shap_table_groups,
                                    pattern_table)

            flat, cover = self._shap_sources()
            stn = build_shap_table_groups(flat, cover)
            self._shap_tables_np = stn
            # per-group pattern tables against ONE shared per-model
            # byte budget (a group past the remainder runs the DP
            # kernel) — the tables become per-executable jit constants,
            # so an unbounded total would pin arbitrary device bytes
            # the scorer cache cannot partially evict
            remaining = _PATTERN_TABLE_MAX_BYTES
            ctabs = []
            for g in stn:
                c = pattern_table(g, budget=remaining)
                if c is not None:
                    remaining -= c.nbytes
                ctabs.append(c)
            self._shap_ctab_np = ctabs
        from .tree.shap import ShapTables

        st = [ShapTables(*(jnp.asarray(a) for a in g)) for g in stn]
        ct = [None if c is None else jnp.asarray(c)
              for c in self.__dict__["_shap_ctab_np"]]
        self._shap_tables = st
        self._shap_ctab = ct
        # RETURN the locals (FlatTreeScorer._serving_prepare contract):
        # a concurrent byte-budget eviction may pop the attributes
        # between this return and the caller's read mid-trace
        return st, ct

    def contrib_plan(self, rows: int) -> list[str]:
        """Which implementation each virtual-tree group takes for a
        ``rows``-row contributions dispatch: "dp" (no pattern table —
        the direct weight recurrence), "kernel" (the Pallas
        `flat_shap_tab_kernel`) or "xla" (lowered `flat_shap_tab`).
        THE one decision `_contrib_matrix` traces with, so a caller
        can COUNT how many groups left the kernel (chip_smoke.py
        fails when the kernel is the resolved impl and none took it).
        Resolves at TRACE time (H2O_TPU_SHAP_KERNEL, same semantics as
        hist_impl): the executable cached under this model's scorer
        key keeps its impl until evict/re-promote."""
        from ..ops.shap_kernel import kernel_fits, resolve_impl

        groups, ctabs = self._contrib_prepare()
        use_kernel = resolve_impl() == "pallas"
        return ["dp" if ct is None
                else "kernel" if use_kernel and kernel_fits(g, ct, rows)
                else "xla" for g, ct in zip(groups, ctabs)]

    def _contrib_matrix(self, X: jax.Array) -> jax.Array:
        """[rows, F+1] contributions on raw features via the jitted
        path-enumeration TreeSHAP kernel (the pattern-table fast path
        when the ensemble is shallow enough for it) — the serving twin
        of ``predict_contributions``, which keeps the f64 host
        recursion as the parity oracle the way predict() stays
        eager."""
        from ..ops.shap_kernel import flat_shap_tab_kernel
        from .tree.shap import flat_shap, flat_shap_tab

        groups, ctabs = self._contrib_prepare()
        em = self._contrib_enum_mask()
        phi = None
        for g, ct, impl in zip(groups, ctabs,
                               self.contrib_plan(int(X.shape[0]))):
            if impl == "dp":
                p = flat_shap(g, X, em)
            elif impl == "kernel":
                p = flat_shap_tab_kernel(g, ct, X, em)
            else:
                p = flat_shap_tab(g, ct, X, em)
            # each group accumulates from zero in ITS order, then the
            # groups sum in ascending-D order: without the barrier XLA
            # folds this add into a one-trip group's scatter chain,
            # and the served bytes depend on which impl ran the group
            p = jax.lax.optimization_barrier(p)
            phi = p if phi is None else phi + p
        scale, init = self._contrib_scale_init()
        phi = phi * jnp.float32(scale)
        return phi.at[:, -1].add(jnp.float32(init))

    def _contrib_chunk(self) -> int:
        """Rows per TreeSHAP device dispatch. The kernel's working set
        is O(rows · leaves · depth), so deep/wide ensembles shrink the
        chunk to keep transients bounded; H2O_TPU_CONTRIB_CHUNK caps
        it (default 16384, floored to a power of two so every full
        chunk shares ONE trace key)."""
        try:
            cap = int(float(os.environ.get("H2O_TPU_CONTRIB_CHUNK",
                                           "16384")))
        except ValueError:
            cap = 16384
        cap = max(_SCORE_MIN_BATCH, cap)
        c = _SCORE_MIN_BATCH
        while c * 2 <= cap:
            c *= 2
        cap = c
        stn = self.__dict__.get("_shap_tables_np")
        if stn:
            ld = max(g.feat.shape[1] * g.feat.shape[2] for g in stn)
            fit = max((1 << 24) // max(ld, 1), _SCORE_MIN_BATCH)
            while cap > _SCORE_MIN_BATCH and cap > fit:
                cap //= 2
        return cap

    def contrib_numpy(self, X) -> np.ndarray:
        """Serving entry for per-row TreeSHAP contributions: raw
        [n, F] ndarray (training value space, enum codes / NaN NAs)
        -> [n, F+1] float32 contributions, last column the bias term
        (per-tree expectations + init) — additive to the raw margin.

        Same serving discipline as ``score_numpy``: pow2 batch
        padding into the per-model jitted cache (warm traffic is
        zero-retrace), the circuit breaker + device guard around the
        dispatch, and the ``score.dispatch`` fault point. Large
        batches are chunked to ``_contrib_chunk()`` rows so the
        kernel's [rows × leaves × depth] transients stay bounded —
        every full chunk reuses one executable."""
        from ..runtime.health import device_dispatch, require_healthy
        from ..runtime.lifecycle import breaker_guard

        reason = self.contrib_support()
        if reason:
            raise ValueError(reason)
        require_healthy(fault_site=None)
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2 or X.shape[1] != len(self.feature_names):
            raise ValueError(
                f"contrib_numpy expects [n, {len(self.feature_names)}] "
                f"(features {self.feature_names}), got {X.shape}")
        n = X.shape[0]
        if n == 0:
            raise ValueError("contrib_numpy: empty batch")
        from ..runtime import faults

        with breaker_guard("contributions scoring"), \
                device_dispatch("contributions scoring", locking=False):
            faults.fire("score.dispatch")
            self._contrib_prepare()
            chunk = self._contrib_chunk()
            outs = []
            for s in range(0, n, chunk):
                xs = X[s:s + chunk]
                b = _batch_bucket(xs.shape[0])
                if b != xs.shape[0]:
                    Xp = np.zeros((b, X.shape[1]), dtype=np.float32)
                    Xp[: xs.shape[0]] = xs
                else:
                    Xp = xs
                out = self._cached_apply(jnp.asarray(Xp), None,
                                         "contrib")
                outs.append(np.asarray(out)[: xs.shape[0]])
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def warm_up(self, buckets=None, contributions: bool = False
                ) -> list[int]:
        """Pre-trace the jitted serving scorer at the given batch
        buckets (padded to the pow2 buckets score_numpy actually
        dispatches), so the FIRST real request after a replica goes
        ready pays zero compiles — the operator warm-up contract
        (docs/OPERATOR.md): a scorer-pool replica runs this before its
        ``/readyz`` flips, and warm traffic at any batch size <= the
        largest warmed bucket then adds only cache `hits`.

        The whole pow2 ladder up to the LARGEST requested bucket is
        traced (128, 256, ... top): score_numpy pads any batch to its
        own bucket, so a skipped rung would be a first-request compile
        for batches in that range. ``buckets=None`` reads
        ``H2O_TPU_POOL_WARM_BUCKETS`` (default ``128,1024``). Compiles
        land in the persistent XLA cache (runtime/backend.py), so
        sibling replicas on the same host warm from disk instead of
        recompiling. Returns the bucket sizes warmed, ascending."""
        if not self._serving_jit:
            raise ValueError(
                f"model '{self.algo}' has no jitted serving scorer to "
                "warm (score it through predict() instead)")
        if buckets is None:
            raw = os.environ.get("H2O_TPU_POOL_WARM_BUCKETS", "128,1024")
            buckets = [b for b in raw.replace(" ", "").split(",") if b]
        elif isinstance(buckets, (str, bytes)):
            # a JSON string like "512" would otherwise iterate as the
            # DIGITS ('5','1','2' — top bucket 128) and silently warm
            # the wrong ladder, breaking the zero-miss contract the
            # route then advertises
            raise ValueError(
                f"warm-up buckets must be a list of ints, got the "
                f"string {buckets!r}")
        try:
            top = max(_batch_bucket(int(b)) for b in buckets)
            if min(int(b) for b in buckets) < 1:
                raise ValueError
        except (TypeError, ValueError):
            raise ValueError(
                f"bad warm-up bucket list {buckets!r} (want positive "
                "ints, e.g. 128,1024)") from None
        # the FULL pow2 ladder up to the largest requested bucket:
        # score_numpy pads any n to its own bucket, so skipping a rung
        # would leave batches in that range paying a first-request
        # compile — exactly what the contract forbids
        padded, b = [], _SCORE_MIN_BATCH
        while b <= top:
            padded.append(b)
            b *= 2
        F = len(self.feature_names)
        need_off = bool(getattr(self, "offset_column", None))
        for b in padded:
            # zeros are valid everywhere: enum code 0 is a real level,
            # numerics are finite — the VALUES don't matter, only the
            # (schema, padded-batch, offset?) trace key
            X = np.zeros((b, F), dtype=np.float32)
            off = np.zeros(b, dtype=np.float32) if need_off else None
            self.score_numpy(X, offset=off)
        if contributions:
            # pre-trace the contributions executables too — the ladder
            # is capped at the model's chunk size (contrib_numpy never
            # dispatches a bigger bucket: larger batches split into
            # full chunks + one tail bucket, all <= chunk)
            reason = self.contrib_support()
            if reason:
                raise ValueError(reason)
            done: set[int] = set()
            for b in padded:
                be = min(b, self._contrib_chunk())
                if be in done:
                    continue
                done.add(be)
                self.contrib_numpy(np.zeros((be, F), dtype=np.float32))
        return padded

    def score_numpy(self, X, offset=None) -> np.ndarray:
        """Serving entry: raw [n, F] ndarray (training value space,
        enum codes / NaN NAs) -> [n, K] probabilities or [n]
        predictions, skipping Frame/rollup construction entirely.

        Rows are padded to a power-of-two bucket so warm traffic at
        ANY batch size <= the bucket reuses one compiled executable
        (zero retrace); output is trimmed back to n rows.

        The dispatch runs under the serving circuit breaker
        (runtime/lifecycle.py): consecutive device-dispatch errors trip
        it open and every call is then rejected instantly with
        CircuitOpenError (503 over REST) until the half-open probe
        succeeds — a persistently failing device gets a cooldown, not
        the full brunt of serving traffic."""
        from ..runtime.health import device_dispatch, require_healthy
        from ..runtime.lifecycle import breaker_guard

        require_healthy(fault_site=None)   # fail fast on a locked cloud
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2 or X.shape[1] != len(self.feature_names):
            raise ValueError(
                f"score_numpy expects [n, {len(self.feature_names)}] "
                f"(features {self.feature_names}), got {X.shape}")
        n = X.shape[0]
        if n == 0:
            raise ValueError("score_numpy: empty batch")
        if getattr(self, "offset_column", None) and offset is None:
            raise ValueError(
                f"this model was trained with offset_column="
                f"'{self.offset_column}'; pass offset= per row")
        b = _batch_bucket(n)
        if b != n:
            Xp = np.zeros((b, X.shape[1]), dtype=np.float32)
            Xp[:n] = X
        else:
            Xp = X
        offp = None
        if offset is not None:
            offset = np.asarray(offset, dtype=np.float32).reshape(-1)
            if offset.shape[0] != n:
                raise ValueError(
                    f"offset has {offset.shape[0]} rows, X has {n}")
            offp = np.zeros(b, dtype=np.float32)
            offp[:n] = offset
            offp = jnp.asarray(offp)
        from ..runtime import faults

        with breaker_guard("model scoring"), \
                device_dispatch("model scoring", locking=False):
            # the one rehearsable serving fault point: dispatch_error
            # here feeds the breaker without locking the cloud
            faults.fire("score.dispatch")
            if self._serving_jit:
                out = self._cached_score(jnp.asarray(Xp), offp)
            else:
                out = self._score(jnp.asarray(Xp), offp)
            return np.asarray(out)[:n]

    def _design_matrix(self, frame: Frame) -> jax.Array:
        """[padded, F] float32 in TRAINING value space.

        Enum codes from a scoring frame are remapped to the training
        domain (unseen levels → NA); the reference does the same domain
        adaptation in Model.adaptTestForTrain (hex/Model.java).
        """
        cols = []
        for name in self.feature_names:
            v = frame.vec(name)
            tdom = self.feature_domains.get(name)
            if tdom is not None:
                if not v.is_enum():
                    raise ValueError(
                        f"column '{name}' was categorical at training time "
                        f"but is {v.kind} in the scoring frame")
                if list(v.domain) == tdom:
                    cols.append(v.as_float())
                else:
                    lut = {d: i for i, d in enumerate(tdom)}
                    perm = np.array(
                        [lut.get(d, -1) for d in v.domain] + [-1],
                        dtype=np.int32)  # trailing slot = NA code
                    idx = jnp.where(v.data < 0, len(perm) - 1, v.data)
                    remap = jnp.asarray(perm)[idx]
                    cols.append(jnp.where(remap < 0, jnp.nan,
                                          remap.astype(jnp.float32)))
            else:
                if v.is_enum():
                    raise ValueError(
                        f"column '{name}' was numeric at training time "
                        "but is categorical in the scoring frame")
                cols.append(v.as_float())
        return jnp.stack(cols, axis=1)

    def _predict_raw_device(self, frame: Frame) -> jax.Array:
        """Device half of predict_raw: the [padded(, K)] scoring array
        BEFORE the host transfer, dispatched under the device guard.

        The CV fold pipeline (models/cv.py) consumes the transfer on
        its host stream so fold f+1's train can dispatch while fold
        f's holdout predictions come back — JAX dispatch is async, so
        returning the un-transferred array is exactly the overlap
        point."""
        from ..runtime.health import device_dispatch, require_healthy

        # scoring is not a training chunk boundary: it must never
        # consume an armed train.step fault's skip/count budget
        require_healthy(fault_site=None)
        # the guard covers the design-matrix build too: it dispatches
        # per-column device ops, so a chip halting there must surface
        # the same way as one halting mid-score (ValueErrors from the
        # validation below pass through the guard untouched)
        with device_dispatch("model scoring"):
            X = self._design_matrix(frame)
            off = self._frame_offset(frame)
            if off is not None:
                return self._score(X, off)
            return self._score(X)

    def predict_raw(self, frame: Frame) -> np.ndarray:
        """[n, K] class probabilities, or [n] regression predictions.

        Scoring fails fast on a locked cloud (same gate as training)
        and runs its dispatch under the device guard: a runtime error
        escaping the mesh mid-predict (halted chip, dead ICI link)
        surfaces as ClusterHealthError with the locked-cloud recovery
        message, not a raw XLA traceback."""
        from ..runtime.health import device_dispatch

        out_dev = self._predict_raw_device(frame)
        # the transfer stays under the guard too: an async-dispatched
        # device error surfaces HERE, at the first read
        with device_dispatch("model scoring"):
            return np.asarray(out_dev)[: frame.nrows]

    def _frame_offset(self, frame: Frame) -> jax.Array | None:
        """Validated per-row offset column for an offset-trained model
        (None otherwise) — the ONE offset contract, shared by
        predict_raw and the REST micro-batcher path.

        A model trained with an offset needs it at scoring time too
        (hex/Model.adaptTestForTrain errors likewise [U3]); NA offsets
        propagate: a row with no defined base margin has no defined
        prediction (training likewise drops such rows via w=0) —
        coercing to 0 would return a confident number for a row the
        model cannot score."""
        if not getattr(self, "offset_column", None):
            return None
        if self.offset_column not in frame:
            raise ValueError(
                f"this model was trained with offset_column="
                f"'{self.offset_column}' which is missing from "
                "the scoring frame")
        return frame.vec(self.offset_column).as_float()

    def predict(self, frame: Frame) -> Frame:
        """H2O-style prediction frame: `predict` (+ per-class probs)."""
        return self._prediction_frame(self.predict_raw(frame))

    def _prediction_frame(self, out: np.ndarray) -> Frame:
        """Raw predictions -> the H2O-style frame (shared by predict()
        and the REST micro-batcher, which scores raw matrices)."""
        if self.nclasses > 1:
            labels = out.argmax(axis=1).astype(np.int32)
            cols: dict[str, Any] = {"predict": labels}
            dom = self.response_domain or [str(i) for i in
                                           range(self.nclasses)]
            pf = Frame.from_arrays(cols, domains={"predict": dom})
            for k, name in enumerate(dom):
                pf[f"p{name}"] = Vec.from_numpy(out[:, k])
            return pf
        return Frame.from_arrays({"predict": out})

    def partial_plot(self, frame: Frame, cols: Sequence[str],
                     nbins: int = 20, plot: bool = False
                     ) -> list[Frame]:
        """Partial dependence (h2o model.partial_plot, hex/PartialDependence
        [U3]): per column, sweep a value grid, overwrite the column for
        EVERY row, and record the mean (+sd, +std-error) of the model's
        response — positive-class probability for binomial, prediction
        for regression. Returns one Frame per column; `plot` is accepted
        for h2o-py signature parity and ignored (no display surface)."""
        if self.nclasses > 2:
            raise ValueError("partial_plot supports binomial and "
                             "regression models only")
        del plot
        out_frames = []
        n = frame.nrows
        # one design-matrix build; each grid step overwrites a single
        # column on device instead of re-sharding the whole frame
        X = self._design_matrix(frame)
        # PD means must average the model as it actually predicts —
        # scoring at offset 0 would disagree with predict() on the
        # same frame
        off = self._frame_offset(frame)
        for col in cols:
            if col not in self.feature_names:
                raise ValueError(
                    f"partial_plot: '{col}' is not a model feature")
            j = self.feature_names.index(col)
            v = frame.vec(col)
            tdom = self.feature_domains.get(col)
            if tdom is not None:
                # grid/labels in TRAINING domain space — the design
                # matrix is remapped to it, so sweeping the scoring
                # frame's codes would mislabel every row when domains
                # differ
                grid = list(range(len(tdom)))
                labels = list(tdom)
            else:
                x = v.to_numpy()
                finite = x[~np.isnan(x)]
                if finite.size == 0:
                    raise ValueError(f"partial_plot: '{col}' is all-NA")
                # quantile-spaced grid like the reference's default
                grid = list(np.unique(np.quantile(
                    finite, np.linspace(0, 1, nbins))))
                labels = None
            means, sds, sems = [], [], []
            for gv in grid:
                Xg = _set_col_jit(X, j, float(gv))
                pred = np.asarray(self._score(Xg, off))[:n]
                resp = pred[:, 1] if self.nclasses == 2 else pred
                means.append(float(np.mean(resp)))
                sds.append(float(np.std(resp, ddof=1))
                           if n > 1 else 0.0)
                sems.append(sds[-1] / np.sqrt(n))
            pd_out = Frame()
            if labels is not None:
                pd_out[col] = Vec.from_numpy(
                    np.arange(len(grid), dtype=np.int32), col,
                    domain=labels)
            else:
                pd_out[col] = Vec.from_numpy(
                    np.asarray(grid, dtype=np.float32), col)
            pd_out["mean_response"] = Vec.from_numpy(
                np.asarray(means, dtype=np.float32), "mean_response")
            pd_out["stddev_response"] = Vec.from_numpy(
                np.asarray(sds, dtype=np.float32), "stddev_response")
            pd_out["std_error_mean_response"] = Vec.from_numpy(
                np.asarray(sems, dtype=np.float32),
                "std_error_mean_response")
            out_frames.append(pd_out)
        return out_frames

    def confusion_matrix(self, frame: Frame, y: str,
                         threshold: float | None = None) -> np.ndarray:
        """Confusion matrix (rows actual, cols predicted). Binomial:
        2x2 at `threshold` (F1-optimal when None, like the reference's
        default); multinomial: KxK argmax counts."""
        yv = frame.vec(y)
        preds = self.predict_raw(frame)
        if self.nclasses == 2:
            codes = yv.to_numpy()
            ok = codes >= 0 if yv.is_enum() else ~np.isnan(codes)
            return M.confusion_matrix(codes[ok], preds[ok][:, 1],
                                      threshold=threshold)
        if self.nclasses > 2:
            codes = yv.to_numpy()
            ok = codes >= 0
            lab = preds[ok].argmax(axis=1)
            K = self.nclasses
            cm = np.zeros((K, K))
            np.add.at(cm, (codes[ok].astype(int), lab), 1.0)
            return cm
        raise ValueError("confusion_matrix needs a classification model")

    def model_performance(self, frame: Frame, y: str) -> dict[str, float]:
        return self.performance_of(frame, y, self.predict_raw(frame))

    def performance_of(self, frame: Frame, y: str,
                       out: np.ndarray) -> dict[str, float]:
        """The metrics of predictions ``out`` (`predict_raw`'s form,
        padding rows allowed past the frame's) against ``frame[y]``."""
        yv = frame.vec(y)
        out = out[: frame.nrows]
        ok = ~np.isnan(yv.as_float().__array__()[: frame.nrows]) \
            if not yv.is_enum() else yv.to_numpy() >= 0
        return score_predictions(self.nclasses, self.distribution,
                                 yv.to_numpy()[ok], out[ok])


def score_predictions(nclasses: int, distribution: str,
                      y_true: np.ndarray, preds: np.ndarray
                      ) -> dict[str, float]:
    """Metric dispatch shared by model_performance and CV scoring.

    y_true: class codes (classification) or numeric response; preds:
    [n, K] probabilities or [n] regression predictions — NA rows
    already filtered by the caller.
    """
    if len(y_true) == 0:
        raise ValueError("cannot score an empty holdout "
                         "(no rows with a valid response)")
    if nclasses == 2:
        p1 = preds[:, 1]
        out = {
            "auc": M.roc_auc(y_true, p1),
            "logloss": M.logloss(y_true, p1),
            "rmse": M.rmse(y_true, p1),
        }
        try:
            # threshold table metrics (ModelMetricsBinomial surface);
            # degenerate single-class holdouts keep the basic metrics
            stats = M.binomial_stats(y_true, p1)
            out.update({k: stats[k] for k in
                        ("pr_auc", "gini", "f1", "max_f1_threshold",
                         "mean_per_class_error")})
        except ValueError:
            pass
        return out
    if nclasses > 2:
        lab = preds.argmax(axis=1)
        yc = np.asarray(y_true).astype(int)
        # mean per-class error (reference ModelMetricsMultinomial):
        # average of 1 - recall_k over classes present in the holdout
        errs = [float((lab[yc == k] != k).mean())
                for k in range(nclasses) if np.any(yc == k)]
        # macro one-vs-rest AUC (reference multinomial auc_type=MACRO_OVR)
        aucs = [M.roc_auc((yc == k).astype(np.float32), preds[:, k])
                for k in range(nclasses) if np.any(yc == k)]
        return {
            "logloss": M.multinomial_logloss(y_true, preds),
            "accuracy": M.accuracy(y_true, lab),
            "mean_per_class_error": float(np.mean(errs)) if errs
            else float("nan"),
            "auc": float(np.mean(aucs)) if aucs else float("nan"),
        }
    dist = "poisson" if distribution == "poisson" else "gaussian"
    return {
        "rmse": M.rmse(y_true, preds),
        "mae": M.mae(y_true, preds),
        "r2": M.r2(y_true, preds),
        "mean_residual_deviance": M.mean_residual_deviance(
            y_true, preds, dist),
    }
