"""N-fold cross-validation shared by every supervised estimator.

The analog of the reference's ModelBuilder CV plumbing
(hex/ModelBuilder.java computeCrossValidation — fold assignment, one
cv-model per fold trained on the complement, holdout predictions kept
for metrics and for Stacked Ensembles; SURVEY.md §2b C15/C16):

- fold assignment schemes mirror H2O's ``fold_assignment`` enum:
  AUTO(→Random), Random, Modulo, Stratified, plus an explicit
  ``fold_column``;
- each fold model trains on the out-of-fold rows and predicts the
  in-fold rows; the concatenated holdout predictions are scored once
  ("combined holdout metrics", H2O's main CV metric surface) and are
  exactly what StackedEnsemble consumes as level-one data;
- per-fold metrics are summarised mean ± std (H2O's
  cross_validation_metrics_summary).

Estimators opt in by constructing with ``nfolds=...`` (and optionally
``fold_assignment=`` / ``fold_column=``), exactly like h2o-py.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from ..frame import Frame

_CV_KEYS = ("nfolds", "fold_assignment", "fold_column",
            "keep_cross_validation_predictions",
            "keep_cross_validation_models")


@dataclass
class CVArgs:
    """CV knobs popped off an estimator's **kwargs (h2o-py surface)."""

    nfolds: int = 0
    fold_assignment: str = "auto"     # auto | random | modulo | stratified
    fold_column: str | None = None
    keep_cross_validation_predictions: bool = True
    keep_cross_validation_models: bool = True

    @classmethod
    def pop(cls, kw: dict) -> "CVArgs":
        args = {k: kw.pop(k) for k in _CV_KEYS if k in kw}
        out = cls(**args)
        if out.fold_assignment.lower() not in (
                "auto", "random", "modulo", "stratified"):
            raise ValueError(
                f"unknown fold_assignment '{out.fold_assignment}'")
        return out

    @property
    def enabled(self) -> bool:
        return self.nfolds >= 2 or self.fold_column is not None


@dataclass
class CVResult:
    """Attached to a model as .cross_validation_* (h2o-py accessors)."""

    fold_ids: np.ndarray
    models: list | None
    holdout_predictions: np.ndarray | None   # [n, K] probs or [n] preds
    metrics: dict[str, float]                # combined-holdout metrics
    metrics_summary: dict[str, dict[str, float]]  # per-metric mean/std
    fold_metrics: list[dict[str, float]] = field(default_factory=list)


def fold_ids(n: int, nfolds: int, scheme: str = "auto",
             y: np.ndarray | None = None, seed: int = 0) -> np.ndarray:
    """Per-row fold index in [0, nfolds) under an H2O assignment scheme."""
    scheme = scheme.lower()
    if scheme == "modulo":
        return (np.arange(n) % nfolds).astype(np.int32)
    rng = np.random.default_rng(seed if seed >= 0 else None)
    if scheme in ("auto", "random"):
        return rng.integers(0, nfolds, size=n).astype(np.int32)
    if scheme == "stratified":
        if y is None:
            raise ValueError("stratified fold assignment needs a "
                             "categorical response")
        out = np.empty(n, dtype=np.int32)
        start = 0
        for cls_val in np.unique(y):
            idx = np.flatnonzero(y == cls_val)
            rng.shuffle(idx)
            # round-robin within the class, rotating the starting fold
            # across classes so small classes don't all land in fold 0
            out[idx] = (np.arange(len(idx)) + start) % nfolds
            start += len(idx)
        return out
    raise ValueError(f"unknown fold_assignment '{scheme}'")


def _combined_metrics(model, y_true_codes, is_enum, preds,
                      dist: str) -> dict[str, float]:
    """Score concatenated holdout predictions (H2O's headline CV metric)."""
    from .base import score_predictions

    ok = (y_true_codes >= 0) if is_enum else ~np.isnan(y_true_codes)
    return score_predictions(model.nclasses, dist, y_true_codes[ok],
                             preds[ok])


def cross_validate(est, y: str, frame: Frame, cv: CVArgs,
                   train_kw: dict[str, Any], seed: int = 0) -> CVResult:
    """Train one model per fold; returns holdout preds + metric summary.

    ``est`` is the configured estimator; each fold trains a deep copy
    with CV disabled (the reference likewise clones the builder per
    fold, ModelBuilder.cv_makeFramesAndBuilders).
    """
    n = frame.nrows
    yv = frame.vec(y)
    if cv.fold_column is not None:
        fv = frame.vec(cv.fold_column)
        fc = fv.to_numpy()
        has_na = (fc < 0).any() if fv.is_enum() else \
            np.isnan(np.asarray(fc, dtype=np.float64)).any()
        if has_na:
            raise ValueError(f"fold_column '{cv.fold_column}' has NAs")
        codes = np.unique(fc)
        folds = np.searchsorted(codes, fc).astype(np.int32)
        nfolds = len(codes)
        if nfolds < 2:
            raise ValueError("fold_column must define >= 2 folds")
    else:
        nfolds = cv.nfolds
        if nfolds > n:
            raise ValueError(f"nfolds={nfolds} > {n} rows")
        scheme = cv.fold_assignment.lower()
        if scheme == "auto":
            scheme = "random"
        if scheme == "stratified" and not yv.is_enum():
            raise ValueError("stratified folds need a categorical response")
        folds = fold_ids(n, nfolds, scheme,
                         yv.to_numpy() if yv.is_enum() else None, seed)
    counts = np.bincount(folds, minlength=nfolds)
    if (counts == 0).any():
        # the reference rejects degenerate fold maps up front
        # (ModelBuilder.cv_init) rather than training on a full frame
        raise ValueError(
            f"fold assignment left fold(s) "
            f"{np.flatnonzero(counts == 0).tolist()} empty "
            f"(nfolds={nfolds}, nrows={n})")

    tkw = dict(train_kw)
    tkw.pop("validation_frame", None)
    fold_col_ignore = [cv.fold_column] if cv.fold_column else []
    if fold_col_ignore:
        ignored = list(tkw.get("ignored_columns") or []) + fold_col_ignore
        tkw["ignored_columns"] = ignored

    # SHAPE-SHARED fold training (compile-dominated regime): instead of
    # slicing per-fold frames (each a new row shape → every jitted
    # program recompiles per fold AND for the final fit), train each
    # fold model on the FULL frame with the holdout rows' weights
    # zeroed. All fold fits + the final fit then share one row shape,
    # one binned matrix and one set of XLA executables — the dominant
    # share of a cold AutoML's compile count (232 → 166 counted on the
    # CPU mesh). Holdout rows still carry zero
    # loss/histogram/Gram weight (w=0 is the established dead-row
    # convention); frame-global statistics (quantile bin edges, mean
    # imputation, standardization) see the holdout feature
    # distributions — the same global-binning semantics LightGBM's cv
    # uses, and label-free. The trade: each fold model computes over
    # all n rows (n/(nfolds-1)·nfolds extra FLOPs) — a clear win on
    # TPU, where a fold fit is milliseconds and every avoided compile
    # is a REMOTE round trip, and a measured loss on the CPU mesh
    # (+22% wall at 30k rows on 1 core), so it gates on the backend.
    # Above the row threshold the classic sliced-frame CV runs either
    # way (at 10M rows fold FLOPs dwarf compiles). Env overrides:
    # H2O_TPU_CV_SHAPE_SHARE_ROWS=0 disables, =N forces the threshold
    # on any backend.
    import os

    import jax

    _thresh_env = os.environ.get("H2O_TPU_CV_SHAPE_SHARE_ROWS")
    if _thresh_env is not None:
        share = n <= int(_thresh_env)
    else:
        share = jax.default_backend() == "tpu" and n <= 1_000_000
    wcol = tkw.get("weights_column")
    mask_col = "_cv_mask_w_"
    if mask_col in frame.names:       # collision: fall back, stay correct
        share = False
    if share:
        from ..frame import Vec

        base_w = (np.asarray(frame.vec(wcol).as_float())[:n]
                  if wcol else np.ones(n, dtype=np.float32))
        tkw_share = dict(tkw)
        tkw_share["weights_column"] = mask_col
        if wcol:
            # the original weights column is folded into the mask; it
            # must stay EXCLUDED from features (resolve_xy only ignores
            # the active weights_column)
            tkw_share["ignored_columns"] = list(
                tkw.get("ignored_columns") or []) + [wcol]

    y_codes_all = yv.to_numpy() if yv.is_enum() else \
        np.asarray(yv.as_float())[:n]

    # -- fold pipelining (runtime/scheduler.py kill switch) -----------
    # JAX dispatch is async, so fold f's holdout-prediction TRANSFER +
    # metric extraction (host work) can ride a one-worker host stream
    # while fold f+1's train dispatches on the main thread; in sliced
    # mode the same worker also prefetches fold f+1's frame slices when
    # they take select_rows' HOST-gather path (the device-gather path
    # stays on the main thread: only the device-token holder may
    # dispatch device programs — tests/conftest.py rendezvous rule).
    # Results are deterministic either way: tasks run on ONE worker in
    # submission order and every fold's metrics are a pure function of
    # its predictions. H2O_TPU_AUTOML_PIPELINE=0 restores the serial
    # loop bit-for-bit.
    from ..runtime import scheduler as _sched

    pipe = nfolds >= 2 and _sched.pipeline_enabled()
    if not pipe:
        models, fold_metrics = [], []
        preds = None
        for k in range(nfolds):
            hold = folds == k
            clone = copy.deepcopy(est)
            clone.cv_args = CVArgs()        # fold models never recurse
            if share:
                wk = np.where(hold, 0.0, base_w).astype(np.float32)
                vecs = {nm: frame.vec(nm) for nm in frame.names}
                vecs[mask_col] = Vec.from_numpy(wk, mask_col)
                m = clone.train(y=y, training_frame=Frame(vecs),
                                **tkw_share)
                pk_full = m.predict_raw(frame)  # full shape: shared
                pk = pk_full[hold]              # program
            else:
                m = clone.train(y=y,
                                training_frame=frame.select_rows(~hold),
                                **tkw)
                pk = m.predict_raw(frame.select_rows(hold))
            if preds is None:
                preds = np.zeros((n,) + pk.shape[1:], dtype=pk.dtype)
            preds[hold] = pk
            # fold metrics straight from pk — a model_performance()
            # call would rebuild the design matrix and re-score
            fold_metrics.append(_combined_metrics(
                m, y_codes_all[hold], yv.is_enum(), pk, m.distribution))
            models.append(m)
    else:
        models, fold_metrics, preds = _cross_validate_pipelined(
            est, y, frame, folds, nfolds, share,
            tkw_share if share else tkw,
            base_w if share else None, mask_col, y_codes_all, yv, n)

    keys = fold_metrics[0].keys()
    summary = {key: {"mean": float(np.mean([fm[key] for fm in fold_metrics])),
                     "std": float(np.std([fm[key] for fm in fold_metrics]))}
               for key in keys}
    combined = _combined_metrics(models[0], y_codes_all, yv.is_enum(),
                                 preds, models[0].distribution)
    return CVResult(
        fold_ids=folds,
        models=models if cv.keep_cross_validation_models else None,
        holdout_predictions=(preds if
                             cv.keep_cross_validation_predictions else None),
        metrics=combined, metrics_summary=summary,
        fold_metrics=fold_metrics)


def _cross_validate_pipelined(est, y, frame: Frame, folds, nfolds: int,
                              share: bool, tkw: dict, base_w,
                              mask_col: str, y_codes_all, yv, n: int):
    """The pipelined fold loop — numerics identical to the serial one
    (same train calls in the same order on the main thread, same
    per-fold metric computation), with the holdout transfer + metric
    extraction (and eligible slice prefetches) on a one-worker host
    stream. Returns (models, fold_metrics, preds)."""
    from concurrent.futures import ThreadPoolExecutor

    from ..frame import Vec
    from ..frame.frame import _device_gather_min
    from ..runtime.health import device_dispatch
    from ..runtime.mesh import ROWS, global_mesh

    models: list = [None] * nfolds
    fold_metrics: list = [None] * nfolds
    box: dict = {}                      # {"preds": ndarray} once known

    def extract(k, m, hold, out_dev, hold_n):
        # the transfer stays under the device guard, like predict_raw:
        # an async-dispatched device error surfaces at this first read
        with device_dispatch("model scoring"):
            arr = np.asarray(out_dev)
        if share:
            pk = arr[:n][hold]
        else:
            pk = arr[:hold_n]
        if "preds" not in box:
            box["preds"] = np.zeros((n,) + pk.shape[1:], dtype=pk.dtype)
        box["preds"][hold] = pk
        fold_metrics[k] = _combined_metrics(
            m, y_codes_all[hold], yv.is_enum(), pk, m.distribution)

    # slice prefetch rides the worker ONLY on select_rows' host-gather
    # path; past the device-gather threshold the gather is a device
    # program and belongs to the main (device-token) thread
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(global_mesh(), P(ROWS))
    prefetch_ok = (not share) and (
        n < _device_gather_min() or not sharding.is_fully_addressable)

    def make_slices(hold):
        return frame.select_rows(~hold), frame.select_rows(hold)

    pool = ThreadPoolExecutor(max_workers=1,
                              thread_name_prefix="h2o-cv-host")
    slice_futs: list = [None] * nfolds
    metric_futs: list = [None] * nfolds
    try:
        for k in range(nfolds):
            # fail fast like the serial loop: a COMPLETED earlier
            # fold's extraction error surfaces before the next train
            # dispatches (done() keeps the check non-blocking, so the
            # pipeline overlap is untouched)
            for fut in metric_futs[:k]:
                if fut is not None and fut.done():
                    fut.result()
            hold = folds == k
            clone = copy.deepcopy(est)
            clone.cv_args = CVArgs()        # fold models never recurse
            if share:
                wk = np.where(hold, 0.0, base_w).astype(np.float32)
                vecs = {nm: frame.vec(nm) for nm in frame.names}
                vecs[mask_col] = Vec.from_numpy(wk, mask_col)
                tr_frame, hold_frame = Frame(vecs), frame
            elif slice_futs[k] is not None:
                tr_frame, hold_frame = slice_futs[k].result()
            else:
                tr_frame, hold_frame = make_slices(hold)
            if prefetch_ok and k + 1 < nfolds:
                # submitted BEFORE the train so it overlaps fold k's
                # device work (FIFO worker: it runs after fold k-1's
                # metric extraction)
                slice_futs[k + 1] = pool.submit(make_slices,
                                                folds == (k + 1))
            m = clone.train(y=y, training_frame=tr_frame, **tkw)
            models[k] = m
            out_dev = m._predict_raw_device(hold_frame)
            metric_futs[k] = pool.submit(extract, k, m, hold, out_dev,
                                         hold_frame.nrows)
        for fut in metric_futs:
            fut.result()            # re-raise fold task errors in order
    finally:
        pool.shutdown(wait=True)
    return models, fold_metrics, box["preds"]


def finalize_train(est, model, y: str, training_frame: Frame,
                   train_kw: dict[str, Any],
                   validation_frame: Frame | None = None):
    """Post-train hook every supervised estimator calls: validation
    metrics + optional CV. Returns the (annotated) model."""
    if validation_frame is not None:
        model.validation_metrics = model.model_performance(
            validation_frame, y)
    cv = getattr(est, "cv_args", None)
    if cv is not None and cv.enabled:
        seed = int(getattr(est.params, "seed", 0) or 0)
        model.cv = cross_validate(est, y, training_frame, cv, train_kw,
                                  seed=seed)
    else:
        model.cv = None
    return model
