"""DRF — distributed random forest on the shared histogram tree core.

Reference: hex/tree/drf/DRF.java (SURVEY.md §2b C10) — SharedTree with
bootstrap row sampling, per-split feature sampling (`mtries`), and no
boosting: trees fit the raw target independently and predictions
average across trees. With g = -y, h = 1 the shared core's leaf value
-G/H is exactly the in-leaf mean of y (CART variance-reduction splits),
so classification leaves hold P(class) directly — no link function.

Depth note: the reference allows max_depth up to 20 via dynamic row
partitions; the dense-heap TPU layout is per-level O(2^d · F · B) — 147
MB of level histograms at depth 12 with 64 bins on 28 features, 11.7 GB
at depth 20 with 20 — so the practical default here is 12 with 64 bins
(XRT-style capped depth). Past 512 histogrammed nodes a level (depth 12
at 64 bins) the histogram kernel serves the level in several blocks of
hi slots (`hist_blocked` in a profile), each at the 512-node level's
cost: a level costs by its nodes x bins and no more (PERF.md §5).

Categorical columns: with ``categorical_encoding="enum"`` (H2O-3's
AUTO for DRF) a forest splits on SETS of an enum's levels, as a GBM
does — one bin a level, the levels of a node in mean-response order
(the shared `_set_order` with h = 1), every prefix scanned — and its
trees are scored by the heap descent over bin codes (`predict`); the
flat scorer, MOJO export, the registry and TreeSHAP refuse such a
model by name. A K-class forest carries sets too: each class tree of a
round orders its levels by its own class's mean and keeps its own
table of the bins that go left.

What each tree saw: a forest's model keeps its trees' keys and hands
out each tree's bag (`tree_bag(t)`) and each node's candidate features
(`tree_candidates(t)`) on demand — what scoring out of bag starts from
(models/gbm.py `TreeDraws`).
"""

from __future__ import annotations

from dataclasses import replace
from typing import Sequence

import numpy as np

from ..frame import Frame
from .base import _feature_names
from .gbm import GBM, GBMModel, GBMParams


class DRFModel(GBMModel):
    algo = "drf"


class DRF(GBM):
    """H2ORandomForestEstimator analog."""

    model_cls = DRFModel

    def __init__(self, ntrees: int = 50, max_depth: int = 12,
                 nbins: int = 64, sample_rate: float = 0.632,
                 mtries: int = -1, min_rows: float = 1.0, **kw):
        kw.setdefault("min_split_improvement", 1e-5)
        super().__init__(ntrees=ntrees, max_depth=max_depth, nbins=nbins,
                         sample_rate=sample_rate, min_rows=min_rows, **kw)
        self.params._drf_mode = True
        self.params.learn_rate = 1.0
        self._mtries_arg = mtries

    def _resolve_mtries(self, y: str, training_frame: Frame,
                        x: Sequence[str] | None,
                        ignored_columns=None, weights_column=None
                        ) -> None:
        """Resolve the mtries default into self.params — sqrt(F) for
        classification, F/3 for regression (reference DRF defaults) —
        from column names only, without materializing the design
        matrix twice.  Shared by train() and compile-ahead so the
        pre-lowered TreeParams carry the same mtries the dispatch
        will."""
        ignored = set(ignored_columns or [])
        ignored.add(y)
        if self.cv_args.fold_column:
            ignored.add(self.cv_args.fold_column)
        if weights_column:
            ignored.add(weights_column)
        F = len(_feature_names(training_frame, x, ignored))
        classification = training_frame.vec(y).is_enum()
        # H2O semantics: -1 → sqrt(F) classification / F/3 regression
        # (the default), -2 → all features, >0 → that many
        if self._mtries_arg == -1:
            m = int(np.sqrt(F)) if classification else max(F // 3, 1)
            self.params.mtries = max(m, 1)
        elif self._mtries_arg == -2:
            self.params.mtries = -1          # TreeParams: <=0 disables
        elif self._mtries_arg > 0:
            self.params.mtries = self._mtries_arg
        else:
            raise ValueError(f"mtries must be -1, -2 or > 0, "
                             f"got {self._mtries_arg}")

    def train(self, y: str, training_frame: Frame,
              x: Sequence[str] | None = None, **kw) -> DRFModel:
        self._resolve_mtries(y, training_frame, x,
                             kw.get("ignored_columns"),
                             kw.get("weights_column"))
        return super().train(y=y, training_frame=training_frame, x=x, **kw)

    def compile_ahead_lowerings(self, y: str, training_frame: Frame,
                                x: Sequence[str] | None = None) -> list:
        try:
            self._resolve_mtries(y, training_frame, x)
        except (ValueError, KeyError):
            return []                 # train() will raise it properly
        return super().compile_ahead_lowerings(y, training_frame, x)
