"""XGBoost-hist semantics on the shared TPU histogram tree core.

The reference bundles native XGBoost behind a JNI extension
(h2o-extensions/xgboost: XGBoost.java converts Frame→DMatrix and drives
xgboost4j with tree_method=hist/gpu_hist; Rabit allreduces histograms —
SURVEY.md §2b C14). The TPU rebuild needs no foreign library: the same
regularized-gain hist algorithm runs on the shared tree core
(models/tree/core.py), whose per-level psum over the ROWS mesh axis IS
the Rabit allreduce, now on ICI.

XGBoost-specific semantics implemented here, distinct from H2O GBM:
- split gain regularized by `reg_lambda` (default 1.0), `reg_alpha`,
  `gamma` (min loss reduction), `min_child_weight` on hessian mass;
- objective aliases (reg:squarederror, binary:logistic, multi:softprob,
  count:poisson) and base_score-style flat init;
- learning-to-rank: rank:pairwise and rank:ndcg (LambdaMART) over a
  query `group_column`, the reference's MSLR-WEB30K lambdarank config
  (BASELINE.json:9). They are distributions of the boost plan like any
  other (`GBM.train` → `BoostPlan`, mode ``single``): the query layout
  is an operand of `core._boost_jit` and the pairwise gradients are
  taken inside its scan, by `core._round_grad_hess` (models/tree/rank.py
  has the semantics and the layout). EVERY pair (i, j) of a query with y_i > y_j is taken, the
  rank by a stable sort of the margins, maxDCG over the whole list —
  where XGBoost samples or truncates a query's pairs
  (`lambdarank_pair_method`, `lambdarank_num_pair_per_sample`) and
  normalises at its truncation level: deterministic, so that a plain
  reference can hold it (bench/reference/lambdamart_plain.py).
"""

from __future__ import annotations

from typing import Sequence

import jax

from .. import metrics as M
from ..frame import Frame
from .gbm import GBM, GBMModel
from .tree.rank import grouped

_OBJECTIVE_ALIASES = {
    "reg:squarederror": "gaussian",
    "reg:linear": "gaussian",
    "binary:logistic": "bernoulli",
    "multi:softprob": "multinomial",
    "multi:softmax": "multinomial",
    "count:poisson": "poisson",
    "rank:pairwise": "rank:pairwise",
    "rank:ndcg": "rank:ndcg",
}


class XGBoostModel(GBMModel):
    algo = "xgboost"
    _group_column: str | None = None

    def _score_matrix(self, X: jax.Array,
                      offset: jax.Array | None = None) -> jax.Array:
        if grouped(self.distribution):
            return self._margins(X, offset)  # raw ranking scores
        return super()._score_matrix(X, offset)

    def model_performance(self, frame: Frame, y: str,
                          group_column: str | None = None,
                          k: int = 10) -> dict[str, float]:
        if grouped(self.distribution):
            gcol = group_column or self._group_column
            score = self.predict_raw(frame)
            yv = frame.vec(y).to_numpy()
            g = frame.vec(gcol).to_numpy()
            return {f"ndcg@{k}": M.ndcg(yv, score, g, k=k)}
        return super().model_performance(frame, y)


# ---------------------------------------------------------------------------
# Estimator
# ---------------------------------------------------------------------------

class XGBoost(GBM):
    """H2OXGBoostEstimator analog (tree_method=hist on TPU).

    XGBoost defaults differ from H2O GBM: eta .3, depth 6, lambda 1,
    min_child_weight 1 (hessian mass, not row count).
    """

    model_cls = XGBoostModel

    def __init__(self, ntrees: int = 50, max_depth: int = 6,
                 learn_rate: float = 0.3, eta: float | None = None,
                 reg_lambda: float = 1.0, reg_alpha: float = 0.0,
                 gamma: float = 0.0, min_child_weight: float = 1.0,
                 subsample: float = 1.0,
                 colsample_bytree: float = 1.0,
                 nbins: int = 256, objective: str | None = None,
                 booster: str = "gbtree", tree_method: str = "hist",
                 **kw):
        if booster != "gbtree":
            raise ValueError(f"only booster=gbtree is supported: {booster}")
        if tree_method not in ("hist", "gpu_hist", "approx", "auto"):
            raise ValueError(f"unknown tree_method {tree_method}")
        # H2O-side spellings map onto the XGBoost-native ones (the
        # reference's XGBoostV3 schema does the same aliasing)
        if "min_rows" in kw:
            min_child_weight = kw.pop("min_rows")
        if "sample_rate" in kw:
            subsample = kw.pop("sample_rate")
        if "col_sample_rate_per_tree" in kw:
            colsample_bytree = kw.pop("col_sample_rate_per_tree")
        dist = kw.pop("distribution", "auto")
        if objective is not None:
            if objective not in _OBJECTIVE_ALIASES:
                raise ValueError(f"unknown objective {objective}")
            dist = _OBJECTIVE_ALIASES[objective]
        super().__init__(
            ntrees=ntrees, max_depth=max_depth,
            learn_rate=eta if eta is not None else learn_rate,
            reg_lambda=reg_lambda, reg_alpha=reg_alpha,
            min_split_improvement=gamma,
            min_child_weight=min_child_weight,
            sample_rate=subsample,
            col_sample_rate_per_tree=colsample_bytree,
            nbins=nbins, min_rows=1.0,
            distribution=dist, **kw)

    def train(self, y: str, training_frame: Frame,
              x: Sequence[str] | None = None,
              group_column: str | None = None, **kw) -> XGBoostModel:
        if grouped(self.params.distribution) and group_column is None:
            raise ValueError("ranking objectives need group_column")
        return super().train(y=y, training_frame=training_frame, x=x,
                             group_column=group_column, **kw)
