"""GBM — gradient boosting with the shared histogram tree core.

Reference behavior: hex/tree/gbm/GBM.java driving SharedTree
(SURVEY.md §3.4): per tree, score-and-update residuals, then per level
one full-cluster histogram MRTask + split finding. Here the whole tree
builds in one jitted shard_map (models/tree/core.py); the outer loop
over trees is host-side Python, as in the reference's Driver.

Distributions (hex/genmodel DistributionFamily analogs):
  gaussian     g = f - y,            h = 1
  bernoulli    g = p - y,            h = p(1-p)       (logit link)
  multinomial  K trees/iter, softmax gradient
  poisson      g = exp(f) - y,       h = exp(f)        (log link)
  gamma        g = 1 - y·exp(-f),    h = y·exp(-f)      (log link)
  tweedie      compound-poisson deviance at power 1.5   (log link)
  laplace      g = sign(f - y),      h = 1              (L1 loss)
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, replace
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..frame import Frame
from ..runtime.health import device_dispatch, require_healthy
from ..runtime.mesh import ROWS, global_mesh, row_sharding
from ..runtime.telemetry import phase_span
from .base import (Model, TrainData, _feature_names, resolve_response,
                   resolve_xy)
from .tree.binning import (BinSpec, apply_bins, apply_bins_jit,
                           bin_code_dtype, fit_bins, fused_fit_bins,
                           matrix_bins, resolve_encoding, set_features)
from .tree.core import (BoostParams, FlatTrees, Tree, TreeParams,
                        _boost_jit, _boost_multi_jit, descend_tree,
                        flat_margin, flatten_cover, flatten_trees,
                        goss_round_keys, hist_level_forms,
                        level_hist_bytes, multi_grow_vmapped,
                        node_lookup_forms,
                        predict_tree, round_keys, set_split_reason)
from .tree.rank import (RankLayout, grouped, groups_abstract, ndcg_at,
                        rank_layout)


@dataclass
class GBMParams:
    ntrees: int = 50
    max_depth: int = 5
    learn_rate: float = 0.1
    min_rows: float = 10.0
    nbins: int = 256
    # H2O-3's own: the levels an enum keeps a bin each for (past them
    # contiguous code ranges share bins), and how an enum is split.
    # "label_encoder": ordinally on its code, in a matrix of `nbins`
    # bins (so past nbins-1 levels code ranges share bins whatever
    # nbins_cats says). "enum": one bin a level up to nbins_cats, a
    # split sends a SET of levels left — H2O-3's AUTO for GBM. "AUTO"
    # here is still label_encoder: not every serving format carries a
    # set yet (`refuse_set_splits`, `core.require_ordinal`).
    nbins_cats: int = 1024
    categorical_encoding: str = "AUTO"
    sample_rate: float = 1.0
    col_sample_rate_per_tree: float = 1.0
    mtries: int = -1                     # per-node feature sampling (DRF)
    distribution: str = "auto"
    reg_lambda: float = 0.0
    reg_alpha: float = 0.0
    min_child_weight: float = 0.0        # XGBoost-style hessian-mass floor
    min_split_improvement: float = 1e-5  # H2O default
    seed: int = 0
    score_every: int = 0                 # 0 = score only at end
    # continue training from a previous model (reference SharedTree
    # checkpoint semantics, SURVEY.md §5.4): ntrees is the TOTAL count
    checkpoint: object = None
    # histogram kernel selection (ops/histogram: auto|segment|pallas)
    _hist_impl: str = "auto"
    # DRF mode: no shrinkage on margins, trees vote/average
    _drf_mode: bool = False


# module-level jitted transforms: a fresh jax.jit per call would
# retrace every scoring event (the jit-inside-a-loop antipattern), and
# an eager sharded op risks the XLA:CPU rendezvous flake
_jit_sigmoid = jax.jit(jax.nn.sigmoid)
_jit_softmax = jax.jit(functools.partial(jax.nn.softmax, axis=1))
_jit_exp = jax.jit(jnp.exp)
_jit_min_pos = jax.jit(
    lambda y, w: jnp.nanmin(jnp.where(w > 0, y, jnp.inf)))
# max histogram work units (rows·F·nbins·2^depth summed over a chunk's
# trees) per compiled dispatch — see BoostPlan.chunks
_DISPATCH_BUDGET = 3e12

# h ≡ 1 losses accumulate 2-channel histograms (1/3 fewer MXU passes +
# smaller psums) — the ONE membership list _make_tree_params keys on
_UNIT_HESS_DISTS = ("gaussian", "laplace", "quantile", "huber")


def _make_tree_params(p: "GBMParams", distribution: str,
                      n_bins: int | None = None,
                      set_feats: tuple = ()) -> TreeParams:
    """GBMParams + resolved distribution -> the TreeParams the boost
    dispatch is traced with (`boost_plan` is the one caller on the
    pointwise path). ``n_bins``: the binned matrix's bins where they
    are not ``p.nbins`` (a job with ``set_feats``: `matrix_bins`)."""
    return TreeParams(max_depth=p.max_depth, n_bins=n_bins or p.nbins,
                      set_feats=set_feats,
                      min_rows=p.min_rows, reg_lambda=p.reg_lambda,
                      reg_alpha=p.reg_alpha,
                      gamma=p.min_split_improvement, mtries=p.mtries,
                      min_child_weight=p.min_child_weight,
                      hist_impl=p._hist_impl,
                      unit_hess=(p._drf_mode or
                                 distribution in _UNIT_HESS_DISTS))


def goss_params(p: "GBMParams", distribution: str) -> tuple[float, float]:
    """(top_a, rand_b) of GOSS gradient-based one-side sampling
    (arXiv:1809.04559) — (0.0, 0.0) when off. THE one env reader:
    H2O_TPU_GOSS=1 activates it for the boosted-tree growers (GBM +
    XGBoost-hist); DRF stays bagged/unsampled, and a grouped objective
    under it is refused by name (`refuse_grouped`). Knobs are read at
    train time, so AutoML plan entries and CV folds inherit them
    uniformly."""
    if p._drf_mode:
        return 0.0, 0.0
    if os.environ.get("H2O_TPU_GOSS", "0") != "1":
        return 0.0, 0.0
    a = float(os.environ.get("H2O_TPU_GOSS_TOP_A", "0.1"))
    b = float(os.environ.get("H2O_TPU_GOSS_RAND_B", "0.1"))
    if not (0.0 <= a < 1.0 and 0.0 < b <= 1.0 and a + b <= 1.0):
        raise ValueError(
            f"bad GOSS knobs: H2O_TPU_GOSS_TOP_A={a} / "
            f"H2O_TPU_GOSS_RAND_B={b} — need 0 <= a < 1, 0 < b, "
            "a + b <= 1")
    return a, b


def _make_boost_params(p: "GBMParams", distribution: str) -> BoostParams:
    """The BoostParams twin of _make_tree_params."""
    goss_a, goss_b = goss_params(p, distribution)
    return BoostParams(
        distribution=distribution,
        learn_rate=1.0 if p._drf_mode else p.learn_rate,
        sample_rate=p.sample_rate,
        col_sample_rate_per_tree=p.col_sample_rate_per_tree,
        drf_mode=p._drf_mode,
        goss_a=goss_a, goss_b=goss_b)


def _draws_from_keys(p: "GBMParams", F: int) -> bool:
    """True when a round draws anything from its key: a row sample, a
    column sample, or `mtries` candidates a node."""
    return p.sample_rate < 1.0 or p.col_sample_rate_per_tree < 1.0 \
        or 0 < p.mtries < F


# What cannot carry a set split yet (categorical_encoding="enum"), by
# the fact that says a job would reach it and the name the error gives
# it. The scoring side's list is `core.require_ordinal`'s callers.
_NO_SET_SPLITS_YET = (
    ("xgboost", "the XGBoost facade"),
    ("multinomial", "the multinomial grower"),
    ("checkpoint", "checkpoint restart"),
    ("efb", "an EFB-bundled frame"),
    ("goss", "GOSS (H2O_TPU_GOSS)"),
    ("ooc", "the out-of-core path"),
)


def refuse_set_splits(**facts) -> None:
    """THE one check of what trains with set splits: raise, naming it,
    for the first true fact of `_NO_SET_SPLITS_YET`."""
    for fact, name in _NO_SET_SPLITS_YET:
        if facts.get(fact):
            raise ValueError(
                f"categorical_encoding='enum': {name} cannot carry a "
                "set split yet (a split that sends a set of an enum's "
                "levels left); train with "
                "categorical_encoding='label_encoder', or a bernoulli / "
                "regression GBM or DRF in device memory")


# What cannot carry a GROUPED objective yet (rank:pairwise / rank:ndcg:
# a gradient that hangs on a row's query, `rank.grouped`), as
# `_NO_SET_SPLITS_YET` above: the fact and the name the error gives it.
_NO_GROUPED_YET = (
    ("efb", "an EFB-bundled frame"),
    ("goss", "GOSS (H2O_TPU_GOSS)"),
    ("ooc", "the out-of-core path"),
    ("checkpoint", "checkpoint restart"),
    ("offset", "offset_column"),
    ("cv", "cross-validation (its folds are not group-aware)"),
)


def refuse_grouped(distribution: str, **facts) -> None:
    """THE one check of what trains a grouped objective: raise, naming
    it, for the first true fact of `_NO_GROUPED_YET`."""
    for fact, name in _NO_GROUPED_YET:
        if facts.get(fact):
            raise ValueError(
                f"{distribution}: {name} cannot carry a grouped "
                "objective yet (pairwise gradients over the rows of a "
                "query); train it in device memory, unbundled, from "
                "scratch, without an offset or cross-validation")


# THE table of boosting modes: the jitted program that serves a job.
# A device trace shows each as module `jit_<__name__>`;
# telemetry.TRAIN_PROGRAMS["boost"] lists the same two names
# (tests/test_telemetry.py holds it to this table).
_BOOST_PROGRAMS = {"single": _boost_jit,        # one tree a round
                   "multi": _boost_multi_jit}   # K class trees a round


class BoostPlan(NamedTuple):
    """What a training job is, decided ONCE from what is known before
    any device work: which program serves it, with which static
    arguments and operands, in which dispatches, in HBM or streamed.
    `_train`, `_boost_in_hbm` and `compile_ahead_lowerings` all execute
    this plan, so what is lowered ahead is what is dispatched.
    ``F`` is the HISTOGRAM width (the bundled width under EFB)."""

    p: GBMParams
    distribution: str
    K: int                  # class trees a round (1: single output)
    F: int
    tp: TreeParams
    bp: BoostParams
    hist_bytes: int         # level histograms live at the deepest level
    budget: float           # H2O_TPU_HIST_BYTES_BUDGET, read once
    mesh: Any
    # the query layout of a grouped objective (`rank.RankLayout`: the
    # last operand of `_boost_jit`), None for a pointwise one
    rank: RankLayout | None = None
    # how a round's K class trees are grown: "vmap" (one batched
    # histogram call a level) or "map" (a class at a time, past
    # `core._MULTI_HIST_BUDGET`); None for one tree a round
    class_batch: str | None = None

    @property
    def mode(self) -> str:
        """This job's row of `_BOOST_PROGRAMS` (a forest's by its K)."""
        return "multi" if self.K > 1 else "single"

    @property
    def grouped(self) -> bool:
        """The gradients hang on a row's query (rank:*): the job takes
        a query layout, skips the EFB planning pass whatever the
        frame's width, and is refused by name wherever a layout cannot
        be carried (`refuse_grouped`)."""
        return grouped(self.distribution)

    @property
    def score_every(self) -> int:
        """Rounds between scoring events inside the loop (0: none; a
        forest scores once, at the end)."""
        return 0 if self.bp.drf_mode else (self.p.score_every or 0)

    @property
    def device_init(self) -> bool:
        """A fresh job's prior and margin come from `_init_margin`: a
        forest and a ranker start from zeros, laplace from the host's
        median."""
        return not self.bp.drf_mode and not self.grouped \
            and self.distribution != "laplace"

    def validate(self, algo: str = "gbm", ckpt=None, efb: bool = False,
                 padded: int | None = None, offset: bool = False,
                 cv: bool = False) -> None:
        """Refuse, before the frame is binned, what cannot train. The
        arguments are what `_train` knows beside the plan, and matter
        to a job with set splits (`refuse_set_splits`) or a grouped
        objective (`refuse_grouped`) alone."""
        p = self.p
        if self.grouped:
            refuse_grouped(
                self.distribution, efb=efb, goss=self.bp.goss_b > 0,
                ooc=padded is not None
                and self.ooc_chunk(padded, ckpt) is not None,
                checkpoint=ckpt is not None, offset=offset, cv=cv)
        if any(self.tp.set_feats):
            refuse_set_splits(
                xgboost=algo == "xgboost", multinomial=self.K > 1,
                checkpoint=ckpt is not None, efb=efb,
                goss=self.bp.goss_b > 0,
                ooc=padded is not None
                and self.ooc_chunk(padded, ckpt) is not None)
        if self.bp.goss_b > 0 and p.sample_rate < 1.0:
            raise ValueError(
                "H2O_TPU_GOSS replaces row subsampling — train with "
                f"sample_rate=1.0 (got {p.sample_rate}) or disable "
                "the GOSS knob")
        # deep-tree memory: the dense heap's per-level histogram
        # working set is O(2^d·F·B·C) — the SAME accounting
        # (core.level_hist_bytes) the multinomial vmap branch uses, K×
        # only when the grower really vmaps. ANY depth whose level
        # histograms fit the budget trains (depth 16 with 4 features ×
        # 16 bins is ~25 MB); one that cannot fails HERE with sizing
        # guidance instead of an opaque device OOM mid-boost.
        if self.hist_bytes > self.budget:
            raise ValueError(
                f"max_depth={p.max_depth} with {self.F} histogram "
                f"columns x {self.tp.n_bins} bins needs "
                f"~{self.hist_bytes / 2 ** 20:.0f} MiB of "
                f"level histograms (> budget "
                f"{self.budget / 2 ** 20:.0f} MiB). "
                "Lower max_depth or nbins, drop features, or raise "
                "H2O_TPU_HIST_BYTES_BUDGET if the device has room.")

    def chunks(self, padded: int, start_t: int = 0) -> list[int]:
        """Tree counts of the compiled dispatches of the in-HBM loop.
        One dispatch's work is capped: the TPU worker (behind its RPC
        deadline) kills executions that run for minutes. Work/round ~
        rows·F·nbins·2^depth·K (deepest level dominates with sibling
        subtraction): 1.9e12 units are 1.0 s on a v5e at depth 6 x 256
        bins, so `_DISPATCH_BUDGET` keeps a dispatch within seconds and
        leaves shallow shapes in a single one. A forest's trees go by
        the same rule, one a scan step."""
        p = self.p
        per_round = padded * max(self.F, 1) * self.tp.n_bins \
            * (2 ** p.max_depth) * self.K
        budget_chunk = max(1, int(_DISPATCH_BUDGET // per_round))
        score = self.score_every
        out: list[int] = []
        t = start_t
        while t < p.ntrees:
            n = min(budget_chunk, p.ntrees - t)
            if score:
                # stop at score boundaries, but never let the budget
                # densify the scoring cadence (each scoring event is
                # a blocking host sync)
                n = min(n, score - (t - start_t) % score)
            out.append(n)
            t += n
        return out

    def ooc_chunk(self, padded: int, ckpt) -> int | None:
        """Rows per host-pinned chunk when out-of-core mode engages,
        None for the in-HBM path.

        Trigger: H2O_TPU_OOC=1 forces it (where eligible), =0 disables;
        otherwise it engages when the uint8 binned matrix would exceed
        the headroom the budget leaves after the level histograms.
        Eligibility is pointwise single-output boosting — multinomial,
        DRF voting, huber (global residual quantile per round),
        checkpoint continuation, a scoring cadence (score_every: the
        stream scores once at the end, and a parameter must never be
        dropped silently), and row/column/per-node feature sampling
        (sample_rate / col_sample_rate_per_tree < 1, mtries > 0: the
        streamed key schedule differs from the fused core's, so the
        MODEL would depend on the chunk-size perf knob or on which
        path engaged) stay in-HBM (docs/SCALING.md). Multi-host (DCN)
        meshes stay in-HBM too: the chunk staging `device_put` cannot
        target other processes' devices (same guard as
        Vec.select_rows)."""
        p = self.p
        env = os.environ.get("H2O_TPU_OOC", "auto")
        if env == "0":
            return None
        if self.K > 1 or self.bp.drf_mode or ckpt is not None or \
                self.distribution == "huber" or p.score_every or \
                p.sample_rate < 1.0 or p.col_sample_rate_per_tree < 1.0 \
                or p.mtries > 0:
            return None
        if not row_sharding(self.mesh).is_fully_addressable:
            return None
        if env != "1" and \
                padded * self.F <= max(self.budget - self.hist_bytes, 0):
            return None
        from .tree.ooc import chunk_rows_for

        return chunk_rows_for(padded, self.F, self.budget,
                              self.hist_bytes)

    def operands(self, binned, y, w, margin, keys, goss_keys,
                 efb=None) -> tuple:
        """The program's whole argument list — arrays for a dispatch,
        ShapeDtypeStructs for a lowering. Under GOSS the scan runs
        over a (round keys, goss keys) pair; with GOSS off the scanned
        operand is the plain key array, byte-identical to a build
        without the feature."""
        if self.bp.goss_b > 0:
            keys = (keys, goss_keys)
        statics = (self.tp, self.bp, self.K, self.mesh)
        if self.rank is not None:
            statics += (self.rank.groups,)
        return (binned, y, w, margin, keys, efb) + statics

    def dispatch(self, binned, y, w, margin, kc, n: int, efb=None,
                 goss_keys=None) -> tuple:
        """``n`` rounds in ONE compiled dispatch → (margin, trees
        [n·K, N], the rounds' keys [n], the GOSS overflow scalar or
        None). Every draw a round makes is a pure function of its key
        (`TreeDraws`)."""
        keys = round_keys(kc, n)
        out = _BOOST_PROGRAMS[self.mode](*self.operands(
            binned, y, w, margin, keys, goss_keys, efb))
        trees = out[1]
        if self.K > 1:
            # [n, K, ...] -> interleaved [n*K, ...] (class fastest),
            # the layout _margins de-interleaves with a[k::K]
            trees = jax.tree.map(
                lambda a: a.reshape((-1,) + a.shape[2:]), trees)
        return out[0], trees, keys, \
            (out[2] if self.bp.goss_b > 0 else None)

    def lowerings(self, padded: int) -> list[tuple]:
        """(jitted program, abstract arguments) of what a fresh in-HBM
        job of ``padded`` rows dispatches, in its order: `_init_margin`,
        then the boost program once a distinct dispatch. efb=None: EFB
        plans are data-dependent, and compile-ahead serves the frames
        the auto gate keeps unbundled."""
        rows = row_sharding(self.mesh)
        row_s = jax.ShapeDtypeStruct((padded,), jnp.float32, sharding=rows)
        binned_s = jax.ShapeDtypeStruct(
            (padded, self.F), bin_code_dtype(self.tp.n_bins, self.p.nbins),
            sharding=rows)
        out = []
        if self.device_init:
            out.append((_init_margin, (row_s, row_s, row_s,
                                       self.distribution, self.K,
                                       self.mesh)))
        # every dispatch takes its margin sharded by rows: the first as
        # `_initial_margin` makes it (`_init_margin`'s prior, [rows, K]
        # for K classes; the zeros a forest's sum of leaf values starts
        # from), every later one the output of the one before — so a
        # job's dispatches of one length are ONE executable
        margin_s = jax.ShapeDtypeStruct(
            (padded,) if self.K == 1 else (padded, self.K),
            jnp.float32, sharding=rows)
        keydt = jax.eval_shape(lambda: jax.random.key(0)).dtype
        for n in dict.fromkeys(self.chunks(padded)):
            keys_s = jax.ShapeDtypeStruct((n,), keydt)
            args = self.operands(binned_s, row_s, row_s, margin_s,
                                 keys_s, keys_s)
            if self.rank is not None:
                args = args[:-1] + (groups_abstract(args[-1]),)
            out.append((_BOOST_PROGRAMS[self.mode], args))
        return out


def boost_plan(p: "GBMParams", distribution: str, nclasses: int, F: int,
               mesh=None, n_bins: int | None = None,
               set_feats: tuple = (), rank: RankLayout | None = None
               ) -> BoostPlan:
    """The plan of ``p`` on a resolved response and ``F`` histogram
    columns; ``n_bins`` / ``set_feats`` as `_make_tree_params` takes
    them, ``rank`` the query layout of a grouped objective. Bad GOSS
    knobs raise here (`goss_params`)."""
    K = nclasses if nclasses > 2 else 1
    tp = _make_tree_params(p, distribution, n_bins, set_feats)
    hist_bytes = level_hist_bytes(tp, F)
    class_batch = None
    if K > 1:
        # the memory that will actually be live: K× only when the
        # grower really vmaps (past its budget it falls to lax.map
        # with one class's histograms live)
        class_batch = "vmap" if multi_grow_vmapped(tp, F, K) else "map"
        if class_batch == "vmap":
            hist_bytes *= K
    budget = float(os.environ.get("H2O_TPU_HIST_BYTES_BUDGET", 2 ** 30))
    return BoostPlan(p, distribution, K, F, tp,
                     _make_boost_params(p, distribution), hist_bytes,
                     budget, mesh or global_mesh(), rank, class_batch)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _init_margin(y, w, off, dist: str, K: int, mesh):
    """(init score, starting margin) fully ON DEVICE — the round-2 path
    transferred the prior sums to the host before the first boost
    dispatch, a blocking host round trip per train() that AutoML pays
    per model. The host reads `init` back only after the boosting
    chunks are enqueued. Pad/NA rows carry y=0, w=0 (resolve_xy).

    ``off`` is the per-row offset margin (zeros when none): the margin
    starts at init + off and the init prior is the intercept MLE GIVEN
    the offset (hex/tree/gbm GBM getInitialValue solves the same
    offset-aware prior [U3]) — closed form for gaussian/poisson/gamma,
    3 Newton steps from logit(ȳ) for bernoulli. A K-class margin
    [rows, K] lies as the rows of ``mesh`` do (a broadcast of the class
    priors would come out replicated: a first boost dispatch unlike
    every later one, and a second executable of the same program)."""
    w_sum = jnp.sum(w)
    if dist == "bernoulli":
        p1 = jnp.clip(jnp.sum(y * w) / w_sum, 1e-6, 1 - 1e-6)
        init0 = jnp.log(p1 / (1 - p1))

        def newton(_, b):
            p = jax.nn.sigmoid(b + off)
            num = jnp.sum(w * (y - p))
            den = jnp.clip(jnp.sum(w * p * (1.0 - p)), 1e-10, None)
            return b + num / den

        init = lax.fori_loop(0, 3, newton, init0)
        return init, init + off
    if dist == "multinomial":
        cls_w = jax.ops.segment_sum(
            w, jnp.where(w > 0, y, K).astype(jnp.int32),
            num_segments=K + 1)[:K]
        init = jnp.log(jnp.clip(cls_w / w_sum, 1e-8, None)).astype(
            jnp.float32)
        return init, lax.with_sharding_constraint(
            jnp.broadcast_to(init[None, :], (y.shape[0], K)),
            row_sharding(mesh))
    if dist in ("poisson", "tweedie"):
        # intercept MLE with log link + offset: e^b = Σwy / Σw·e^off
        init = jnp.log(jnp.clip(
            jnp.sum(y * w) /
            jnp.clip(jnp.sum(w * jnp.exp(off)), 1e-10, None), 1e-8, None))
        return init, init + off
    if dist == "gamma":
        # gamma deviance MLE: e^b = Σ w·y·e^{-off} / Σw
        init = jnp.log(jnp.clip(
            jnp.sum(y * w * jnp.exp(-off)) / w_sum, 1e-8, None))
        return init, init + off
    init = jnp.sum((y - off) * w) / w_sum              # gaussian mean
    return init, init + off


def _margin_metrics(dist: str, margin, y, w, model=None,
                    rank: RankLayout | None = None) -> dict:
    """Training metrics from the CURRENT boosting margin (no re-predict).

    Fully device-side with w-masking (pads/holdouts carry w=0): the
    round-1 version round-tripped the 1M-row margin through the host,
    which cost multiple seconds per call. A grouped objective's
    NDCG@10 is ranked on the device too, over the job's query layout
    (`rank.ndcg_at`)."""
    from .. import metrics as M

    if grouped(dist):
        return {"train_ndcg@10": ndcg_at(margin, rank.groups, 10)}
    if dist == "bernoulli":
        p1 = _jit_sigmoid(margin)
        return {"train_logloss": M.logloss(y, p1, w=w),
                "train_auc": M.roc_auc(y, p1, w=w)}
    if dist == "multinomial":
        pr = _jit_softmax(margin)
        return {"train_logloss": M.multinomial_logloss(y, pr, w=w)}
    if dist in ("poisson", "gamma", "tweedie"):
        return {"train_rmse": M.rmse(y, _jit_exp(margin), w=w)}
    return {"train_rmse": M.rmse(y, margin, w=w)}


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stack_predict(trees: Tree, binned, max_depth: int, n_bins: int):
    """Sum of leaf values over a stacked [T, ...] Tree pytree."""

    def body(acc, tree):
        return acc + predict_tree(tree, binned, max_depth, n_bins), None

    init = jnp.zeros(binned.shape[0], dtype=jnp.float32)
    total, _ = lax.scan(body, init, trees)
    return total


def _leaf_sums(trees: Tree, binned, K: int, max_depth: int, n_bins: int):
    """`_stack_predict` of an ensemble of K class trees a round: [rows],
    or [rows, K] with the interleaved trees (class fastest) taken apart
    by class. What a boost scan's carry holds less the prior."""
    if K == 1:
        return _stack_predict(trees, binned, max_depth, n_bins)
    return jnp.stack(
        [_stack_predict(jax.tree.map(lambda a: a[k::K], trees), binned,
                        max_depth, n_bins) for k in range(K)], axis=1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _stack_leaf_nodes(trees: Tree, binned, max_depth: int, n_bins: int):
    """[T, rows] resting heap node index per tree (leaf assignment) —
    shares descend_tree with predict so split semantics can't drift."""

    def body(_, tree):
        return None, descend_tree(tree, binned, max_depth, n_bins)

    _, nodes = lax.scan(body, None, trees)
    return nodes


class TreeDraws(NamedTuple):
    """What a trained ensemble keeps to say what each tree saw. Every
    draw a boosting round makes — its row sample, its column sample,
    its nodes' `mtries` candidate features — is a pure function of the
    round's key, so the keys (a few bytes a round), the layout the
    rows were sharded in and the sampling parameters are enough:
    `tree_bag` and `tree_candidates` draw again, on demand, with the
    functions the grower itself calls (core.row_keep,
    core.level_candidates). Nothing is kept per row. All fields are
    plain numbers and lists, so the record goes through JSON as it
    is; a multinomial round's ``classes`` trees share one row sample.
    """

    keys: list              # [rounds][2] uint32 key data
    shards: int             # row shards of the training mesh
    padded: int             # padded rows over all shards
    rows: int               # the training frame's rows
    sample_rate: float
    col_rate: float
    mtries: int
    features: int
    max_depth: int
    classes: int = 1        # class trees a round (1: one tree a round)

    def _tree_keys(self, t: int):
        """(k_row, k_col, k_tree) of tree ``t``, as the boost scan's
        body splits them off the round's key."""
        if not 0 <= t < len(self.keys) * self.classes:
            raise IndexError(
                f"tree {t} of {len(self.keys) * self.classes}")
        kt = jax.random.wrap_key_data(
            jnp.asarray(self.keys[t // self.classes], dtype=jnp.uint32))
        k_row, k_col, k_tree = jax.random.split(kt, 3)
        if self.classes > 1:
            k_tree = jax.random.split(k_tree, self.classes)[
                t % self.classes]
        return k_row, k_col, k_tree

    def tree_bag(self, t: int) -> np.ndarray:
        from .tree.core import tree_bag

        k_row = self._tree_keys(t)[0]
        with phase_span("model.tree_bag", kind="wait", tree=t):
            return np.asarray(tree_bag(
                k_row, self.shards, self.padded // self.shards,
                float(self.sample_rate)))[:self.rows]

    def tree_candidates(self, t: int) -> np.ndarray:
        from .tree.core import tree_candidates

        _, k_col, k_tree = self._tree_keys(t)
        with phase_span("model.tree_candidates", kind="wait", tree=t):
            return np.asarray(tree_candidates(
                k_col, k_tree, self.features, self.max_depth,
                self.mtries, float(self.col_rate)))


class GBMModel(Model):
    algo = "gbm"
    _serving_jit = True     # predict routes through the jitted-scorer cache

    def __init__(self, data: TrainData, params: GBMParams,
                 bin_spec: BinSpec, trees, init_score, varimp):
        super().__init__(data)
        self.params = params
        self.bin_spec = bin_spec
        # stacked pytree: leaves have leading tree axis [T(*K), N];
        # accepts an already-stacked Tree (what BoostPlan.dispatch
        # hands back) or a list of single trees
        if isinstance(trees, Tree):
            self.trees = trees
            self.ntrees = int(trees.value.shape[0])
        else:
            # stack on HOST: an eager 90-operand jnp.stack on committed
            # multi-device arrays is exactly the dispatch shape that
            # trips XLA:CPU's flaky rendezvous (device_get transfers
            # never do)
            self.trees = jax.tree.map(
                lambda *xs: jnp.asarray(
                    np.stack([np.asarray(x) for x in xs])), *trees)
            self.ntrees = len(trees)
        self.init_score = init_score
        self.margin_scale = 1.0       # laplace robust scaling (train sets)
        self._varimp = varimp
        self._edges = jnp.asarray(bin_spec.edges_matrix())
        self._enum_mask = jnp.asarray(np.array(bin_spec.is_enum))

    # -- what each tree saw (TreeDraws, above) ---------------------------
    tree_draws = None

    def _draws(self) -> "TreeDraws":
        if self.tree_draws is None:
            raise ValueError(
                "this model keeps no tree keys: it was trained without "
                "row, column or per-node feature sampling, or continues "
                "a checkpoint that kept none")
        return self.tree_draws

    def tree_bag(self, t: int) -> np.ndarray:
        """bool [rows]: the training rows tree ``t`` kept (its bag; all
        of them without row sampling), in the training frame's order —
        what scoring a tree out of bag starts from."""
        return self._draws().tree_bag(t)

    def tree_candidates(self, t: int) -> np.ndarray:
        """bool [2^(max_depth+1)-1, F]: the features each heap node of
        tree ``t`` was offered when it looked for its split."""
        return self._draws().tree_candidates(t)

    @property
    def _set_splits(self) -> bool:
        """This ensemble's splits send sets of levels left
        (`Tree.left_bins`): it is scored by the heap descent over bin
        codes and by nothing that reads `split_bin` as a threshold."""
        return self.trees.left_bins is not None

    def _flat(self) -> FlatTrees:
        """The ONE flattening of this ensemble (serving scorer + MOJO
        export share it): compact reachable-node arrays with raw-
        feature thresholds, built lazily and cached on the model."""
        ft = self.__dict__.get("_flat_trees")
        if ft is None:
            ft = flatten_trees(self.trees, np.asarray(self._edges),
                               np.asarray(self._enum_mask),
                               self.params.max_depth)
            ft = FlatTrees(*(jnp.asarray(a) for a in ft))
            self._flat_trees = ft
        return ft

    def _serving_prepare(self):
        """base._cached_score calls this before tracing the jitted
        scorer: the flat ensemble, which a model with set splits does
        not have (its `_margins` descends the heap)."""
        return None if self._set_splits else self._flat()

    def _margins(self, X: jax.Array,
                 offset: jax.Array | None = None) -> jax.Array:
        """Raw boosting margins via the flattened serving scorer — no
        re-binning at score time; bitwise-equal to `_margins_binned`
        (the heap re-descent kept as the parity reference)."""
        if self._set_splits:
            return self._margins_binned(X, offset)
        K = self.nclasses if self.nclasses > 2 else 1
        p = self.params
        lv = flat_margin(self._flat(), X, self._enum_mask, p.max_depth,
                         K)                               # [K, rows]
        if K == 1:
            m = lv[0]
            if p._drf_mode:
                m = m / self.ntrees
            base = self.init_score if offset is None \
                else self.init_score + offset
            return base + getattr(self, "margin_scale", 1.0) * m
        if p._drf_mode:
            lv = lv / (self.ntrees // K)
        return (jnp.asarray(self.init_score)[:, None] + lv).T

    def _margins_binned(self, X: jax.Array,
                        offset: jax.Array | None = None) -> jax.Array:
        """Legacy per-tree heap re-descent over binned codes — the
        training-structure scorer the flat path must match bitwise
        (tests/test_flat_scorer.py, tools/kernel_gate.py)."""
        return self._margins_of_binned(
            apply_bins(X, self._edges, self._enum_mask,
                       self.bin_spec.na_bin), offset)

    def _margins_of_binned(self, binned: jax.Array,
                           offset: jax.Array | None = None) -> jax.Array:
        """`_margins_binned` from the bin codes themselves: every tree's
        heap walked over them. Its program depends on the ensemble's
        dense shape [T, N] alone, where the flat scorer's width is the
        model's own (its reachable nodes). Training needs it only where
        a job holds no sum of its own (a checkpoint's trees at a
        restart): a forest's train metric is `_margins_of_sums` of what
        its scan carried."""
        K = self.nclasses if self.nclasses > 2 else 1
        return self._margins_of_sums(
            _leaf_sums(self.trees, binned, K, self.params.max_depth,
                       self.bin_spec.n_bins), offset)

    def _margins_of_sums(self, sums: jax.Array,
                         offset: jax.Array | None = None) -> jax.Array:
        """Margins from the sum of the trees' leaf values a row ([rows],
        or [rows, K]): a forest's mean over its trees a class, a
        boosted ensemble's prior, offset and scale. THE arithmetic
        between a sum and a margin, for the walk (`_stack_predict`) and
        for the sum a forest's scan carried."""
        K = self.nclasses if self.nclasses > 2 else 1
        if self.params._drf_mode:
            sums = sums / (self.ntrees // K)
        if K == 1:
            base = self.init_score if offset is None \
                else self.init_score + offset
            return base + getattr(self, "margin_scale", 1.0) * sums
        return jnp.asarray(self.init_score)[None, :] + sums

    def _score_matrix(self, X: jax.Array,
                      offset: jax.Array | None = None) -> jax.Array:
        return self._response(self._margins(X, offset))

    def _response(self, m: jax.Array) -> jax.Array:
        """Margins to what `predict_raw` hands out: class probabilities
        or the response's own scale."""
        d = self.distribution
        if d == "bernoulli":
            p1 = jnp.clip(m, 0.0, 1.0) if self.params._drf_mode \
                else jax.nn.sigmoid(m)
            return jnp.stack([1.0 - p1, p1], axis=1)
        if d == "multinomial":
            if self.params._drf_mode:
                m = jnp.clip(m, 0.0, None)
                return m / (jnp.sum(m, axis=1, keepdims=True) + 1e-10)
            return jax.nn.softmax(m, axis=1)
        if d in ("poisson", "gamma", "tweedie"):
            return jnp.exp(m)
        return m

    def predict_leaf_node_assignment(self, frame: Frame,
                                     type: str = "Path") -> Frame:
        """Per-row resting leaf per tree (h2o predict_leaf_node_assignment
        [U3]): one column per tree (`T1..Tk`, class-suffixed for
        multinomial). `Path` (the default, matching h2o) gives the L/R
        descent string from the root; `Node_ID` gives dense-heap
        indices."""
        from ..frame.frame import Vec

        if type not in ("Node_ID", "Path"):
            raise ValueError("type must be 'Node_ID' or 'Path'")
        X = self._design_matrix(frame)
        binned = apply_bins_jit(X, self._edges, self._enum_mask,
                                self.bin_spec.na_bin)
        p = self.params
        nodes = np.asarray(_stack_leaf_nodes(
            self.trees, binned, p.max_depth,
            self.bin_spec.n_bins))[:, : frame.nrows]
        K = self.nclasses if self.nclasses > 2 else 1
        out = Frame()
        for t in range(nodes.shape[0]):
            name = f"T{t // K + 1}" if K == 1 else \
                f"T{t // K + 1}.C{t % K + 1}"
            if type == "Node_ID":
                out[name] = Vec.from_numpy(
                    nodes[t].astype(np.float32), name)
                continue
            # heap index -> root path string (L/R per level, h2o style);
            # only the ~2^depth unique leaves touch Python — per-row
            # work stays vectorized via the unique-inverse remap
            uniq, inv = np.unique(nodes[t], return_inverse=True)
            paths = [_heap_path(int(i)) for i in uniq]
            dom = sorted(set(paths))
            pos = {s: j for j, s in enumerate(dom)}
            remap = np.array([pos[s] for s in paths], dtype=np.int32)
            out[name] = Vec.from_numpy(remap[inv], name, domain=dom)
        return out

    def contrib_support(self) -> str | None:
        """TreeSHAP preconditions — THE one gate shared by the host
        ``predict_contributions``, the serving entry ``contrib_numpy``,
        and the REST contributions route (which turns a non-None
        reason into a clean 400, never a 500 traceback)."""
        if self.nclasses > 2:
            return ("predict_contributions supports binomial "
                    "and regression models only")
        if self._set_splits:
            return set_split_reason(self.trees,
                                    "TreeSHAP (predict_contributions)")
        if getattr(self, "offset_column", None):
            # a per-row offset is not attributable to any feature, so
            # SHAP columns could not sum to the margin
            return ("predict_contributions is not supported "
                    "for models trained with an offset")
        cov = getattr(self.trees, "cover", None)
        if cov is None or np.isnan(np.asarray(cov)).any():
            # .any(), not .all(): checkpoint continuation from a
            # pre-cover model mixes NaN-backfilled trees with real ones
            return (
                "this model contains trees saved by a build without "
                "per-node cover (pre-0.2); TreeSHAP needs it — retrain "
                "with this build")
        return None

    def _contrib_scale_init(self) -> tuple[float, float]:
        """(scale, init) applied to the raw kernel/recursion output —
        one formula for the host and device paths."""
        scale = float(getattr(self, "margin_scale", 1.0))
        if self.params._drf_mode:
            scale /= self.ntrees
        init = self.init_score if np.ndim(self.init_score) == 0 \
            else float(np.asarray(self.init_score).ravel()[0])
        return scale, float(init)

    def _shap_sources(self):
        """(flat arrays, slot-aligned cover) for the TreeSHAP path
        tables — the SAME flattening the serving scorer descends."""
        flat = self._flat()
        return (FlatTrees(*(np.asarray(a) for a in flat)),
                flatten_cover(self.trees, self.params.max_depth))

    def _contrib_enum_mask(self):
        return self._enum_mask

    def predict_contributions(self, frame: Frame) -> Frame:
        """Per-row TreeSHAP feature contributions (h2o
        predict_contributions, h2o-genmodel TreeSHAP [U3]): one column
        per feature plus BiasTerm, additive to the raw margin
        prediction. Binomial and regression only, like the reference.

        This is the in-process HOST path (float64 recursion over the
        heap trees) — the parity reference; serving traffic rides the
        compiled device kernel via ``contrib_numpy`` / the REST
        contributions route (docs/SERVING.md "Explainable serving")."""
        reason = self.contrib_support()
        if reason:
            raise ValueError(reason)
        from .tree.shap import ensemble_shap

        X = self._design_matrix(frame)
        binned = np.asarray(apply_bins_jit(
            X, self._edges, self._enum_mask,
            self.bin_spec.na_bin))[: frame.nrows]
        trees_np = {f: np.asarray(getattr(self.trees, f))
                    for f in ("split_feat", "split_bin", "na_left",
                              "is_split", "value", "cover")}
        scale, init = self._contrib_scale_init()
        phi = ensemble_shap(trees_np, binned,
                            len(self.feature_names),
                            self.bin_spec.na_bin, scale=scale)
        phi[:, -1] += init
        cols = {name: phi[:, i].astype(np.float32)
                for i, name in enumerate(self.feature_names)}
        cols["BiasTerm"] = phi[:, -1].astype(np.float32)
        return Frame.from_arrays(cols)

    def varimp(self) -> dict[str, float]:
        """Relative importance: per-feature summed split gain, scaled."""
        v = self._varimp
        top = max(v.values()) if v else 1.0
        return {k: val / top if top > 0 else 0.0
                for k, val in sorted(v.items(), key=lambda kv: -kv[1])}


class GBM:
    """H2OGradientBoostingEstimator analog."""

    model_cls = GBMModel

    def __init__(self, **kw):
        from .cv import CVArgs

        self.cv_args = CVArgs.pop(kw)
        if "nbins" not in kw:               # env/config default tier
            from ..config import get_config

            kw["nbins"] = get_config("nbins")
        self.params = GBMParams(**kw)

    def train(self, y: str, training_frame: Frame,
              x: Sequence[str] | None = None,
              ignored_columns: Sequence[str] | None = None,
              weights_column: str | None = None,
              validation_frame: Frame | None = None,
              offset_column: str | None = None,
              group_column: str | None = None) -> GBMModel:
        # one `train` root span a job (runtime/telemetry.phase_span:
        # histogram, /3/Timeline, GET /3/Trace/{id}, the profiler's
        # trace); `_train` opens one child per phase.
        # ``group_column``: the query of every row, for a grouped
        # objective (rank:*, through the XGBoost estimator); never a
        # feature
        p = self.params
        if group_column:
            ignored_columns = list(ignored_columns or []) + [group_column]
        with phase_span("train", estimator=type(self).__name__,
                        ntrees=p.ntrees, max_depth=p.max_depth) as root:
            return self._train(root, y, training_frame, x,
                               ignored_columns, weights_column,
                               validation_frame, offset_column,
                               group_column)

    def _train(self, root, y, training_frame, x, ignored_columns,
               weights_column, validation_frame, offset_column,
               group_column=None):
        p = self.params
        with phase_span("train.prepare"):
            if p.ntrees < 1:
                raise ValueError(f"ntrees must be >= 1, got {p.ntrees}")
            # the binning paths ask the same function; asking up front
            # keeps the error first whichever of them runs
            bin_code_dtype(p.nbins)
            if offset_column and p._drf_mode:
                # the reference rejects offsets for DRF too (trees vote —
                # there is no additive margin for an offset to join)
                raise ValueError("offset_column is not supported for DRF")
            if self.cv_args.fold_column:
                ignored_columns = list(ignored_columns or []) + \
                    [self.cv_args.fold_column]
            # materialize_x=False: the tree learners never touch a full
            # [n, F] float32 design matrix — binning happens column-block-
            # wise straight from the Frame columns (Frame.binned), and
            # gradients come from the y/weights/offset columns alone. The
            # uint8 binned matrix is the only full-width training-resident
            # array (docs/SCALING.md).
            data = resolve_xy(training_frame, y, x, ignored_columns,
                              weights_column, p.distribution, offset_column,
                              materialize_x=False)
            if offset_column and data.distribution in ("multinomial",
                                                       "laplace"):
                raise ValueError("offset_column is not supported for "
                                 f"{data.distribution} GBM")
            if data.distribution in ("gamma", "tweedie", "poisson"):
                ymin = float(_jit_min_pos(data.y, data.w))
                if data.distribution == "gamma" and ymin <= 0:
                    raise ValueError(
                        "gamma distribution needs a strictly positive "
                        "response")
                if ymin < 0:
                    raise ValueError(f"{data.distribution} distribution "
                                     "needs a non-negative response")
            ckpt = p.checkpoint
            bin_spec = None                  # fit below, fused when it fits
            if ckpt is not None:
                _check_checkpoint(ckpt, p, data, offset_column,
                                  self.cv_args.enabled)
                bin_spec = ckpt.bin_spec     # same binning → trees compose
            key = jax.random.key(p.seed)

            # Exclusive Feature Bundling (models/tree/efb.py,
            # docs/SCALING.md "Wide sparse frames"): on wide frames
            # dominated by one-hot / near-empty columns, mutually
            # exclusive sparse features pack into single bundle columns at
            # bin time, so the binned matrix, every per-level scatter-add,
            # and the cross-shard histogram psum all run at the bundled
            # width.  Splits decode back to ORIGINAL (feature, bin) before
            # tree emission — bin_spec/trees/artifacts/serving are
            # bundle-free.  H2O_TPU_EFB=0 kills it; plan-less frames fall
            # through to the fused prologue unchanged.
            from .tree import efb as efb_mod

            encoding, set_feats, nbins_cats, n_bins = _bin_layout(
                p, training_frame, data.feature_names)
            if ckpt is not None and ckpt.bin_spec.set_feats:
                refuse_set_splits(checkpoint=True)

            # a grouped objective's query layout, built once a job
            rank = None
            if grouped(data.distribution):
                if not group_column:
                    raise ValueError(
                        f"{data.distribution} needs group_column: the "
                        "query of every row")
                with phase_span("train.group_layout"):
                    rank = _frame_rank_layout(training_frame, y,
                                              group_column)

            efb_plan = efb = None
            F = len(data.feature_names)
            # (a grouped job skips the planning pass: `BoostPlan.grouped`)
            if rank is None and efb_mod.efb_eligible(F, ckpt):
                # reuse the fitted spec either way: when the plan is
                # rejected (shrink gate / no exclusive sets) re-fitting
                # through the fused prologue would just duplicate the
                # quantile fit this pass already paid
                bin_spec, efb_plan = efb_mod.fit_plan_cached(
                    training_frame, data.feature_names, p.nbins)
                if efb_plan is not None:
                    efb = efb_plan.device_luts()
                    # histograms are accounted at the width they have:
                    # the memory win is exactly what buys deeper trees
                    # on wide sparse frames
                    F = efb_plan.fb
                elif set_feats:
                    bin_spec = None     # that fit was label_encoder's

            plan = boost_plan(p, data.distribution, data.nclasses, F,
                              n_bins=n_bins, set_feats=set_feats,
                              rank=rank)
            plan.validate(self.model_cls.algo, ckpt, efb_plan is not None,
                          data.y.shape[0], offset=bool(offset_column),
                          cv=self.cv_args.enabled)
            # the per-round GOSS key stream is derived OUTSIDE the
            # dispatch-chunk key schedule (goss_round_keys) so the fused
            # in-HBM path and the ooc stream draw identical keep patterns
            # at one seed
            goss_keys = goss_round_keys(key, p.ntrees) \
                if plan.bp.goss_b > 0 else None
            # out-of-core: the binned matrix stays host-resident in
            # chunks, streamed per boosting iteration (models/tree/ooc.py);
            # `binned` is on the device for the in-HBM path only
            ooc_chunk = plan.ooc_chunk(data.y.shape[0], ckpt)
            binned = None
        root.update(rows=training_frame.nrows, features=plan.F,
                    chips=plan.mesh.size, encoding=encoding,
                    bins=n_bins, enum_features=sum(
                        training_frame.vec(n).is_enum()
                        for n in data.feature_names),
                    objective=data.distribution)
        if p._drf_mode:
            root.update(mtries=p.mtries, set_features=sum(set_feats))
        if rank is not None:
            root.update(queries=rank.queries, max_query=rank.max_query)
        # no span blocks on the device for its own sake (the dispatch
        # pipeline below is the loop's design): `enqueue` spans read the
        # dispatch, the device's side is in the device trace under
        # telemetry.TRAIN_PROGRAMS' names
        with phase_span("train.bin", kind="enqueue",
                        rows=data.y.shape[0], features=plan.F):
            if efb_plan is not None:
                # bundled training matrix [padded, Fb] (host-built
                # during planning, device-cached on the plan); the
                # out-of-core branch slices the same host matrix into
                # its chunk grid
                if ooc_chunk is None:
                    binned = efb_plan.binned_device()
            elif bin_spec is None:
                # fresh fit: in HBM the quantile fit and the bin apply
                # fuse into ONE dispatch with no host sync in between
                # (binning.fused_fit_bins); the stream keeps the
                # two-dispatch fit (its apply streams host chunks)
                if ooc_chunk is None:
                    bin_spec, binned = fused_fit_bins(
                        training_frame, data.feature_names,
                        n_bins=p.nbins, nbins_cats=nbins_cats)
                else:
                    bin_spec = fit_bins(training_frame,
                                        data.feature_names,
                                        n_bins=p.nbins,
                                        nbins_cats=nbins_cats)
            if ooc_chunk is None and binned is None:
                binned = training_frame.binned(bin_spec)

        with phase_span("train.init_margin", kind="enqueue"):
            init, margin, margin_scale, data = _initial_margin(
                plan, data, ckpt, binned)

        start_t = 0 if ckpt is None else len(ckpt.trees.value) // plan.K
        if plan.K > 1:
            root.update(classes=plan.K, rounds=p.ntrees - start_t,
                        class_batch=plan.class_batch)
        history: list[dict] = []
        key_chunks: list = []
        # fused loop: all boosting rounds of a chunk build inside ONE
        # compiled shard_map (scan over rounds; for K>2 classes the K
        # trees of a round grow via vmap inside the scan) — the margin
        # never leaves the device and the host dispatches once per chunk
        # instead of >=3 times per tree
        if ooc_chunk is not None:
            # chunk-streamed boosting: host-pinned binned chunks,
            # double-buffered device_put per level, chunk-accumulated
            # histograms (models/tree/ooc.py). Metrics land once at
            # the end — models with a score_every cadence never reach
            # this branch (plan.ooc_chunk gates them in-HBM).
            from ..runtime.mrtask import shard_rows
            from .tree.ooc import boost_trees_chunked, make_chunks

            require_healthy()
            with device_dispatch("gbm out-of-core boost"), \
                    phase_span("train.boost", kind="enqueue", mode="ooc",
                               trees=p.ntrees):
                cks = make_chunks(training_frame, bin_spec, data.y,
                                  data.w, margin, ooc_chunk,
                                  plan=efb_plan)
                margin_np, trees, goss_dropped = boost_trees_chunked(
                    cks, key, p.ntrees, plan.tp, plan.bp, efb=efb,
                    goss_keys=goss_keys)
            _warn_goss_overflow(goss_dropped)
            margin = shard_rows(margin_np)
        else:
            with phase_span("train.boost", kind="enqueue", mode="in_hbm",
                            trees=p.ntrees):
                trees, margin, history, key_chunks = self._boost_in_hbm(
                    plan, data, binned, margin, key, ckpt, start_t,
                    history, efb=efb, goss_keys=goss_keys)
        with phase_span("train.read_model", kind="wait"):
            if isinstance(init, jax.Array):
                # read the device init back AFTER the boost chunks are
                # enqueued (async dispatch: this blocks only on the tiny
                # init computation, not on training)
                init = jax.device_get(init)
                init = init if init.ndim else float(init)
                if not np.all(np.isfinite(np.atleast_1d(init))):
                    # 0/0 on device (every row weight zero / every response
                    # NA) must surface as an error, not a silently-NaN model
                    raise ValueError(
                        "no rows with positive weight and a non-NA response "
                        "— cannot fit a prior")
            model = self.model_cls(data, p, bin_spec, trees,
                                   init_score=init, varimp=None)
            model.margin_scale = margin_scale
            model.offset_column = offset_column
            if key_chunks:
                model.tree_draws = TreeDraws(
                    keys=np.concatenate(
                        [np.asarray(k) for k in key_chunks]).tolist(),
                    shards=plan.mesh.shape[ROWS],
                    padded=int(data.y.shape[0]), rows=int(data.nrows),
                    sample_rate=p.sample_rate,
                    col_rate=p.col_sample_rate_per_tree, mtries=p.mtries,
                    features=len(data.feature_names),
                    max_depth=p.max_depth, classes=plan.K)
            model._varimp = _stacked_varimp(model.trees, data.feature_names)
            _count_splits(model.trees, set_feats)
            if group_column:
                model._group_column = group_column
            if rank is not None:
                _count_pairs(rank, p.ntrees - start_t)
            if plan.K > 1:
                _count_class_trees(plan, p.ntrees - start_t)
            if ooc_chunk is None:
                _count_node_lookups(plan, p.ntrees - start_t)
                _count_hist_levels(plan, p.ntrees - start_t)
        # which way the metric is read: off the boosting margin, off
        # the sum of leaf values a forest's scan carried (every tree
        # over every row, bitwise what `_margins_of_binned` walks
        # to), or by scoring the frame where a forest holds no such
        # sum (none today: `ooc_chunk` keeps every forest in HBM)
        source = "margin" if not p._drf_mode \
            else "walk" if binned is None else "carried"
        with phase_span("train.metric", kind="wait", source=source):
            if p._drf_mode:
                if source == "walk":
                    perf = model.model_performance(training_frame, y)
                else:
                    perf = model.performance_of(
                        training_frame, y, np.asarray(model._response(
                            model._margins_of_sums(margin))))
                history.append({"ntrees": p.ntrees,
                                **{f"train_{k}": v for k, v in perf.items()}})
            elif not (history and history[-1].get("ntrees") == p.ntrees):
                # (when score_every divides ntrees the loop already scored
                # the final round — don't duplicate the row)
                history.append({"ntrees": p.ntrees, **_margin_metrics(
                    data.distribution, margin, data.y, data.w,
                    rank=rank)})
            if margin_scale != 1.0 and history:
                # report rmse in ORIGINAL units, not MAD units
                for hrow in history:
                    if "train_rmse" in hrow:
                        hrow["train_rmse"] *= margin_scale
            model.scoring_history = history
        from .cv import finalize_train

        with phase_span("train.finalize"):
            return finalize_train(
                self, model, y, training_frame,
                {"x": x, "ignored_columns": ignored_columns,
                 "weights_column": weights_column,
                 "offset_column": offset_column},
                validation_frame)

    def _boost_in_hbm(self, plan: BoostPlan, data, binned, margin, key,
                      ckpt, start_t, history, efb=None, goss_keys=None):
        """The fused in-HBM boosting loop (all rows device-resident):
        the dispatches `plan.chunks` names, each through
        `plan.dispatch`. ``goss_keys`` ([ntrees] rows, indexed by
        GLOBAL tree number) is sliced per dispatch so the per-round
        GOSS draw never depends on the _DISPATCH_BUDGET schedule."""
        p = plan.p
        chunks: list[Tree] = [] if ckpt is None else [ckpt.trees]
        goss_overflow: list = []      # per-dispatch device scalars
        # the keys of the rounds grown, kept while a round draws
        # anything from its key (the model hands out each tree's bag
        # and candidates from them): a checkpoint's first, where it
        # kept them (one that did not leaves the new model without)
        sampled = _draws_from_keys(p, len(data.feature_names))
        key_chunks: list = []
        if sampled and ckpt is not None:
            prior = getattr(ckpt, "tree_draws", None)
            sampled = prior is not None
            if sampled:
                key_chunks.append(np.asarray(prior.keys, np.uint32))
        score = plan.score_every
        t = start_t
        for n in plan.chunks(data.y.shape[0], start_t):
            require_healthy()        # fail fast on a dead mesh (§5.3)
            key, kc = jax.random.split(key)
            # the boost dispatch runs under the device guard: a chip
            # halting AT dispatch marks the cluster unhealthy and
            # raises ClusterHealthError (locked-cloud protocol) — this
            # loop dispatches shard_map directly, bypassing doall's
            # guard. Deliberately NOT block_until_ready: chunk
            # pipelining is the loop's perf design, so a mid-EXECUTION
            # device error instead surfaces at the metrics/model read
            # and is escalated to the same locked-cloud failure by
            # AutoML's step_failed device-error check
            gk = None if goss_keys is None else goss_keys[t: t + n]
            with device_dispatch("gbm boost dispatch"), \
                    phase_span("train.dispatch", kind="enqueue",
                               first_tree=t, trees=n):
                margin, tchunk, kchunk, overflow = plan.dispatch(
                    binned, data.y, data.w, margin, kc, n, efb, gk)
            chunks.append(tchunk)
            if overflow is not None:
                goss_overflow.append(overflow)
            if sampled:
                key_chunks.append(jax.random.key_data(kchunk))
            t += n
            if score and (t - start_t) % score == 0:
                history.append({"ntrees": t, **_margin_metrics(
                    data.distribution, margin, data.y, data.w,
                    rank=plan.rank)})
        trees = jax.tree.map(
            lambda *xs: jnp.concatenate(xs), *chunks) \
            if len(chunks) > 1 else chunks[0]
        if goss_overflow:
            _warn_goss_overflow(
                int(sum(int(jax.device_get(o)) for o in goss_overflow)))
        return trees, margin, history, key_chunks

    # -- compile-ahead (runtime/scheduler.py) ---------------------------

    def compile_ahead_lowerings(self, y: str, frame: Frame,
                                x: Sequence[str] | None = None,
                                group_column: str | None = None) -> list:
        """Zero-arg thunks that AOT-lower+compile the programs
        ``train(y, frame, x)`` will dispatch (`BoostPlan.lowerings`) —
        run on the compile-ahead stream while the device token is busy
        with an earlier model, so the device stream's later dispatch
        is a compile-cache hit (in-process executable cache + the
        persistent XLA cache: a fill on a cold run, a no-op warm).

        The plan is built from column METADATA only (padded_len,
        kinds, cardinality — no device dispatch, the compile stream
        never touches the device token). Coverage is the in-HBM
        pointwise tree path: the final fit's full-frame shape plus,
        under modulo CV (AutoML's fold assignment), the fold shapes —
        identical to the full shape in weights-masked share mode, the
        complement sizes in sliced mode. A grouped objective's job is
        lowered with the layout of ``group_column`` (its shapes hang
        on the multiset of query sizes; without the column: no
        thunks). What it alone rules out (checkpoint continuation, a
        fold column, a missing response, a width EFB may rebundle),
        what the plan refuses and what streams out of core return no
        thunks, and train compiles on demand. tests/test_scheduler.py holds
        what is lowered to what is dispatched, mode by mode."""
        from ..runtime.mrtask import _padded_len
        from .tree import efb as efb_mod

        p = self.params
        if p.checkpoint is not None or self.cv_args.fold_column or \
                y not in frame:
            return []
        try:
            names = _feature_names(
                frame, x, {y, group_column} if group_column else {y})
            dist, nclasses, _ = resolve_response(frame, y, p.distribution)
            # EFB may rebundle the frame to a DATA-dependent width:
            # F-width executables would be dead compile work
            if not names or (grouped(dist) and not group_column) or \
                    (not grouped(dist)
                     and efb_mod.efb_eligible(len(names), None)):
                return []
            _, set_feats, _, n_bins = _bin_layout(p, frame, names)
            plan = boost_plan(
                p, dist, nclasses, len(names), n_bins=n_bins,
                set_feats=set_feats,
                rank=_frame_rank_layout(frame, y, group_column)
                if grouped(dist) else None)
            plan.validate(self.model_cls.algo, cv=self.cv_args.enabled)
        except ValueError:
            return []       # train() raises it, on the driver thread
        n = frame.nrows
        # the shapes train() will see: the final fit's padded length,
        # plus the modulo-CV fold lengths — full-frame in share mode
        # (models/cv.py weights-masked folds), complement sizes sliced
        padded_sizes = {frame.vec(names[0]).padded_len}
        cv = self.cv_args
        if cv.enabled and cv.nfolds >= 2 and \
                cv.fold_assignment.lower() == "modulo":
            env = os.environ.get("H2O_TPU_CV_SHAPE_SHARE_ROWS")
            if env is not None:
                share = n <= int(env)
            else:
                share = jax.default_backend() == "tpu" and n <= 1_000_000
            if "_cv_mask_w_" in frame.names:
                share = False
            if not share:
                shards = plan.mesh.shape[ROWS]
                for k in range(cv.nfolds):
                    hold = n // cv.nfolds + (1 if k < n % cv.nfolds
                                             else 0)
                    padded_sizes.add(_padded_len(n - hold, shards))
        return [functools.partial(_aot, fn, *args)
                for padded in sorted(padded_sizes)
                if plan.ooc_chunk(padded, None) is None
                for fn, args in plan.lowerings(padded)]


def _bin_layout(p: GBMParams, frame, names: list[str]) -> tuple:
    """(encoding, set_feats, nbins_cats, n_bins) of a job: what
    `categorical_encoding` resolves to, the features that take set
    splits, the `nbins_cats` the binning is told (None: the matrix is
    label_encoder's) and the binned matrix's bins a feature — static
    facts, from column metadata. Without a set feature
    (``label_encoder``, or no enum column within `nbins_cats`) the job
    is label_encoder's, to the program's last byte."""
    encoding = resolve_encoding(p.categorical_encoding)
    set_feats = set_features(frame, names, p.nbins_cats) \
        if encoding == "enum" else ()
    nbins_cats = p.nbins_cats if set_feats else None
    n_bins = matrix_bins(frame, names, p.nbins, nbins_cats)
    bin_code_dtype(n_bins, p.nbins)
    return encoding, set_feats, nbins_cats, n_bins


def _check_checkpoint(ckpt, p: GBMParams, data: TrainData, offset_column,
                      cv_enabled: bool) -> None:
    """Refuse a checkpoint whose trees the new rounds would not compose
    with (reference SharedTree checkpoint semantics, SURVEY.md §5.4)."""
    if cv_enabled:
        # H2O forbids checkpoint+CV: fold models would inherit
        # trees that already saw their holdout rows
        raise ValueError(
            "checkpoint cannot be combined with cross-validation")
    if ckpt.feature_names != data.feature_names:
        raise ValueError(
            "checkpoint model was trained on different features "
            f"({ckpt.feature_names} vs {data.feature_names})")
    if ckpt.distribution != data.distribution:
        raise ValueError("checkpoint distribution mismatch")
    if ckpt.nclasses != data.nclasses or \
            (ckpt.response_domain or []) != (data.response_domain or []):
        raise ValueError(
            "checkpoint response mismatch: "
            f"{ckpt.nclasses} classes {ckpt.response_domain} vs "
            f"{data.nclasses} classes {data.response_domain}")
    K0 = ckpt.nclasses if ckpt.nclasses > 2 else 1
    if p.ntrees * K0 <= len(ckpt.trees.value):
        raise ValueError(
            f"ntrees={p.ntrees} must exceed the checkpoint's "
            f"{len(ckpt.trees.value) // K0} trees")
    if ckpt.params.nbins != p.nbins or \
            ckpt.params.max_depth != p.max_depth:
        raise ValueError(
            "checkpoint nbins/max_depth must match "
            f"({ckpt.params.nbins}/{ckpt.params.max_depth} vs "
            f"{p.nbins}/{p.max_depth})")
    if (getattr(ckpt, "offset_column", None) or None) != \
            (offset_column or None):
        raise ValueError(
            "checkpoint offset_column mismatch: "
            f"{getattr(ckpt, 'offset_column', None)!r} vs "
            f"{offset_column!r}")


def _initial_margin(plan: BoostPlan, data: TrainData, ckpt, binned):
    """(init score, starting margin, margin_scale, data) of a job: a
    checkpoint's trees scored over the binned matrix, a fresh forest's
    zeros, laplace's robust scaling (which rescales ``data.y``), or the
    prior on the device."""
    p, K = plan.p, plan.K
    margin_scale = 1.0
    off = data.offset if data.offset is not None \
        else jnp.zeros_like(data.y)
    laplace = data.distribution == "laplace"
    if ckpt is not None:
        init = ckpt.init_score
        # the scan's carry goes on from the checkpoint's trees, walked
        # once: a forest's sum of leaf values, a boosted margin
        margin = _leaf_sums(ckpt.trees, binned, K, p.max_depth,
                            plan.tp.n_bins)
        if not p._drf_mode:
            margin = init + off + margin if K == 1 \
                else jnp.asarray(init)[None, :] + margin
        if laplace:
            # continuation must reuse the checkpoint's robust scaling or
            # the new trees' leaf units would not compose; the working
            # margin lives in SCALED units (tree leaves), so the init
            # added above is dropped below
            margin_scale = getattr(ckpt, "margin_scale", 1.0)
    elif plan.device_init:
        # bernoulli/multinomial/poisson/gamma/tweedie/gaussian:
        # init + margin in one device dispatch, no host sync before
        # the first boost chunk (init is read back at model build)
        init, margin = _init_margin(data.y, data.w, off,
                                    data.distribution, K, plan.mesh)
    elif p._drf_mode or plan.grouped:
        # DRF: no boosting — leaves are in-leaf target means, init 0;
        # a ranker's scores start from 0 (only their differences count)
        init = np.zeros(K, dtype=np.float32) if K > 1 else 0.0
        margin = jnp.zeros((data.y.shape[0], K),
                           device=row_sharding(plan.mesh)) if K > 1 \
            else jnp.zeros_like(data.y)
    else:
        # laplace: L1 leaf steps are bounded by learn_rate, so fit in
        # median/MAD-scaled space: |y-f| is scale-equivariant and
        # the minimizer is unchanged; predictions rescale on read
        yv = np.asarray(data.y)[np.asarray(data.w) > 0]
        init = float(np.median(yv)) if len(yv) else 0.0
        mad = float(np.median(np.abs(yv - init))) if len(yv) else 1.0
        # MAD degenerates to 0 on zero-inflated data (>=50% of y at
        # one value) — only then fall back to the non-robust std,
        # otherwise keep the outlier-insensitive scale
        if mad * 1.4826 > 1e-8:
            margin_scale = mad * 1.4826
        else:
            std = float(np.std(yv)) if len(yv) else 1.0
            margin_scale = max(std, 1e-8)
    if laplace:
        data = replace(data, y=(data.y - init) / margin_scale)
        margin = jnp.zeros_like(data.y) if ckpt is None else margin - init
    return init, margin, margin_scale, data


def _warn_goss_overflow(dropped: int) -> None:
    """Loud (never silent) notice that GOSS compaction truncated: the
    static per-shard capacity is sized for the EXPECTED a+b selected
    fraction, but a frame whose row ORDER correlates with |gradient|
    (sorted by target or residual) can cluster far more selected rows
    into one shard — and the truncated rows are exactly the
    high-gradient ones GOSS exists to keep (it also breaks the
    in-HBM↔ooc same-seed equivalence, since the two layouts truncate
    different segments). The model still trains; the operator should
    shuffle the rows or raise a+b."""
    if dropped <= 0:
        return
    from ..diagnostics import log

    log.warning(
        "GOSS compaction overflow: %d selected row contributions were "
        "dropped because one or more shards selected more rows than "
        "the static capacity (sized for the expected a+b fraction). "
        "The row order likely correlates with |gradient| — shuffle "
        "the training frame, or raise H2O_TPU_GOSS_TOP_A/"
        "H2O_TPU_GOSS_RAND_B so the capacity covers the clustering.",
        dropped)


def _aot(jitted, *args) -> None:
    """Lower + compile one jitted program ahead of use (compile-ahead
    stream). The executable lands in jax's compilation caches (and the
    persistent XLA cache), so the training-time dispatch of the same
    (program, shapes, statics) is a cache hit instead of a compile."""
    jitted.lower(*args).compile()


def _heap_path(i: int) -> str:
    """Dense-heap index -> 'LRL...' root descent (root itself = '')."""
    bits = []
    while i > 0:
        bits.append("L" if i % 2 == 1 else "R")   # odd = left child
        i = (i - 1) // 2
    return "".join(reversed(bits))


def _gain_by_feat(tree: Tree, F: int) -> np.ndarray:
    feat = np.asarray(tree.split_feat)
    gain = np.asarray(tree.gain)
    out = np.zeros(F, dtype=np.float64)
    sel = feat >= 0
    np.add.at(out, feat[sel], gain[sel])
    return out


def _stacked_varimp(trees: Tree, names: list[str]) -> dict[str, float]:
    """Varimp from a stacked [T, N] Tree pytree in ONE host transfer —
    a per-tree np.asarray would force a device sync every boosting
    iteration. The ravel happens host-side (np) — an eager jnp op
    on the committed tree arrays is a multi-device dispatch."""
    flat = trees._replace(split_feat=np.asarray(trees.split_feat).ravel(),
                          gain=np.asarray(trees.gain).ravel())
    return dict(zip(names, _gain_by_feat(flat, len(names))))


def _frame_rank_layout(frame: Frame, y: str, group_column: str
                       ) -> RankLayout:
    """The query layout of ``frame`` (`rank.rank_layout`), from its
    group column and its labels as the job reads them (an enum's codes
    are its grades; a missing label is NaN)."""
    yv = frame.vec(y)
    return rank_layout(
        frame.vec(group_column).to_numpy(),
        np.asarray(yv.as_float())[: frame.nrows], yv.padded_len)


def _count_pairs(rank: RankLayout, rounds: int) -> None:
    """`h2o_train_rank_pairs_total{kind}`, added up once a job: over
    its rounds, the pairs that exist (sum of n_q^2 over the queries:
    ``real``) and the pair slots the layout's size classes computed
    for them (``slots``)."""
    from ..runtime.telemetry import REGISTRY

    ctr = REGISTRY.counter(
        "h2o_train_rank_pairs_total",
        "document pairs of the ranking jobs trained, over their "
        "rounds: real (sum of n_q^2 over the queries) and slots (pair "
        "slots the query layout computed)", label="kind")
    ctr.inc(rank.pairs_real * rounds, label_value="real")
    ctr.inc(rank.pairs_slots * rounds, label_value="slots")


def _count_class_trees(plan: BoostPlan, rounds: int) -> None:
    """`h2o_train_class_trees_total{kind}`, added up once a job: the
    class trees of its rounds (rounds x K), by how a round's K were
    grown — ``batched`` (one vmapped grow: a level's histograms in one
    kernel call, the bin codes read once for the K trees) or ``mapped``
    (a class at a time under `lax.map`)."""
    from ..runtime.telemetry import REGISTRY

    REGISTRY.counter(
        "h2o_train_class_trees_total",
        "class trees of the K-class jobs trained (rounds x K), by how "
        "a round's K trees were grown: batched (one vmapped grow) or "
        "mapped (a class at a time)", label="kind").inc(
            rounds * plan.K, label_value="batched"
            if plan.class_batch == "vmap" else "mapped")


def _count_node_lookups(plan: BoostPlan, rounds: int) -> None:
    """`h2o_train_node_lookups_total{form}`, added up once a job held in
    HBM: the by-row lookups of a node table that its trees (rounds x K)
    traced — each level's descent and each tree's margin update — by
    the form `core.node_lookup_forms` gives them as the program is
    traced: ``select`` or ``gather``."""
    from ..runtime.telemetry import REGISTRY

    ctr = REGISTRY.counter(
        "h2o_train_node_lookups_total",
        "by-row lookups of a node table in the boost scans of the jobs "
        "trained (a level's descent, a tree's margin update), by form: "
        "select (over the table's entries) or gather", label="form")
    forms = node_lookup_forms(plan.tp.max_depth)
    for form in ("select", "gather"):
        ctr.inc(rounds * plan.K * forms.count(form), label_value=form)


def _count_hist_levels(plan: BoostPlan, rounds: int) -> None:
    """`h2o_train_hist_levels_total{form}`, added up once a job held in
    HBM: the histogram calls of its trees (rounds x K) that serve a
    level in several hi blocks, by the form `core.hist_level_forms`
    gives them as the program is traced: ``compacted`` (over rows
    ordered by node block) or ``blocked`` (every row tile against
    every block)."""
    from ..runtime.telemetry import REGISTRY

    ctr = REGISTRY.counter(
        "h2o_train_hist_levels_total",
        "histogram levels past one hi block in the boost scans of the "
        "jobs trained, by form: compacted (rows ordered by node block) "
        "or blocked (every row tile against every block)", label="form")
    forms = hist_level_forms(plan.tp, plan.F,
                             batched=plan.class_batch == "vmap")
    for form in ("compacted", "blocked"):
        ctr.inc(rounds * plan.K * forms.count(form), label_value=form)


def _count_splits(trees: Tree, set_feats: tuple) -> None:
    """`h2o_train_splits_total{kind}`: the splits of a model just read
    back, by whether they send a set of levels left or cut a numeric
    (or label-encoded) feature at a threshold."""
    from ..runtime.telemetry import REGISTRY

    feat = np.asarray(trees.split_feat).ravel()
    feat = feat[feat >= 0]
    n_set = int(np.asarray(set_feats, dtype=bool)[feat].sum()) \
        if set_feats else 0
    ctr = REGISTRY.counter(
        "h2o_train_splits_total",
        "splits of the tree models trained, by kind: set (a set of an "
        "enum's levels goes left) or numeric (a threshold)",
        label="kind")
    ctr.inc(n_set, label_value="set")
    ctr.inc(len(feat) - n_set, label_value="numeric")
