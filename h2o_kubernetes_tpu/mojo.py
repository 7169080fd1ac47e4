"""MOJO-analog: standalone scoring artifacts, pure numpy at score time.

Reference: h2o-genmodel + ModelMojoWriter (SURVEY.md §2b C18) — a model
exports to a self-contained artifact scoreable WITHOUT a running
cluster. Here the artifact is a zip of npz arrays + JSON metadata, and
`MojoModel` scores it with numpy only (no jax import needed), so the
artifact runs on any serving host.

Supported: GBM / DRF / XGBoost (trees + bin edges), GLM (beta + design
layout, all families/links incl. multinomial), KMeans (centers),
DeepLearning (layer weights; MLP, softmax and autoencoder modes),
NaiveBayes (priors + likelihood tables), PCA (eigenvectors),
Word2Vec (embeddings + vocab with word_vector/find_synonyms accessors),
IsolationForest, CoxPH (linear log-hazard), GLRM (archetypes; predict
gives the per-row factor projection, reconstruct() the imputed frame),
TargetEncoder (transform() applies the fitted level→encoding tables),
and StackedEnsemble (every base-model MOJO plus the metalearner MOJO
nested in one artifact — the AutoML leader exports whole).
"""

from __future__ import annotations

import io
import json
import zipfile

import numpy as np

__all__ = ["export_mojo", "import_mojo", "MojoModel", "MOJO_FORMAT",
           "read_mojo_parts"]

# format 2: tree ensembles carry the flattened serving arrays
# (flat_*) instead of heap tree_* + bin edges — bumped so an OLD
# reader rejects a new artifact cleanly instead of KeyError-ing deep
# in its scorer; THIS reader accepts both (legacy branch kept)
_FORMAT = "h2o_kubernetes_tpu/mojo/2"
_READABLE_FORMATS = ("h2o_kubernetes_tpu/mojo/1", _FORMAT)

# public name for consumers that must pin the CURRENT format (the
# operator model registry only ships v2 artifacts: replicas serve the
# flat_* arrays directly, so a v1 artifact has nothing to serve)
MOJO_FORMAT = _FORMAT


def _np(a):
    return np.asarray(a)


def _export_dinfo(meta: dict, arrays: dict, d) -> None:
    """Serialize a DataInfo design layout (shared by every expanded-
    design algo: glm/deeplearning/pca/kmeans/coxph/glrm)."""
    meta["numeric_idx"] = list(d.numeric_idx)
    meta["enum_specs"] = [list(s) for s in d.enum_specs]
    meta["drop_first"] = d.drop_first
    arrays["means"] = _np(d.means)
    arrays["stds"] = _np(d.stds)


def export_mojo(model, path) -> str:
    """Write `model` as a standalone scoring artifact at `path` (a
    filesystem path or a binary file-like object)."""
    algo = model.algo
    extra_files: dict[str, bytes] = {}
    # word2vec has no tabular design, so the shared fields are optional
    meta = {
        "format": _FORMAT,
        "algo": algo,
        "feature_names": getattr(model, "feature_names", []),
        "feature_domains": getattr(model, "feature_domains", {}),
        "nclasses": getattr(model, "nclasses", 1),
        "response_domain": getattr(model, "response_domain", None),
        "distribution": getattr(model, "distribution", None),
        # offset-trained models need the per-row offset at scoring time
        # too — omitting it would silently shift every MOJO prediction
        "offset_column": getattr(model, "offset_column", None),
    }
    arrays: dict[str, np.ndarray] = {}
    if algo in ("gbm", "drf", "xgboost"):
        from .models.tree.core import require_ordinal

        require_ordinal(model.trees, "MOJO export")
        meta["max_depth"] = model.params.max_depth
        meta["nbins"] = model.params.nbins
        meta["drf_mode"] = bool(model.params._drf_mode)
        meta["ntrees"] = model.ntrees
        meta["na_bin"] = model.bin_spec.na_bin
        meta["margin_scale"] = float(getattr(model, "margin_scale", 1.0))
        arrays["init_score"] = _np(model.init_score)
        arrays["enum_mask"] = _np(model._enum_mask)
        # the SAME flattening the in-process serving scorer descends
        # (models/tree/core.py flatten_trees, cached on the model):
        # compact reachable nodes + raw-feature thresholds — the
        # artifact scores without bin edges or re-binning
        flat = model._flat()
        for f in ("split_feat", "thresh", "left", "na_left", "value"):
            arrays[f"flat_{f}"] = _np(getattr(flat, f))
        # OPTIONAL cover part (still format 2 — extra npz keys are
        # invisible to older readers): per-flat-node training weight
        # mass, slot-aligned with the arrays above, which is all a
        # scorer replica needs to serve predict_contributions
        # (TreeSHAP path tables). Omitted when the source model
        # predates per-node cover (persist.py NaN-backfill sentinel) —
        # such artifacts keep serving margins and reject contributions
        # with a re-export message.
        cov = getattr(model.trees, "cover", None)
        if cov is not None and not np.isnan(_np(cov)).any():
            from .models.tree.core import flatten_cover

            arrays["flat_cover"] = flatten_cover(
                model.trees, model.params.max_depth)
    elif algo == "glm":
        from .models.glm import _famspec

        meta["family"] = model.params.family
        meta["link"] = _famspec(model.params).link
        arrays["beta"] = _np(model.beta)
        d = model.dinfo
        _export_dinfo(meta, arrays, d)
    elif algo == "deeplearning":
        meta["activation"] = model.params.activation
        meta["loss_kind"] = model.loss_kind
        meta["autoencoder"] = bool(model.params.autoencoder)
        meta["n_layers"] = len(model.net)
        d = model.dinfo
        _export_dinfo(meta, arrays, d)
        for i, lyr in enumerate(model.net):
            arrays[f"net_{i}_w"] = _np(lyr["w"])
            arrays[f"net_{i}_b"] = _np(lyr["b"])
    elif algo == "naivebayes":
        meta["num_cols"] = list(model.num_cols)
        meta["enum_cols"] = list(model.enum_cols)
        meta["n_enum_tables"] = len(model.enum_tables)
        arrays["priors"] = _np(model.priors)
        arrays["num_mean"] = _np(model.num_mean)
        arrays["num_sd"] = _np(model.num_sd)
        for i, tab in enumerate(model.enum_tables):
            arrays[f"nbtab_{i}"] = _np(tab)
    elif algo == "pca":
        d = model.dinfo
        _export_dinfo(meta, arrays, d)
        arrays["eigenvectors"] = _np(model.eigenvectors)
        arrays["eigenvalues"] = _np(model.eigenvalues)
    elif algo == "word2vec":
        meta["vocab"] = list(model.vocab)
        arrays["embeddings"] = _np(model.W)
    elif algo == "kmeans":
        arrays["centers"] = _np(model.centers_std)
        d = model.dinfo
        _export_dinfo(meta, arrays, d)
    elif algo == "isolationforest":
        meta["max_depth"] = model.params.max_depth
        meta["ntrees"] = model.ntrees
        meta["sample_size_effective"] = int(model.sample_size_effective)
        for f in ("split_feat", "split_val", "is_split", "count"):
            arrays[f"iso_{f}"] = _np(getattr(model.trees, f))
    elif algo == "coxph":
        # hex/coxph scoring is the linear log-hazard Xe·beta (SURVEY.md
        # §2b C17); the artifact is the expanded-design layout + beta
        d = model.dinfo
        _export_dinfo(meta, arrays, d)
        arrays["beta"] = _np(model.beta)
    elif algo == "glrm":
        # archetypes V + design layout: scoring solves the per-row
        # ridge U-step against fixed V (models/glrm.py::_solve_u)
        d = model.dinfo
        _export_dinfo(meta, arrays, d)
        meta["coef_names"] = list(d.coef_names[:-1])
        arrays["V"] = _np(model.V)
    elif algo == "targetencoder":
        # level→encoding tables; mojo transform is the SCORING path
        # (full-data stats, no leakage handling / noise — matching the
        # reference's TE mojo)
        p = model.params
        meta["te_columns"] = list(model.columns)
        meta["prior"] = float(model.prior)
        meta["blending"] = bool(p.blending)
        meta["inflection_point"] = float(p.inflection_point)
        meta["smoothing"] = float(p.smoothing)
        meta["te_domains"] = {c: list(model.tables[c]["domain"])
                              for c in model.columns}
        for i, c in enumerate(model.columns):
            arrays[f"te_sum_{i}"] = _np(model.tables[c]["sum"])
            arrays[f"te_cnt_{i}"] = _np(model.tables[c]["cnt"])
    elif algo == "stackedensemble":
        # one artifact nests every base model's MOJO plus the
        # metalearner's (reference: StackedEnsembleMojoWriter packs the
        # base mojos into the ensemble zip, SURVEY.md §2b C18) — so the
        # AutoML leader is servable even when it is an ensemble
        meta["base_tags"] = list(model.base_tags)
        meta["base_count"] = len(model.base_models)
        for i, bm in enumerate(model.base_models):
            buf = io.BytesIO()
            export_mojo(bm, buf)
            extra_files[f"base_{i}.mojo"] = buf.getvalue()
        buf = io.BytesIO()
        export_mojo(model.metalearner, buf)
        extra_files["metalearner.mojo"] = buf.getvalue()
    else:
        raise ValueError(f"mojo export not supported for algo '{algo}'")

    npz = io.BytesIO()
    np.savez_compressed(npz, **arrays)
    with zipfile.ZipFile(path, "w", zipfile.ZIP_DEFLATED) as z:
        z.writestr("model.json", json.dumps(meta))
        z.writestr("arrays.npz", npz.getvalue())
        for name, blob in extra_files.items():
            z.writestr(name, blob)
    return path


def import_mojo(path: str) -> "MojoModel":
    return MojoModel(path)


def read_mojo_parts(path, want_nested: bool = False
                    ) -> tuple[dict, dict, dict]:
    """(meta, arrays, nested) of a mojo artifact without building a
    scorer — the shared reader for MojoModel and the operator model
    registry (operator/registry.py validates the format/algo and wraps
    the arrays in a jitted serving scorer instead of numpy descent).

    ``nested`` holds the inner ``*.mojo`` blobs of a stackedensemble
    artifact when ``want_nested``; empty otherwise."""
    with zipfile.ZipFile(path) as z:
        meta = json.loads(z.read("model.json"))
        if meta.get("format") not in _READABLE_FORMATS:
            raise ValueError(f"not a {_FORMAT} artifact "
                             f"(format={meta.get('format')!r})")
        with np.load(io.BytesIO(z.read("arrays.npz"))) as npz:
            arrays = {k: npz[k] for k in npz.files}
        nested = {}
        if want_nested:
            nested = {n: z.read(n) for n in z.namelist()
                      if n.endswith(".mojo")}
    return meta, arrays, nested


class MojoModel:
    """Loads and scores a mojo artifact with numpy only."""

    def __init__(self, path):
        self.meta, self.arrays, nested = read_mojo_parts(
            path, want_nested=True)
        if self.meta["algo"] == "stackedensemble":
            self._base = [
                MojoModel(io.BytesIO(nested[f"base_{i}.mojo"]))
                for i in range(self.meta["base_count"])]
            self._metalearner = MojoModel(
                io.BytesIO(nested["metalearner.mojo"]))
        self.algo = self.meta["algo"]
        self.feature_names = self.meta["feature_names"]
        self.nclasses = self.meta["nclasses"]
        if self.algo == "word2vec":   # O(1) lookups on large vocabs
            self._word_index = {w: i for i, w in
                                enumerate(self.meta["vocab"])}

    # -- feature matrix from a dict of columns ------------------------------

    def _matrix(self, data) -> np.ndarray:
        """data: mapping name -> array (numeric values or string levels),
        or a Frame (columns decoded to raw values first — scoring-frame
        enum codes are NOT assumed to share the training domain)."""
        if hasattr(data, "vec") and hasattr(data, "names"):
            decoded = {}
            tdoms = self.meta["feature_domains"]
            for n in self.feature_names:
                if n not in data.names:
                    raise ValueError(f"missing feature column '{n}'")
                v = data.vec(n)
                # kind mismatches raise exactly like the in-process
                # Model._design_matrix — silently treating numerics as
                # category codes (or vice versa) scores garbage
                if tdoms.get(n) is not None and not v.is_enum():
                    raise ValueError(
                        f"column '{n}' was categorical at training time "
                        f"but is {v.kind} in the scoring frame")
                if tdoms.get(n) is None and v.is_enum():
                    raise ValueError(
                        f"column '{n}' was numeric at training time "
                        "but is categorical in the scoring frame")
                if v.is_enum():
                    dom = np.array(list(v.domain or []) + [None],
                                   dtype=object)
                    codes = v.to_numpy()
                    decoded[n] = dom[np.where(codes < 0, len(dom) - 1,
                                              codes)]
                elif v.kind == "time":
                    # reproduce as_float() f32 rounding (rel + f32
                    # origin) — training bin edges were fit on those
                    # values, and exact float64 epochs can land a
                    # boundary timestamp in a different bin
                    ms = v.to_numpy()
                    rel = (ms - v.origin).astype(np.float32)
                    decoded[n] = rel + np.float32(v.origin)
                else:
                    decoded[n] = v.to_numpy()
            data = decoded
        cols = []
        doms = self.meta["feature_domains"]
        for name in self.feature_names:
            if name not in data:
                raise ValueError(f"missing feature column '{name}'")
            col = np.asarray(data[name])
            dom = doms.get(name)
            if dom is not None and col.dtype.kind in ("U", "S", "O"):
                lut = {d: i for i, d in enumerate(dom)}
                col = np.array([lut.get(str(s), -1) for s in col],
                               dtype=np.float32)
                col[col < 0] = np.nan
            cols.append(col.astype(np.float32))
        return np.stack(cols, axis=1)

    def predict(self, data) -> np.ndarray:
        """[n, K] probabilities / [n] predictions / [n] cluster ids."""
        if self.algo == "stackedensemble":
            # bases consume the raw columns themselves — no shared
            # design matrix exists at the ensemble level
            return self._predict_se(data)
        if self.algo == "targetencoder":
            raise ValueError(
                "targetencoder artifacts score via transform(), not "
                "predict()")
        off = self._offset(data)
        X = self._matrix(data) if not isinstance(data, np.ndarray) \
            else data.astype(np.float32)
        if self.algo in ("gbm", "drf", "xgboost"):
            return self._predict_trees(X, off)
        if self.algo == "glm":
            return self._predict_glm(X, off)
        if self.algo == "kmeans":
            return self._predict_kmeans(X)
        if self.algo == "deeplearning":
            return self._predict_deeplearning(X, off)
        if self.algo == "naivebayes":
            return self._predict_naivebayes(X)
        if self.algo == "pca":
            return self._predict_pca(X)
        if self.algo == "isolationforest":
            return self._predict_isolationforest(X)
        if self.algo == "coxph":
            return self._predict_coxph(X)
        if self.algo == "glrm":
            return self._solve_u_glrm(X)
        raise ValueError(self.algo)

    def _offset(self, data) -> np.ndarray | None:
        """Per-row offset for offset-trained artifacts (same contract
        as the in-process Model.predict_raw: the column must be
        supplied at scoring time; NA offsets propagate as NaN)."""
        oc = self.meta.get("offset_column")
        if not oc:
            return None
        if isinstance(data, np.ndarray):
            raise ValueError(
                f"this artifact was trained with offset_column='{oc}'; "
                "score with a dict/Frame including that column, not a "
                "bare matrix")
        if hasattr(data, "vec") and hasattr(data, "names"):
            if oc not in data.names:
                raise ValueError(f"offset column '{oc}' missing from "
                                 "the scoring frame")
            return data.vec(oc).to_numpy().astype(np.float64)
        if oc not in data:
            raise ValueError(f"offset column '{oc}' missing from the "
                             "scoring data")
        return np.asarray(data[oc], dtype=np.float64)

    def _predict_se(self, data):
        """Run every base MOJO, assemble the level-one columns exactly
        like models/stackedensemble.py::_level_one_columns, then run
        the metalearner MOJO on them."""
        cols: dict[str, np.ndarray] = {}
        for bm, tag in zip(self._base, self.meta["base_tags"]):
            preds = bm.predict(data)
            if bm.nclasses == 2:
                cols[tag] = preds[:, 1]
            elif bm.nclasses > 2:
                for k in range(bm.nclasses):
                    cols[f"{tag}_p{k}"] = preds[:, k]
            else:
                cols[tag] = preds
        return self._metalearner.predict(cols)

    def _predict_coxph(self, X):
        """Linear log-hazard Xe·beta (CoxPHModel._score_matrix)."""
        return self._expand(X)[:, :-1] @ self.arrays["beta"]

    def _solve_u_glrm(self, X):
        """[n, k] row factors: per-row ridge solve against fixed V —
        numpy mirror of GLRMModel._solve_u, with the observed mask from
        the RAW matrix (expand mean-imputes, so the mask must not come
        from the expanded values)."""
        m = self.meta
        Xe = self._expand(X)[:, :-1]
        cols = [~np.isnan(X[:, i]) for i in m["numeric_idx"]]
        mats = [np.stack(cols, axis=1)] if cols else []
        for (i, L, has_na, mode) in m["enum_specs"]:
            ok = ~np.isnan(X[:, i])
            width = L - (1 if m["drop_first"] else 0) + (1 if has_na
                                                         else 0)
            mats.append(np.broadcast_to(ok[:, None], (X.shape[0], width)))
        mask = np.concatenate(mats, axis=1).astype(np.float32)
        Xz = np.nan_to_num(Xe) * mask
        V = self.arrays["V"]
        G = V.T @ V + 1e-6 * np.eye(V.shape[1], dtype=V.dtype)
        return Xz @ V @ np.linalg.inv(G)

    def reconstruct(self, data) -> dict[str, np.ndarray]:
        """GLRM imputation: U·Vᵀ in the expanded layout, keyed by
        coefficient name (GLRMModel.reconstruct analog)."""
        if self.algo != "glrm":
            raise ValueError("reconstruct() is a glrm accessor")
        X = self._matrix(data) if not isinstance(data, np.ndarray) \
            else data.astype(np.float32)
        rec = self._solve_u_glrm(X) @ self.arrays["V"].T
        return {f"reconstr_{n}": rec[:, i]
                for i, n in enumerate(self.meta["coef_names"])}

    def transform(self, data) -> dict[str, np.ndarray]:
        """TargetEncoder scoring transform: `<col>_te` encodings from
        the fitted full-data tables (no leakage handling, no noise —
        the TargetEncoderModel.transform(as_training=False) path)."""
        if self.algo != "targetencoder":
            raise ValueError("transform() is a targetencoder accessor")
        m = self.meta
        out: dict[str, np.ndarray] = {}
        for i, col in enumerate(m["te_columns"]):
            dom = m["te_domains"][col]
            if hasattr(data, "vec") and hasattr(data, "names"):
                v = data.vec(col)
                if not v.is_enum():
                    # same kind-mismatch contract as the in-process
                    # TargetEncoderModel._codes_for — str()-ifying
                    # numerics would silently encode every row as the
                    # prior (no domain string matches '1.0')
                    raise ValueError(f"'{col}' is not categorical")
                doms = list(v.domain or [])
                raw = v.to_numpy().astype(np.int64)
                vals = np.array(doms + [None], dtype=object)[
                    np.where(raw < 0, len(doms), raw)]
            else:
                vals = np.asarray(data[col])
                if vals.dtype.kind not in ("U", "S", "O"):
                    raise ValueError(f"'{col}' is not categorical")
            lut = {d: j for j, d in enumerate(dom)}
            codes = np.array([lut.get(str(s), -1) if s is not None
                              else -1 for s in vals], dtype=np.int64)
            sums = self.arrays[f"te_sum_{i}"].astype(np.float64)
            cnts = self.arrays[f"te_cnt_{i}"].astype(np.float64)
            mean = sums / np.maximum(cnts, 1.0)
            if m["blending"]:
                lam = 1.0 / (1.0 + np.exp(
                    -(cnts - m["inflection_point"])
                    / max(m["smoothing"], 1e-12)))
                enc_tab = lam * mean + (1.0 - lam) * m["prior"]
            else:
                enc_tab = mean
            enc_tab = np.where(cnts > 0, enc_tab, m["prior"])
            enc = np.where(codes >= 0, enc_tab[np.maximum(codes, 0)],
                           m["prior"])
            out[f"{col}_te"] = enc.astype(np.float32)
        return out

    def _predict_isolationforest(self, X):
        """[n, 2] (anomaly score, mean path length) — numpy mirror of
        IsolationForestModel._score_matrix (models/isolationforest.py)."""
        m = self.meta
        sf = self.arrays["iso_split_feat"]       # [T, N]
        sv = self.arrays["iso_split_val"]
        sp = self.arrays["iso_is_split"]
        cnt = self.arrays["iso_count"]
        Xf = np.nan_to_num(X.astype(np.float32))
        n = Xf.shape[0]

        def c_avg(x):
            x = np.maximum(x, 2.0)
            return (2.0 * (np.log(x - 1.0) + 0.5772156649)
                    - 2.0 * (x - 1.0) / x)

        total = np.zeros(n, dtype=np.float64)
        for t in range(m["ntrees"]):
            node = np.zeros(n, dtype=np.int64)
            depth = np.zeros(n, dtype=np.float64)
            for _ in range(m["max_depth"]):
                f = sf[t][node]
                v = sv[t][node]
                split = sp[t][node]
                rowval = Xf[np.arange(n), np.maximum(f, 0)]
                child = 2 * node + 1 + (rowval >= v).astype(np.int64)
                node = np.where(split, child, node)
                depth += split.astype(np.float64)
            leaf_n = cnt[t][node]
            total += depth + np.where(leaf_n > 1.0, c_avg(leaf_n), 0.0)
        mean_len = total / m["ntrees"]
        score = np.exp2(-mean_len / c_avg(
            np.float64(m["sample_size_effective"])))
        return np.stack([score, mean_len], axis=1).astype(np.float32)

    # -- word2vec accessors (no row scoring; embeddings ARE the model) ------

    def word_vector(self, word: str) -> np.ndarray:
        if self.algo != "word2vec":
            raise ValueError("word_vector() is a word2vec accessor")
        if word not in self._word_index:
            raise KeyError(word)
        return self.arrays["embeddings"][self._word_index[word]]

    def find_synonyms(self, word: str, count: int = 10) -> dict:
        if self.algo != "word2vec":
            raise ValueError("find_synonyms() is a word2vec accessor")
        W = self.arrays["embeddings"]
        vocab = self.meta["vocab"]
        v = self.word_vector(word)
        sims = (W @ v) / (np.linalg.norm(W, axis=1) *
                          np.linalg.norm(v) + 1e-12)
        order = np.argsort(-sims)
        out = {}
        for i in order:
            if vocab[i] == word:
                continue
            out[vocab[i]] = float(sims[i])
            if len(out) >= count:
                break
        return out

    # -- scorers -------------------------------------------------------------

    def _expand(self, X):
        """DataInfo.expand re-implemented in numpy (glm / kmeans)."""
        m = self.meta
        means, stds = self.arrays["means"], self.arrays["stds"]
        out = []
        for j, i in enumerate(m["numeric_idx"]):
            c = X[:, i].copy()
            c[np.isnan(c)] = means[j]
            out.append((c - means[j]) / stds[j])
        mats = [np.stack(out, axis=1)] if out else []
        for (i, L, has_na, mode) in m["enum_specs"]:
            c = X[:, i]
            code = np.where(np.isnan(c), L, c).astype(np.int32)
            if not has_na:
                code = np.where(code >= L, mode, code)
            lo = 1 if m["drop_first"] else 0
            width = L - lo + (1 if has_na else 0)
            levels = np.arange(lo, lo + width)
            mats.append((code[:, None] == levels[None, :])
                        .astype(np.float32))
        mats.append(np.ones((X.shape[0], 1), dtype=np.float32))
        return np.concatenate(mats, axis=1)

    def _bin(self, X):
        edges = self.arrays["edges"]
        enum_mask = self.arrays["enum_mask"]
        na_bin = self.meta["na_bin"]
        out = np.empty(X.shape, dtype=np.int32)
        for f in range(X.shape[1]):
            col = X[:, f]
            if enum_mask[f]:
                b = np.clip(np.nan_to_num(col, nan=-1), -1,
                            na_bin - 1).astype(np.int32)
                b[(col < 0) | np.isnan(col)] = na_bin
            else:
                b = np.searchsorted(edges[f], col, side="right")
                b = b.astype(np.int32)
                b[np.isnan(col)] = na_bin
            out[:, f] = b
        return out

    def _predict_trees(self, X, off=None):
        if "flat_split_feat" in self.arrays:
            totals = self._tree_totals_flat(X)
        else:            # artifact written by a pre-flattening build
            totals = self._tree_totals_binned(X)
        return self._combine_tree_totals(totals, off)

    def _tree_totals_flat(self, X):
        """[n, K] per-class leaf-value sums over the flattened ensemble
        (raw-feature thresholds; no binning) — the numpy mirror of
        models/tree/core.py flat_margin, same descent decisions."""
        m = self.meta
        sf = self.arrays["flat_split_feat"]      # [T, M]
        th = self.arrays["flat_thresh"]
        lf = self.arrays["flat_left"]
        nl = self.arrays["flat_na_left"]
        val = self.arrays["flat_value"]
        enum_mask = self.arrays["enum_mask"].astype(bool)
        Xc = np.where(enum_mask[None, :] & (X < 0), np.nan, X)
        T = sf.shape[0]
        n = Xc.shape[0]
        K = m["nclasses"] if m["nclasses"] > 2 else 1
        totals = np.zeros((n, K), dtype=np.float64)
        rows = np.arange(n)
        for t in range(T):
            node = np.zeros(n, dtype=np.int64)
            for _ in range(m["max_depth"]):
                f = sf[t][node]
                x = Xc[rows, np.maximum(f, 0)]
                with np.errstate(invalid="ignore"):
                    go_right = np.where(np.isnan(x), ~nl[t][node],
                                        x >= th[t][node])
                child = lf[t][node] + go_right.astype(np.int64)
                node = np.where(f >= 0, child, node)
            totals[:, t % K] += val[t][node]
        return totals

    def _tree_totals_binned(self, X):
        """Legacy-artifact scorer: re-bin, then heap re-descent."""
        m = self.meta
        binned = self._bin(X)
        sf = self.arrays["tree_split_feat"]      # [T, N]
        sb = self.arrays["tree_split_bin"]
        nl = self.arrays["tree_na_left"]
        sp = self.arrays["tree_is_split"]
        val = self.arrays["tree_value"]
        T = sf.shape[0]
        n = binned.shape[0]
        na_bin = m["na_bin"]
        K = m["nclasses"] if m["nclasses"] > 2 else 1
        totals = np.zeros((n, K), dtype=np.float64)
        for t in range(T):
            node = np.zeros(n, dtype=np.int64)
            for _ in range(m["max_depth"]):
                f = sf[t][node]
                b = sb[t][node]
                nleft = nl[t][node]
                split = sp[t][node]
                rowbin = binned[np.arange(n), np.maximum(f, 0)]
                is_na = rowbin == na_bin
                go_right = np.where(is_na, ~nleft, rowbin > b)
                child = 2 * node + 1 + go_right.astype(np.int64)
                node = np.where(split, child, node)
            totals[:, t % K] += val[t][node]
        return totals

    def _combine_tree_totals(self, totals, off=None):
        """Totals -> predictions: init/drf averaging/link, shared by
        the flat and legacy tree scorers."""
        m = self.meta
        T = m["ntrees"]            # total stacked trees (K-interleaved)
        K = m["nclasses"] if m["nclasses"] > 2 else 1
        init = np.atleast_1d(self.arrays["init_score"].astype(np.float64))
        if m["drf_mode"]:
            totals = totals / (T // K)
        probsum = totals + init[None, :]
        if off is not None:
            probsum = probsum + off[:, None]
        d = m["distribution"]
        if d == "bernoulli":
            mgn = probsum[:, 0]
            p1 = np.clip(mgn, 0, 1) if m["drf_mode"] else \
                1.0 / (1.0 + np.exp(-mgn))
            return np.stack([1 - p1, p1], axis=1)
        if d == "multinomial":
            if m["drf_mode"]:
                z = np.clip(probsum, 0, None)
                return z / (z.sum(axis=1, keepdims=True) + 1e-10)
            z = np.exp(probsum - probsum.max(axis=1, keepdims=True))
            return z / z.sum(axis=1, keepdims=True)
        if d in ("poisson", "gamma", "tweedie"):
            return np.exp(probsum[:, 0])
        scale = m.get("margin_scale", 1.0)
        if scale != 1.0:
            # laplace robust scaling never combines with an offset
            # (GBM.train rejects it), so off is None here
            return init[0] + scale * totals[:, 0]
        return probsum[:, 0]

    def _predict_glm(self, X, off=None):
        Xe = self._expand(X)
        eta = Xe @ self.arrays["beta"]
        if off is not None:
            eta = eta + off
        fam = self.meta["family"]
        if fam == "multinomial":
            z = np.exp(eta - eta.max(axis=1, keepdims=True))
            return z / z.sum(axis=1, keepdims=True)
        link = self.meta.get("link", "identity")
        if link == "logit":
            mu = 1.0 / (1.0 + np.exp(-eta))
        elif link == "log":
            mu = np.exp(np.clip(eta, -30, 30))
        elif link == "inverse":
            e = np.where(np.abs(eta) < 1e-6,
                         np.where(eta < 0, -1e-6, 1e-6), eta)
            mu = 1.0 / e
        else:
            mu = eta
        if fam == "binomial":
            return np.stack([1 - mu, mu], axis=1)
        return mu

    def _predict_deeplearning(self, X, off=None):
        m = self.meta
        h = self._expand(X)[:, :-1]          # bias lives in the layers
        act = np.tanh if m["activation"] == "tanh" else \
            (lambda v: np.maximum(v, 0.0))
        L = m["n_layers"]
        for i in range(L - 1):
            h = act(h @ self.arrays[f"net_{i}_w"] +
                    self.arrays[f"net_{i}_b"])
        out = h @ self.arrays[f"net_{L-1}_w"] + self.arrays[f"net_{L-1}_b"]
        if m["loss_kind"] == "ce":
            z = np.exp(out - out.max(axis=1, keepdims=True))
            return z / z.sum(axis=1, keepdims=True)
        if m["autoencoder"]:
            return out
        if off is not None:     # regression net was fit to y - offset
            return out[:, 0] + off
        return out[:, 0]

    def _predict_naivebayes(self, X):
        m = self.meta
        K = m["nclasses"]
        ll = np.broadcast_to(np.log(self.arrays["priors"])[None, :],
                             (X.shape[0], K)).copy()
        if m["num_cols"]:
            Xn = X[:, np.asarray(m["num_cols"])]
            mu, sd = self.arrays["num_mean"], self.arrays["num_sd"]
            z = (Xn[:, None, :] - mu[None]) / sd[None]
            lp = -0.5 * z * z - np.log(sd)[None]
            lp = np.where(np.isnan(Xn)[:, None, :], 0.0, lp)
            ll += lp.sum(axis=2)
        for i, ci in enumerate(m["enum_cols"]):
            tab = self.arrays[f"nbtab_{i}"]
            c = X[:, ci]
            code = np.clip(np.where(np.isnan(c), 0, c).astype(np.int64),
                           0, tab.shape[1] - 1)
            lp = np.log(tab.T)[code]
            ll += np.where(np.isnan(c)[:, None], 0.0, lp)
        mx = ll.max(axis=1, keepdims=True)
        p = np.exp(ll - mx)
        return p / p.sum(axis=1, keepdims=True)

    def _predict_pca(self, X):
        return self._expand(X)[:, :-1] @ self.arrays["eigenvectors"]

    def _predict_kmeans(self, X):
        Xe = self._expand(X)[:, :-1]
        C = self.arrays["centers"]
        d = (Xe * Xe).sum(1)[:, None] - 2 * Xe @ C.T + (C * C).sum(1)[None]
        return d.argmin(axis=1)
