"""Model registry: versioned MOJO-v2 artifacts + the replica scorer.

The training cluster publishes a trained tree ensemble ONCE as a
versioned MOJO-v2 artifact (mojo.py — the flat_* serving arrays ARE
the wire format, PR 2), persisted through any persist.py backend
(local dir, mem://, s3://...). Scorer replicas never see the training
stack: the registry pushes an artifact over ``POST
/3/ModelRegistry/load`` and the replica wraps the flat arrays in a
``FlatTreeScorer`` — a ``Model`` whose ``_score_matrix`` descends the
SAME ``flat_margin`` executable the in-process serving scorer uses,
so predictions are bitwise-identical to the training-side model, and
``score_numpy``/the REST micro-batcher/the jitted-scorer cache all
just work. ``Model.warm_up`` then pre-traces the pow2 batch buckets
through the persistent XLA cache BEFORE the replica's ``/readyz``
flips (the warm-up contract: ``warm_cache_misses == 0`` on the first
real request).

Format-v1 artifacts (pre-flattening: heap trees + bin edges) are
REJECTED — they have no serving arrays to load; re-export with this
build.
"""

from __future__ import annotations

import base64
import hashlib
import io
import json
from typing import Sequence

import numpy as np

from .. import persist
from ..mojo import MOJO_FORMAT, export_mojo, read_mojo_parts
from ..models.base import Model

__all__ = ["ModelRegistry", "FlatTreeScorer", "load_artifact",
           "SERVABLE_ALGOS"]

# the registry serves TREE ensembles (the AutoML leaders that matter
# for throughput); GLM/DL artifact serving rides the same route once a
# flat scorer exists for them
SERVABLE_ALGOS = ("gbm", "drf", "xgboost")


class FlatTreeScorer(Model):
    """Servable model built from a MOJO-v2 tree artifact's flat arrays.

    Mirrors ``GBMModel._margins`` + ``_score_matrix`` op for op on the
    SAME ``flat_margin`` jitted executable (models/tree/core.py), so a
    replica scoring a pushed artifact is bitwise-identical to the
    training-side model serving in-process — pinned by
    tests/test_operator.py's round-trip test."""

    _serving_jit = True

    def __init__(self, meta: dict, arrays: dict):
        # Model.__init__ wants TrainData; a registry scorer has only
        # the artifact metadata — set the serving surface directly.
        # The artifact parts are kept (host numpy) because they ARE
        # this model's persistent state: Model.__getstate__ drops
        # _flat_trees assuming a lazy rebuild from heap trees, which
        # a registry scorer does not have — see __getstate__ below.
        self._artifact_meta = dict(meta)
        keep = ["init_score", "enum_mask", "flat_split_feat",
                "flat_thresh", "flat_left", "flat_na_left",
                "flat_value"]
        if "flat_cover" in arrays:
            # optional MOJO-v2 cover part: enables serving
            # predict_contributions (TreeSHAP path tables); artifacts
            # without it still serve margins
            keep.append("flat_cover")
        self._artifact_arrays = {k: np.asarray(arrays[k]) for k in keep}
        arrays = self._artifact_arrays
        self.algo = meta["algo"]
        self.feature_names = list(meta["feature_names"])
        self.feature_domains = dict(meta.get("feature_domains") or {})
        self.nclasses = int(meta["nclasses"])
        self.response_domain = meta.get("response_domain")
        self.distribution = meta.get("distribution")
        self.offset_column = meta.get("offset_column")
        self.scoring_history: list = []
        self.cv = None
        self.validation_metrics = None
        self.ntrees = int(meta["ntrees"])
        self.max_depth = int(meta["max_depth"])
        self.drf_mode = bool(meta["drf_mode"])
        self.margin_scale = float(meta.get("margin_scale", 1.0))
        self.init_score = np.asarray(arrays["init_score"])
        # device state (_flat_trees, _enum_mask) is built lazily by
        # _serving_prepare from the kept host arrays, so the byte-
        # budgeted scorer cache can evict it and a later score
        # re-promotes — rebuilding the SAME constants means the same
        # HLO, a persistent-cache hit, and bitwise-identical output
        self._serving_prepare()

    def _serving_prepare(self):
        """Build (or fetch) the device arrays; RETURNS them so callers
        hold locals — a concurrent byte-budget eviction may pop the
        attributes between a check and a read (the evict loop runs
        under _SCORER_LOCK, a trace in flight does not), and a
        check-then-self-read would AttributeError mid-score."""
        ft = self.__dict__.get("_flat_trees")
        em = self.__dict__.get("_enum_mask")
        if ft is not None and em is not None:
            return ft, em
        import jax.numpy as jnp

        from ..models.tree.core import FlatTrees

        arrays = self._artifact_arrays
        em = jnp.asarray(np.asarray(arrays["enum_mask"]).astype(bool))
        ft = FlatTrees(
            *(jnp.asarray(arrays[f"flat_{f}"])
              for f in ("split_feat", "thresh", "left", "na_left",
                        "value")))
        self._enum_mask = em
        self._flat_trees = ft
        return ft, em

    def _serving_evict(self) -> None:
        super()._serving_evict()
        self.__dict__.pop("_enum_mask", None)

    # -- compiled TreeSHAP serving -------------------------------------------

    def contrib_support(self) -> "str | None":
        """Mirror of GBMModel.contrib_support for a registry scorer:
        same precondition set, with the cover check against the
        artifact's optional ``flat_cover`` part."""
        if int(self.nclasses) > 2:
            return ("predict_contributions supports binomial "
                    "and regression models only")
        if self.offset_column:
            return ("predict_contributions is not supported "
                    "for models trained with an offset")
        if "flat_cover" not in self._artifact_arrays:
            return (
                "this artifact was exported without per-node cover "
                "(pre-cover build, or a source model trained before "
                "per-node cover existed); TreeSHAP needs it — "
                "re-export the model with this build")
        return None

    def _shap_sources(self):
        """(flat arrays, cover) straight from the kept artifact parts
        — identical numpy values to the training-side model's, so the
        base _contrib_prepare/_contrib_matrix produce the same device
        constants, the same HLO, and bitwise-identical contributions
        (pinned by tests/test_contrib.py)."""
        from ..models.tree.core import FlatTrees

        a = self._artifact_arrays
        flat = FlatTrees(
            *(np.asarray(a[f"flat_{f}"])
              for f in ("split_feat", "thresh", "left", "na_left",
                        "value")))
        return flat, np.asarray(a["flat_cover"])

    def _contrib_enum_mask(self):
        _, em = self._serving_prepare()
        return em

    def _contrib_scale_init(self) -> tuple[float, float]:
        scale = float(self.margin_scale)
        if self.drf_mode:
            scale /= self.ntrees
        return scale, float(np.asarray(self.init_score).ravel()[0])

    def export_artifact(self) -> bytes:
        """Re-serialize this scorer as a MOJO-v2 zip from its kept
        artifact parts — export_mojo cannot walk a registry scorer (no
        params/bin_spec/heap trees), so the REST mojo-download route
        and registry.publish use THIS for FlatTreeScorer instances.
        Semantically identical to the artifact it was loaded from
        (same meta, same arrays); the zip bytes themselves may differ
        (compression/ordering), so it gets its own digest on
        re-publish."""
        import zipfile

        npz = io.BytesIO()
        np.savez_compressed(npz, **self._artifact_arrays)
        buf = io.BytesIO()
        with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
            z.writestr("model.json", json.dumps(self._artifact_meta))
            z.writestr("arrays.npz", npz.getvalue())
        return buf.getvalue()

    def __getstate__(self):
        # the base Model pops _flat_trees (GBMModel rebuilds it lazily
        # from heap trees); this scorer HAS no heap trees — pickle the
        # artifact parts instead and rebuild everything from them
        return {"meta": self._artifact_meta,
                "arrays": self._artifact_arrays}

    def __setstate__(self, state):
        self.__init__(state["meta"], state["arrays"])

    def _score_matrix(self, X, offset=None):
        import jax
        import jax.numpy as jnp

        from ..models.tree.core import flat_margin

        # the eager predict() path reaches here without _cached_score
        # having run _serving_prepare; after an eviction the device
        # arrays must be rebuilt (concrete host→device constants —
        # safe even under a jit trace). LOCALS, not self-reads: a
        # concurrent eviction may pop the attributes mid-score.
        ft, em = self._serving_prepare()
        K = self.nclasses if self.nclasses > 2 else 1
        lv = flat_margin(ft, X, em, self.max_depth, K)      # [K, rows]
        if K == 1:
            m = lv[0]
            if self.drf_mode:
                m = m / self.ntrees
            base = self.init_score if offset is None \
                else self.init_score + offset
            m = base + self.margin_scale * m
        else:
            if self.drf_mode:
                lv = lv / (self.ntrees // K)
            m = (jnp.asarray(self.init_score)[:, None] + lv).T
        d = self.distribution
        if d == "bernoulli":
            p1 = jnp.clip(m, 0.0, 1.0) if self.drf_mode \
                else jax.nn.sigmoid(m)
            return jnp.stack([1.0 - p1, p1], axis=1)
        if d == "multinomial":
            if self.drf_mode:
                m = jnp.clip(m, 0.0, None)
                return m / (jnp.sum(m, axis=1, keepdims=True) + 1e-10)
            return jax.nn.softmax(m, axis=1)
        if d in ("poisson", "gamma", "tweedie"):
            return jnp.exp(m)
        return m


def load_artifact(blob: bytes) -> FlatTreeScorer:
    """MOJO-v2 artifact bytes -> a servable FlatTreeScorer.

    Rejects format-v1 artifacts (no flattened serving arrays — a
    replica would have to re-bin and heap-descend, i.e. carry the
    training stack) and non-tree algos, with actionable messages."""
    meta, arrays, _ = read_mojo_parts(io.BytesIO(blob))
    if meta.get("format") != MOJO_FORMAT:
        raise ValueError(
            f"artifact format {meta.get('format')!r} is not servable "
            f"by a scorer replica (need {MOJO_FORMAT}): format-v1 "
            "artifacts carry heap trees + bin edges, not the flattened "
            "serving arrays — re-export the model with this build")
    if meta.get("algo") not in SERVABLE_ALGOS:
        raise ValueError(
            f"algo '{meta.get('algo')}' is not servable by a scorer "
            f"replica (supported: {', '.join(SERVABLE_ALGOS)})")
    if "flat_split_feat" not in arrays:
        raise ValueError("artifact claims MOJO-v2 but lacks the flat_* "
                         "serving arrays — corrupt or tampered")
    return FlatTreeScorer(meta, arrays)


class ModelRegistry:
    """Versioned artifact store rooted at a persist.py path.

    Layout: ``<root>/index.json`` (name -> {latest, versions}) plus
    ``<root>/<name>-v<N>.mojo`` blobs. Single-writer by design (ONE
    operator process owns a registry root, like one controller owns a
    CRD); replicas only ever read."""

    def __init__(self, root: str):
        self.root = root

    # -- index ----------------------------------------------------------------

    def _index_path(self) -> str:
        return persist.join_path(self.root, "index.json")

    def _load_index(self) -> dict:
        # one read, not exists()+read: on a remote backend an
        # existence probe IS a full GET, so probing first would double
        # every registry operation's round-trips
        try:
            return json.loads(persist.read_bytes(self._index_path()))
        except FileNotFoundError:
            return {}       # fresh registry root

    def _save_index(self, idx: dict) -> None:
        # atomic + read-back-verified: the index is the registry's
        # single point of failure — a publish crashed mid-write must
        # leave the PREVIOUS intact index, never a torn one that
        # breaks every subsequent fetch's digest check
        persist.write_bytes_atomic(self._index_path(),
                                   json.dumps(idx, indent=1).encode())

    # -- publish / fetch ------------------------------------------------------

    def artifact_path(self, name: str, version: int) -> str:
        return persist.join_path(self.root, f"{name}-v{int(version)}.mojo")

    def publish(self, model, name: str) -> int:
        """Export `model` as the next version of artifact `name`;
        returns the new version number. The artifact is the exact
        MOJO-v2 zip export_mojo writes — one flattening code path
        shared with in-process serving and offline MojoModel scoring."""
        if getattr(model, "algo", None) not in SERVABLE_ALGOS:
            raise ValueError(
                f"cannot publish algo '{getattr(model, 'algo', '?')}' "
                f"to a scorer pool (supported: "
                f"{', '.join(SERVABLE_ALGOS)})")
        if getattr(model, "trees", None) is not None:
            from ..models.tree.core import require_ordinal

            require_ordinal(model.trees, "The registry's scorer "
                            "(ModelRegistry.publish)")
        if hasattr(model, "export_artifact"):
            # re-publishing a loaded FlatTreeScorer (replica-to-replica
            # promotion): it has no heap trees for export_mojo to walk,
            # but its kept artifact parts ARE the artifact
            blob = model.export_artifact()
        else:
            buf = io.BytesIO()
            export_mojo(model, buf)
            blob = buf.getvalue()
        idx = self._load_index()
        ent = idx.setdefault(name, {"latest": 0, "versions": {}})
        version = int(ent["latest"]) + 1
        path = self.artifact_path(name, version)
        # blob first, index second (a crash between the two leaves an
        # unreferenced blob, never an index entry without bytes)
        persist.write_bytes_atomic(path, blob)
        ent["versions"][str(version)] = {
            "path": path,
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
            "algo": model.algo,
        }
        ent["latest"] = version
        self._save_index(idx)
        return version

    def latest(self, name: str) -> int:
        ent = self._load_index().get(name)
        if not ent or not ent["latest"]:
            raise KeyError(f"no artifact '{name}' in registry "
                           f"{self.root}")
        return int(ent["latest"])

    def info(self, name: str, version: int) -> dict:
        ent = self._load_index().get(name) or {"versions": {}}
        try:
            return dict(ent["versions"][str(int(version))])
        except KeyError:
            raise KeyError(f"no artifact '{name}' v{version} in "
                           f"registry {self.root}") from None

    def fetch(self, name: str, version: int) -> bytes:
        blob = persist.read_bytes(self.artifact_path(name, version))
        want = self.info(name, version)["sha256"]
        got = hashlib.sha256(blob).hexdigest()
        if got != want:
            raise IOError(
                f"artifact '{name}' v{version} digest mismatch "
                f"({got[:12]} != indexed {want[:12]}) — refusing to "
                "serve a corrupted model")
        return blob

    # -- push to a replica ----------------------------------------------------

    def push(self, base_url: str, name: str, version: int,
             model_key: str, warm_buckets: Sequence[int] | None = None,
             timeout: float = 300.0, inline: bool | None = None,
             slo: str | None = None) -> dict:
        """POST the artifact to a replica's /3/ModelRegistry/load and
        block until it has loaded AND warmed (the route warms before
        it returns, so success here means the replica's readiness gate
        is satisfied).

        ``warm_buckets=None`` omits the field so the REPLICA resolves
        its own ``H2O_TPU_POOL_WARM_BUCKETS`` — a spec-pinned tuple
        overrides it. ``slo`` sets the model's default SLO class on
        the replica (rest.py SLO_CLASSES; per-request X-H2O-SLO still
        wins). ``inline=None`` sends the artifact PATH when the
        backend is host-visible (local FS / cloud schemes the replica
        can read) and falls back to inline base64 bytes for mem://
        roots, which exist only in THIS process."""
        if inline is None:
            inline = self.root.startswith("mem://")
        body = {"model_id": model_key, "name": name,
                "version": int(version)}
        if warm_buckets is not None:
            body["warm_buckets"] = [int(b) for b in warm_buckets]
        if slo is not None:
            body["slo"] = slo
        if inline:
            body["artifact_b64"] = base64.b64encode(
                self.fetch(name, version)).decode()
        else:
            body["path"] = self.artifact_path(name, version)
            body["sha256"] = self.info(name, version)["sha256"]
        return self._post_json(base_url, "/3/ModelRegistry/load",
                               body, timeout)

    def push_many(self, base_url: str, items: Sequence[Sequence],
                  warm_buckets: Sequence[int] | None = None,
                  timeout: float = 300.0,
                  require: bool = True) -> list[dict]:
        """Push a TENANT SET to one replica: ``items`` is a sequence
        of (artifact, version, model_key[, slo]) entries
        (ScorerPoolSpec.all_artifacts). With ``require`` (the
        default), the replica's required-model readiness set is
        declared FIRST — so ``/readyz`` cannot flip green between
        artifact 1 landing and artifact N, whatever order the pushes
        complete in. Returns the per-artifact load responses."""
        items = [tuple(it) for it in items]
        if require:
            self._post_json(base_url, "/3/ModelRegistry/require",
                            {"model_ids": [it[2] for it in items]},
                            timeout)
        out = []
        for it in items:
            name, version, model_key = it[0], it[1], it[2]
            slo = it[3] if len(it) > 3 else None
            out.append(self.push(base_url, name, version, model_key,
                                 warm_buckets=warm_buckets,
                                 timeout=timeout, slo=slo))
        return out

    @staticmethod
    def _post_json(base_url: str, path: str, body: dict,
                   timeout: float) -> dict:
        """POST with the runtime/retry.py backoff layer on TRANSIENT
        failures (replica 5xx/429, connection reset/refused, timeout):
        one flaky push during a rollout used to surface as
        ``load_failed`` and burn a crash-loop backoff slot on a
        replica that was merely busy. Permanent outcomes (4xx other
        than 429 — bad artifact, digest mismatch) propagate on the
        first attempt unchanged, so the poison-rollback path still
        fails fast. The load route is idempotent, so retrying a push
        whose response was lost is safe."""
        import urllib.error
        import urllib.request

        from ..runtime import retry as _retry

        data = json.dumps(body).encode()

        def attempt() -> dict:
            req = urllib.request.Request(
                base_url.rstrip("/") + path, data=data, method="POST",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    return json.loads(r.read())
            except urllib.error.HTTPError as e:
                if e.code == 429 or e.code >= 500:
                    ra = e.headers.get("Retry-After")
                    try:
                        ra = float(ra) if ra is not None else None
                    except ValueError:
                        ra = None
                    detail = e.read()[:200]
                    raise _retry.TransientError(
                        f"replica POST {path}: HTTP {e.code} "
                        f"{detail!r}", retry_after=ra) from None
                raise                       # 4xx: permanent, no retry
            except urllib.error.URLError as e:
                # refused / reset / DNS — the replica is restarting or
                # mid-drain; classic transient
                raise _retry.TransientError(
                    f"replica POST {path}: {e.reason!r}") from None
            except (TimeoutError, ConnectionError, OSError) as e:
                raise _retry.TransientError(
                    f"replica POST {path}: {e!r}") from None

        return _retry.call(attempt,
                           describe=f"registry push {path}")
