"""Failure detection (SURVEY.md §5.3) + AutoML checkpoint-resume (§5.4).

The reference detects node loss via heartbeats and fails fast (locked
cloud, jobs fail cleanly, no elasticity); recovery is out-of-band. The
TPU build mirrors that: a collective liveness probe, `doall` raising on
an unhealthy cluster, and resume via the AutoML manifest.
"""

import numpy as np
import pytest

import h2o_kubernetes_tpu as h2o
from h2o_kubernetes_tpu.runtime import health
from h2o_kubernetes_tpu.runtime.mrtask import doall


@pytest.fixture(autouse=True)
def _fresh_health():
    health.reset()
    yield
    health.reset()


def test_heartbeat_probe_succeeds(mesh8):
    assert health.heartbeat(timeout=120.0)
    st = health.health_status()
    assert st["healthy"] and st["beats"] == 1 and st["last_beat"]
    assert h2o.cluster_status()["cloud_healthy"]


def test_unhealthy_cluster_fails_fast(mesh8):
    import jax.numpy as jnp

    health.mark_unhealthy("simulated chip loss")
    with pytest.raises(health.ClusterHealthError, match="simulated"):
        doall(lambda x: {"s": jnp.sum(x)},
              jnp.ones(16), reduce="sum")
    assert not h2o.cluster_status()["cloud_healthy"]
    health.reset()                      # restart semantics
    out = doall(lambda x: {"s": jnp.sum(x)}, jnp.ones(16), reduce="sum")
    assert float(out["s"]) == 16.0


def _toy_frame(n=300, seed=5):
    rng = np.random.default_rng(seed)
    x0 = rng.normal(size=n).astype(np.float32)
    x1 = rng.normal(size=n).astype(np.float32)
    y = np.where(x0 + 0.5 * x1 + rng.normal(scale=0.4, size=n) > 0,
                 "y", "n")
    return h2o.Frame.from_arrays({"x0": x0, "x1": x1, "y": y})


@pytest.mark.slow
def test_automl_resume_from_manifest(tmp_path, mesh8):
    fr = _toy_frame()
    kw = dict(nfolds=2, seed=3, project_name="resume_t",
              include_algos=["gbm", "glm"], verbosity=None,
              checkpoint_dir=str(tmp_path))
    a1 = h2o.AutoML(max_models=2, **kw)
    a1.train(y="y", training_frame=fr)
    ids1 = [r["model_id"] for r in a1.leaderboard.rows]
    assert len(ids1) == 2
    assert (tmp_path / "automl_manifest.json").exists()

    # a rerun with a larger budget resumes the finished steps (no
    # retraining) and continues with new ones
    a2 = h2o.AutoML(max_models=4, **kw)
    a2.train(y="y", training_frame=fr)
    ids2 = [r["model_id"] for r in a2.leaderboard.rows]
    assert set(ids1) <= set(ids2)
    assert len([i for i in ids2 if "Ensemble" not in i]) == 4
    # resumed models predict
    m = a2.leaderboard.models[ids1[0]]
    assert m.predict(fr).nrows == fr.nrows


def test_automl_job_fails_cleanly_on_dead_cluster(mesh8):
    fr = _toy_frame()
    health.mark_unhealthy("simulated failure")
    a = h2o.AutoML(max_models=1, nfolds=2, include_algos=["gbm"],
                   project_name="failfast_t", verbosity=None)
    with pytest.raises(health.ClusterHealthError):
        a.train(y="y", training_frame=fr)
    assert a.job.status == "FAILED"


def test_gbm_fails_fast_mid_train(mesh8, monkeypatch):
    """VERDICT r2 item 6: a mesh that dies MID-train must surface as
    ClusterHealthError at the next chunk boundary, not a hang/crash —
    the tree core dispatches shard_map directly, bypassing doall."""
    from h2o_kubernetes_tpu.models import GBM
    from h2o_kubernetes_tpu.models import gbm as gbm_mod

    rng = np.random.default_rng(5)
    n = 500
    x = rng.normal(size=n).astype(np.float32)
    y = np.where(x > 0, "p", "n")
    fr = h2o.Frame.from_arrays({"x": x, "y": y})
    # force one tree per dispatch so the loop has chunk boundaries
    monkeypatch.setattr(gbm_mod, "_DISPATCH_BUDGET", 1)
    orig = gbm_mod.BoostPlan.dispatch
    calls = {"n": 0}

    def dying_boost(*a, **kw):
        out = orig(*a, **kw)
        calls["n"] += 1
        if calls["n"] == 2:         # mesh dies after the second chunk
            health.mark_unhealthy("ICI link down (test)")
        return out

    monkeypatch.setattr(gbm_mod.BoostPlan, "dispatch", dying_boost)
    try:
        with pytest.raises(health.ClusterHealthError):
            GBM(ntrees=6, max_depth=3, seed=0).train(
                y="y", training_frame=fr)
    finally:
        health.reset()
    assert calls["n"] == 2          # no further dispatch after death


def test_glm_fails_fast_mid_train(mesh8, monkeypatch):
    from h2o_kubernetes_tpu.models import GLM
    from h2o_kubernetes_tpu.models import glm as glm_mod

    rng = np.random.default_rng(6)
    n = 400
    x = rng.normal(size=n).astype(np.float32)
    y = np.where(x + rng.normal(scale=0.5, size=n) > 0, "p", "n")
    fr = h2o.Frame.from_arrays({"x": x, "y": y})
    orig = glm_mod._gram_task
    calls = {"n": 0}

    def dying_gram(*a, **kw):
        out = orig(*a, **kw)
        calls["n"] += 1
        if calls["n"] == 2:
            health.mark_unhealthy("chip hang (test)")
        return out

    monkeypatch.setattr(glm_mod, "_gram_task", dying_gram)
    try:
        with pytest.raises(health.ClusterHealthError):
            # binomial iterates (gaussian-identity solves in one shot);
            # zero tolerances keep it iterating past the failure point
            GLM(family="binomial", max_iterations=20,
                objective_epsilon=0.0, beta_epsilon=0.0).train(
                    y="y", training_frame=fr)
    finally:
        health.reset()
