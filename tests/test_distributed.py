"""Multi-host (DCN) proof: 2 real processes, one global mesh, one psum.

The reference scales across hosts with one JVM per pod gossiping over
TCP (SURVEY.md §2d multi-host row, §5.8); the TPU-native equivalent is
`jax.distributed.initialize` + collectives that ride DCN. This test is
the localhost-scale version of that claim — the same trick the
reference's own multi-JVM localhost tests use (§4b): no mocks, a real
2-process cluster.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(__file__), "dcn_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _run_workers(mode: str, timeout: float = 240,
                 expect_rc=(0, 0)) -> list[str]:
    port = _free_port()
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(_WORKER)) + \
        os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, _WORKER, str(port), str(i), mode],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        text=True) for i in range(2)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"DCN {mode} workers hung; partial output: {outs}")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == expect_rc[i], \
            f"worker {i} rc={p.returncode} (want {expect_rc[i]}):\n{out}"
    return outs


def test_two_process_dcn_psum():  # bounded by communicate(timeout=)
    outs = _run_workers("psum")
    for i, out in enumerate(outs):
        assert "DCN_OK" in out, f"worker {i} output:\n{out}"


@pytest.mark.slow
def test_two_process_gbm_train():
    """A FULL fused-scan GBM train across 2 jax.distributed processes:
    every tree level's histogram psum crosses the process boundary, and
    both controllers must end with the identical reduced model (the
    round-2 DRF worker-crash class of defect lives on this path, which
    the virtual single-process mesh cannot reach)."""
    outs = _run_workers("gbm", timeout=600)
    aucs = set()
    for i, out in enumerate(outs):
        assert "DCN_GBM_OK" in out, f"worker {i} output:\n{out}"
        aucs.add(out.split("auc=")[1].split()[0])
    assert len(aucs) == 1, f"processes disagree on the model: {aucs}"


@pytest.mark.slow
def test_two_process_glm_irlsm():
    """Binomial IRLSM across 2 processes: the distributed Gram
    accumulation (XᵀWX psum) rides DCN every iteration and the solved
    coefficients must recover the generating model."""
    outs = _run_workers("glm", timeout=600)
    x1s = set()
    for i, out in enumerate(outs):
        assert "DCN_GLM_OK" in out, f"worker {i} output:\n{out}"
        x1s.add(out.split("x1=")[1].split()[0])
    assert len(x1s) == 1, f"processes disagree on beta: {x1s}"


@pytest.mark.slow
def test_process_drop_fails_fast():
    """Member loss mid-session: process 1 dies after cloud formation;
    process 0's heartbeat must flip unhealthy and the next train must
    raise ClusterHealthError (reference semantics: the locked cloud
    becomes unusable, jobs fail cleanly — SURVEY.md §5.3)."""
    outs = _run_workers("drop", timeout=600, expect_rc=(0, 17))
    assert "DCN_DROP_OK" in outs[0], f"worker 0 output:\n{outs[0]}"
    assert "DCN_DROP_EXITING" in outs[1], f"worker 1 output:\n{outs[1]}"
