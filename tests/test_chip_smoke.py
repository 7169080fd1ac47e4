"""Rehearsal of `chip_smoke.py` without the chip (ISSUE 22): its phases
at 20,000 rows x 5 trees on the suite's 8-device CPU mesh, the
4-vs-1-device comparison on 4 of those devices (`on-chip-measurement`
guide, sections 2.1 and 2.2), and the script's refusal to report
anything off the chip. The test steers (sizes, mesh, the SHAP impl);
the script itself has no off-chip option."""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke

ROWS_N, NTREES, DEPTH = 20_000, 5, 6
AUC_FLOOR = 0.7          # 5 trees on 20k rows; the script's is for 50 on 2M
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cols():
    return chip_smoke.higgs_like(ROWS_N, seed=0)


@pytest.fixture(scope="module")
def trained(cols, mesh8):
    _, m, auc = chip_smoke.phase_train(cols, NTREES, DEPTH, mesh8,
                                       auc_floor=AUC_FLOOR)
    return m, auc


def test_data_is_higgs_shaped_and_seeded(cols):
    assert len(cols) == chip_smoke.N_FEATURES + 1
    assert set(cols["y"]) == {"b", "s"}
    again = chip_smoke.higgs_like(ROWS_N, seed=0)
    assert all((cols[k] == again[k]).all() for k in cols)
    other = chip_smoke.higgs_like(ROWS_N, seed=1)
    assert not (cols["f0"] == other["f0"]).all()


def test_train_phase(trained, mesh8):
    m, auc = trained
    assert m.ntrees == NTREES and m.params.max_depth == DEPTH
    assert AUC_FLOOR < auc < 1.0
    with pytest.raises(AssertionError, match="does not beat the floor"):
        # the floor is a real check: an unreachable one fails the phase
        chip_smoke.phase_train(chip_smoke.higgs_like(2_000, 0), 1, 2,
                               mesh8, auc_floor=0.999)


def test_kernel_checks_phase(cols, trained):
    X = chip_smoke.feature_matrix(cols, 4096)
    rep = chip_smoke.phase_kernel_checks(trained[0], X, seed=0,
                                         hist_rows=ROWS_N)
    assert rep["hist_rel_err"] < 1e-5 and rep["margin_bitwise"]
    # interpret mode on the CPU: no Mosaic call — main() requires one
    assert rep["hist_custom_call"] is False


@pytest.mark.parametrize("shap_kernel", ["1", "0"])
def test_serve_phase(cols, trained, mesh8, monkeypatch, shap_kernel):
    """Registry publish -> in-process REST server -> push -> HTTP
    scoring and contributions, with the SHAP kernel forced (interpret
    mode here) and killed: the phase counts the groups per impl."""
    monkeypatch.setenv("H2O_TPU_SHAP_KERNEL", shap_kernel)
    X = chip_smoke.feature_matrix(cols, 1024)
    rep = chip_smoke.phase_serve(trained[0], X, mesh8,
                                 score_batches=(1, 128, 1024),
                                 contrib_batches=(128, 256))
    assert rep["breaker"] == "closed" and rep["dispatch_failures"] == 0
    assert rep["requests"] == 5 and rep["additivity_err"] < 1e-4
    took = "kernel" if shap_kernel == "1" else "xla"
    for plan in rep["contrib_plan"].values():
        assert plan.get(took, 0) > 0 and set(plan) <= {took, "dp"}, plan


def test_mesh_compare_phase(cols):
    """The --chips 4 path on 4 of the 8 virtual devices."""
    rep = chip_smoke.phase_mesh_compare(cols, NTREES, DEPTH,
                                        jax.devices()[:4],
                                        auc_floor=AUC_FLOOR)
    assert rep["sharded_arrays"] >= chip_smoke.N_FEATURES + 2
    assert abs(rep["mesh_auc"] - rep["one_auc"]) < 1e-3
    assert rep["near_tie_nodes"] <= 0.05 * rep["split_nodes"]


def test_script_refuses_without_a_tpu():
    """`python chip_smoke.py` off the chip: non-zero exit, a message
    naming the platform it found, and no result on stdout."""
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert "platform 'cpu'" in r.stderr
    assert '"ok": true' not in r.stdout and r.stdout.strip() == ""
