"""`correct` has to come out false for the control and for every
planted fault, at a size a test run can hold; and true for the plain
reference itself and for the program."""

import numpy as np
import pytest

import datasets
import rehearse
import run
from reference import gbm_plain
from registry import Registry

ROWS, TREES, SEED = 60_000, 3, 17
CONFIG = {"params": {"max_depth": 5, "nbins": 256, "learn_rate": 0.1,
                     "min_rows": 10.0, "min_split_improvement": 1e-5}}
CELL = {"check_trees": TREES, "regret_trees": 2}
comparison = Registry(rehearse.REPO).comparison("gbm_bernoulli")


@pytest.fixture(scope="module")
def limits():
    return Registry(rehearse.REPO).cell("gbm-higgs.train")["limits"]


@pytest.fixture(scope="module")
def table():
    X, y = datasets.higgs_like(ROWS, SEED)
    return np.ascontiguousarray(X.T), y


def read(table, **kw):
    model = gbm_plain.train(*table, CONFIG["params"], TREES, **kw)
    return comparison.compare(model, *table, CONFIG, CELL, SEED)


@pytest.mark.parametrize("precision", ["float64", "float32"])
def test_reference_in_place_is_correct(table, limits, precision):
    ok, compared = run.verdict(read(table, precision=precision), limits)
    assert ok, compared


def test_control_bfloat16_is_not_correct(table, limits):
    """Gradients rounded to bfloat16, the nearest precision below the
    configuration's float32, fail the gains and the leaf values."""
    numbers = read(table, precision="bfloat16")
    ok, compared = run.verdict(numbers, limits)
    assert not ok, compared
    # 8.2e-3 and 3.8e-3 at this size; 1.54e-2 and 2.6e-3 and more at
    # the cell's
    assert numbers["gain_gap"] > 2 * limits["gain_gap"]
    assert numbers["value_gap"] > 2 * limits["value_gap"]


def test_only_the_regret_sees_a_second_best_split(table, limits):
    """A grower that takes a valid split that is not the best records
    it truly: every sum agrees, and only the gain lost shows."""
    numbers = read(table, fault="second_best")
    _, compared = run.verdict(numbers, limits)
    failed = {k for k, (v, lim) in compared.items() if not v <= lim}
    assert failed == {"regret_gap"}, compared
    assert numbers["regret_gap"] > 100 * limits["regret_gap"]


def test_regret_trees_are_the_first_and_some_from_the_seed():
    draw = comparison.regret_trees
    assert draw(20, 0, 5) == [] and draw(20, 1, 5) == [0]
    assert draw(20, 2, 5) == draw(20, 2, 5) and draw(20, 2, 5)[0] == 0
    assert len({tuple(draw(20, 2, s)) for s in range(30)}) > 5
    assert draw(1, 2, 5) == [0] and draw(3, 5, 5) == [0, 1, 2]


@pytest.mark.parametrize("fault", gbm_plain.FAULTS)
def test_planted_fault_is_not_correct(table, limits, fault):
    ok, compared = run.verdict(read(table, fault=fault, shards=4), limits)
    assert not ok, compared


def test_verdict_needs_every_number():
    ok, compared = run.verdict({"a": 0.0}, {"a": 0.0, "b": 1.0})
    assert not ok and np.isnan(compared["b"][0])
    assert run.verdict({"a": 0.0, "b": 0.5}, {"a": 0.0, "b": 1.0})[0]
    assert not run.verdict({"a": 1e-9}, {"a": 0.0})[0]


# -- the rest of a run, with the timed path broken underneath ----------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearse.tiny_root(str(tmp_path_factory.mktemp("tiny")))


def broken_run(root, monkeypatch, breaker, workload="gbm-higgs.train"):
    """`run_cell` with `neutral_model`'s answer passed through
    ``breaker`` (a fault planted where the program hands over what it
    produced), or — where the fault lies in how the trees were grown —
    the job's training replaced by the reference carrying the fault.
    ``breaker`` gets the traffic kind's module and the comparison's."""
    import jax

    import h2o_kubernetes_tpu as h2o

    reg = Registry(root)
    mod, cmp = reg.traffic("train_jobs"), reg.comparison("gbm_bernoulli")
    monkeypatch.setattr(Registry, "traffic", lambda self, kind: mod)
    monkeypatch.setattr(Registry, "comparison", lambda self, name: cmp)
    breaker(mod, cmp, monkeypatch)
    devs = jax.devices()[:reg.entry(workload)["chips"]]
    with h2o.use_mesh(h2o.make_mesh(devices=devs)):
        return run.run_cell(reg, workload, 5, 0.3, False, devs)


def alter_answer(mod, cmp, monkeypatch):
    real = cmp.neutral_model

    def altered(m):
        out = real(m)
        out["trees"][1]["thr"][0] += np.float32(0.25)   # one split moved
        return out

    monkeypatch.setattr(cmp, "neutral_model", altered)


def stale_state(mod, cmp, monkeypatch):
    real = cmp.neutral_model

    def stale(m):
        out = real(m)
        # a step that returned its state unchanged grows the same tree
        out["trees"][1] = out["trees"][0]
        return out

    monkeypatch.setattr(cmp, "neutral_model", stale)


def grown_by(fault):
    def breaker(mod, cmp, monkeypatch):
        def job(self, index):
            cfg = self.config
            model = gbm_plain.train(
                np.ascontiguousarray(self.X.T), self.y, cfg["params"],
                self.trees, fault=fault, shards=4)
            return {"start": 0.0, "end": 1.0, "ingest_s": 0.1,
                    "job_s": 1.0, "model": model, "ok": True}

        monkeypatch.setattr(mod.Traffic, "job", job)
    return breaker


@pytest.mark.parametrize("name,breaker", [
    ("altered_answer", alter_answer), ("stale_state", stale_state),
    ("half_batch", grown_by("half_batch")),
    ("no_exchange", grown_by("no_exchange")),
    ("second_best", grown_by("second_best"))])
def test_run_with_a_broken_path_is_not_correct(root, monkeypatch, name,
                                               breaker):
    line = broken_run(root, monkeypatch, breaker)
    assert line["correct"] is False, (name, line["compared"])
    assert line["failed"] == 0      # the jobs ran; their answer is wrong


def test_run_that_compiles_in_its_window_fails_its_jobs(root, monkeypatch):
    import jax
    import jax.numpy as jnp

    def compiles(mod, cmp, monkeypatch):
        real = mod.Traffic.window

        def window(self, seconds):
            jax.jit(lambda x: x * 3 + len(self.models))(jnp.ones(7))
            return real(self, seconds)

        monkeypatch.setattr(mod.Traffic, "window", window)

    line = broken_run(root, monkeypatch, compiles)
    assert line["failed"] == line["attempted"] > 0
    assert line["correct"] is False
