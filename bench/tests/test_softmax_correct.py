"""`correct` of the K-class comparison (`compare/gbm_softmax.py`) has to
come out false for the control and for every planted fault, each by the
number that is there to see it, at a size a test run can hold (60,000
rows of the Covertype-like table, 7 classes); and true for the plain
reference itself."""

import numpy as np
import pytest

import rehearse
import run
from reference import gbm_softmax_plain
from registry import Registry

ROWS, ROUNDS, SEED, K = 60_000, 2, 29, 7
reg = Registry(rehearse.REPO)
comparison = reg.comparison("gbm_softmax")
covtype = reg.traffic("train_jobs_multi").table_module("covtype_like")
CONFIG = dict(reg.config("xgb-covtype"))
PARAMS = dict(CONFIG["params"], max_depth=4)
CONFIG["params"] = PARAMS
CELL = {"check_rounds": ROUNDS, "regret_rounds": 2}


@pytest.fixture(scope="module")
def limits():
    return reg.cell("xgb-covtype.train")["limits"]


@pytest.fixture(scope="module")
def table():
    X, y = covtype.covtype_like(ROWS, SEED)
    return np.ascontiguousarray(X.T), y


def read(table, **kw):
    Xr, y = table
    model = gbm_softmax_plain.train(Xr, y, PARAMS, ROUNDS, K, **kw)
    return comparison.compare(model, Xr, y, CONFIG, CELL, SEED)


def failed(numbers, limits):
    _, compared = run.verdict(numbers, limits)
    return {k for k, (v, lim) in compared.items() if not v <= lim}


def test_table_is_the_covertype_tables_shape(table):
    Xr, y = table
    assert Xr.shape == (ROWS, 54) and Xr.dtype == np.float32
    assert len(covtype.NAMES) == len(set(covtype.NAMES)) == 54
    # integers held as float32, in the real columns' ranges
    assert (Xr == np.rint(Xr)).all()
    col = lambda name: Xr[:, covtype.NAMES.index(name)]  # noqa: E731
    assert 1860 <= col("Elevation").min() and col("Elevation").max() <= 3860
    assert col("Aspect").min() == 0 and col("Aspect").max() == 360
    assert col("Slope").max() <= 66
    assert col("Vertical_Distance_To_Hydrology").min() < 0
    for name in ("Hillshade_9am", "Hillshade_Noon", "Hillshade_3pm"):
        assert 0 <= col(name).min() and col(name).max() <= 255
    # two one-hot groups, exactly one 1 a row each, skewed
    wild, soil = Xr[:, 10:14], Xr[:, 14:]
    assert set(np.unique(Xr[:, 10:])) == {0.0, 1.0}
    assert (wild.sum(axis=1) == 1).all() and (soil.sum(axis=1) == 1).all()
    assert soil.shape[1] == 40 and soil.mean(axis=0).max() > 0.12
    assert soil.mean(axis=0).min() < 2e-4          # under a hundred of 581,012
    # seven classes in the published shares; the rare one learnable
    shares = np.bincount(y, minlength=7) / ROWS
    want = covtype.SHARES
    assert y.max() == 6 and np.abs(shares - want).max() < 0.01
    assert 0.003 < shares[3] < 0.007
    by_class = [col("Elevation")[y == k].mean() for k in range(7)]
    assert np.argmin(by_class) == 3 and np.argmax(by_class) == 6
    cols = covtype.as_columns(Xr.T, y)
    assert cols["y"].dtype.kind == "U" and cols["y"].dtype.itemsize == 4
    assert sorted(set(cols["y"])) == list("1234567")
    again = covtype.covtype_like(ROWS, SEED)
    assert (again[0].T == Xr).all() and (again[1] == y).all()
    other = covtype.covtype_like(ROWS, SEED + 1)
    assert not (other[0].T == Xr).all()


@pytest.mark.parametrize("precision", ["float64", "float32"])
def test_reference_in_place_is_correct(table, limits, precision):
    ok, compared = run.verdict(read(table, precision=precision), limits)
    assert ok, compared


def test_control_bfloat16_is_not_correct(table, limits):
    numbers = read(table, precision="bfloat16")
    assert "value_gap" in failed(numbers, limits), numbers
    assert numbers["cover_gap"] == 0


@pytest.mark.parametrize("fault,seen_by", [
    ("sequential_softmax", "value_gap"),
    ("one_vs_rest", "value_gap"),
    ("class_shift", "value_gap"),
    ("shared_gradient", "value_gap"),
    ("stale_state", "value_gap"),
    ("half_batch", "cover_gap"),
    ("second_best", "regret_gap"),
    ("altered_answer", "regret_gap"),
])
def test_fault_is_not_correct(table, limits, fault, seen_by):
    numbers = read(table, fault=fault)
    assert seen_by in failed(numbers, limits), numbers


def test_class_shift_and_stale_state_move_the_reported_logloss(
        table, limits):
    """A tree added to another class's margin, or to none: the metric
    the job reports is no longer the final margin's."""
    for fault in ("class_shift", "stale_state"):
        assert "logloss_gap" in failed(read(table, fault=fault), limits)


def test_only_the_regret_sees_a_valid_split_that_is_not_the_best(
        table, limits):
    assert failed(read(table, fault="second_best"), limits) \
        == {"regret_gap"}
