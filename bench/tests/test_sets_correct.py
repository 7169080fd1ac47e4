"""`correct` of the set-split comparison (`compare/gbm_sets.py`) has to
come out false for the control and for every planted fault, each by the
number that is there to see it, at a size a test run can hold; and true
for the plain reference itself and for the program."""

import numpy as np
import pytest

import rehearse
import run
from reference import gbm_sets_plain
from registry import Registry

ROWS, TREES, SEED = 60_000, 3, 19
reg = Registry(rehearse.REPO)
comparison = reg.comparison("gbm_sets")
airline = reg.traffic("train_jobs_enum").table_module("airline_like")
PARAMS = dict(reg.config("gbm-airline")["params"], max_depth=6)
CONFIG = {"params": PARAMS, "levels": airline.LEVELS}
CELL = {"check_trees": TREES, "regret_trees": 2}


@pytest.fixture(scope="module")
def limits():
    return reg.cell("gbm-airline.train")["limits"]


@pytest.fixture(scope="module")
def table():
    X, y = airline.airline_like(ROWS, SEED)
    return np.ascontiguousarray(X.T), y


def read(table, **kw):
    model = gbm_sets_plain.train(*table, airline.LEVELS, PARAMS, TREES,
                                 **kw)
    return comparison.compare(model, *table, CONFIG, CELL, SEED)


def failed(numbers, limits):
    _, compared = run.verdict(numbers, limits)
    return {k for k, (v, lim) in compared.items() if not v <= lim}


def test_table_is_the_airline_tables_shape(table):
    Xr, y = table
    assert Xr.shape == (ROWS, 8) and 0.15 < y.mean() < 0.25
    for j, lv in enumerate(airline.LEVELS):
        if lv:
            col = Xr[:, j]
            assert col.min() >= 0 and col.max() < lv
            assert (col == np.round(col)).all()
    again, _ = airline.airline_like(ROWS, SEED)
    assert (again.T == Xr).all()
    other, _ = airline.airline_like(ROWS, SEED + 1)
    assert not (other.T == Xr).all()
    # hubs dominate: the busiest airport holds several per cent
    top = np.bincount(Xr[:, 5].astype(int), minlength=300).max()
    assert top > 0.02 * ROWS


@pytest.mark.parametrize("precision", ["float64", "float32"])
def test_reference_in_place_is_correct(table, limits, precision):
    ok, compared = run.verdict(read(table, precision=precision), limits)
    assert ok, compared


def test_control_bfloat16_is_not_correct(table, limits):
    numbers = read(table, precision="bfloat16")
    assert {"value_gap", "gain_gap"} <= failed(numbers, limits)
    assert numbers["cover_gap"] == 0


@pytest.mark.parametrize("fault,seen_by", [
    ("ordinal_codes", "regret_gap"),
    ("second_best", "regret_gap"),
    ("range_grouped", "cover_gap"),
    ("wrong_side", "cover_gap"),
    ("half_batch", "cover_gap"),
    ("altered_answer", "regret_gap"),
    ("stale_state", "logloss_gap"),
])
def test_fault_is_not_correct(table, limits, fault, seen_by):
    numbers = read(table, fault=fault)
    assert seen_by in failed(numbers, limits), numbers


@pytest.mark.parametrize("fault", ["ordinal_codes", "second_best"])
def test_only_the_regret_sees_a_valid_split_that_is_not_the_best(
        table, limits, fault):
    """Prefixes in code order (what the program did before it had
    sets) and a second-best feature are valid splits, recorded truly:
    every sum agrees and only the gain lost shows."""
    assert failed(read(table, fault=fault), limits) == {"regret_gap"}
