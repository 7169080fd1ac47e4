"""`correct` of the set-split forest's comparison (`compare/drf_sets.py`)
has to come out false for the control and for every planted fault, at
a size a test run can hold, each by the number that is there to see it;
and true for the plain reference forest itself. (The program's own
forest is held to the cell's limits in `test_drf_airline_cell.py`, and
to the reference split for split in `tests/test_drf_sets.py`.)"""

import numpy as np
import pytest

import rehearse
import run
from reference import drf_sets_plain
from registry import Registry

ROWS, TREES, SEED = 60_000, 3, 29
reg = Registry(rehearse.REPO)
comparison = reg.comparison("drf_sets")
airline = reg.traffic("train_jobs_enum").table_module("airline_like")
PARAMS = reg.config("drf-airline")["params"]
CONFIG = {"params": PARAMS, "levels": airline.LEVELS}
CELL = {"check_trees": TREES, "regret_trees": 2}
# which numbers have to catch which fault (others may as well)
SEEN_BY = {"ordinal_codes": {"regret_gap"},
           "range_grouped": {"cover_gap"},
           "wrong_side": {"cover_gap"},
           "stale_bag": {"cover_gap"},
           "unbagged": {"bag_rate_gap"},
           "all_features": {"mtries_gap"},
           "second_best": {"regret_gap"},
           "half_batch": {"cover_gap", "value_gap"},
           "bag_metric": {"logloss_gap", "auc_gap"}}


@pytest.fixture(scope="module")
def limits():
    got = dict(reg.cell("drf-airline.train")["limits"])
    # a bag of 60,000 rows keeps its share to within 3 standard
    # deviations of 0.2% where the cell's 8,388,608 keep it to 0.017%
    got["bag_rate_gap"] = max(got["bag_rate_gap"], 0.012)
    return got


@pytest.fixture(scope="module")
def table():
    X, y = airline.airline_like(ROWS, SEED)
    return np.ascontiguousarray(X.T), y


def read(table, **kw):
    model = drf_sets_plain.train(*table, airline.LEVELS, PARAMS, TREES,
                                 SEED, **kw)
    return comparison.compare(model, *table, CONFIG, CELL, SEED)


def failed(numbers, limits):
    _, compared = run.verdict(numbers, limits)
    return {k for k, (v, lim) in compared.items() if not v <= lim}


def test_the_cell_limits_every_number_the_comparison_gives(table, limits):
    assert set(read(table)) == set(limits)


def test_reference_in_place_is_correct(table, limits):
    numbers = read(table)
    assert not failed(numbers, limits), numbers
    assert numbers["cover_gap"] == 0 and numbers["mtries_gap"] == 0


def test_control_bfloat16_is_not_correct(table, limits):
    """Histogram sums rounded to bfloat16, the nearest precision below
    the configuration's float32, lose the integers past 256: covers,
    leaves and gains all go."""
    numbers = read(table, precision="bfloat16")
    assert {"cover_gap", "value_gap", "gain_gap"} <= failed(numbers, limits)


@pytest.mark.parametrize("fault", drf_sets_plain.FAULTS)
def test_fault_is_not_correct(table, limits, fault):
    numbers = read(table, fault=fault)
    assert SEEN_BY[fault] <= failed(numbers, limits), (fault, numbers)


@pytest.mark.parametrize("fault", ["ordinal_codes", "second_best"])
def test_only_the_regret_sees_a_valid_split_that_is_not_the_best(
        table, limits, fault):
    """Prefixes in code order (what a forest without sets does to an
    enum) and a second-best candidate are valid splits, recorded truly:
    every sum agrees and only the gain lost shows."""
    assert failed(read(table, fault=fault), limits) == {"regret_gap"}


def test_a_level_sent_the_other_way_is_seen(table, limits):
    """A set handed out with one level on the other side than the rows
    took it: the rows that follow the handed-out set reach other
    nodes."""
    model = drf_sets_plain.train(*table, airline.LEVELS, PARAMS, TREES,
                                 SEED)
    tree = model["trees"][0]
    i = int(np.flatnonzero(tree["is_set"])[0])
    f = int(tree["feat"][i])
    level = int(np.bincount(table[0][:, f].astype(int)).argmax())
    tree["left"][i, level] ^= True
    numbers = comparison.compare(model, *table, CONFIG, CELL, SEED)
    assert "cover_gap" in failed(numbers, limits)
