"""`correct` of the K-class comparison with set splits
(`compare/gbm_softmax_sets.py`) has to come out false for the control
and for every planted fault, each by the number that is there to see
it, at a size a test run can hold (40,000 rows of the KDD-Cup-like
table, all 23 classes, depth 4, two rounds); and true for the plain
reference itself, in float64 and with every row's g and h rounded to
float32."""

import numpy as np
import pytest

import rehearse
import run
from reference import gbm_softmax_sets_plain as ref
from registry import Registry

ROWS, ROUNDS, SEED = 40_000, 2, 29
reg = Registry(rehearse.REPO)
comparison = reg.comparison("gbm_softmax_sets")
kdd = reg.traffic("train_jobs_multi_enum").table_module("kddcup99_like")
PARAMS = dict(reg.config("gbm-kddcup99")["params"], max_depth=4)
CONFIG = dict(reg.config("gbm-kddcup99"), params=PARAMS, levels=kdd.LEVELS)
CELL = {"check_rounds": ROUNDS, "regret_rounds": 1, "regret_classes": 8}


@pytest.fixture(scope="module")
def limits():
    return reg.cell("gbm-kddcup99.train")["limits"]


@pytest.fixture(scope="module")
def table():
    X, y = kdd.kddcup99_like(ROWS, 2 ** 31 + SEED)
    return np.ascontiguousarray(X.T), y


def read(table, **kw):
    Xr, y = table
    model = ref.train(Xr, y, kdd.LEVELS, PARAMS, ROUNDS, kdd.K, **kw)
    return comparison.compare(model, Xr, y, CONFIG, CELL, SEED)


def failed(numbers, limits):
    _, compared = run.verdict(numbers, limits)
    return {k for k, (v, lim) in compared.items() if not v <= lim}


@pytest.mark.parametrize("precision", ["float64", "float32"])
def test_reference_in_place_is_correct(table, limits, precision):
    ok, compared = run.verdict(read(table, precision=precision), limits)
    assert ok, compared


def test_control_bfloat16_is_not_correct(table, limits):
    numbers = read(table, precision="bfloat16")
    assert failed(numbers, limits) == {"value_gap", "gain_gap"}, numbers
    assert numbers["cover_gap"] == 0


@pytest.mark.parametrize("fault,seen_by", [
    ("ordinal_codes", "regret_gap"),
    ("wrong_side", "cover_gap"),
    ("sequential_softmax", "value_gap"),
    ("stale_state", "logloss_gap"),
    ("half_batch", "cover_gap"),
    ("second_best", "regret_gap"),
    ("float32_split", "split_gap"),
])
def test_fault_is_not_correct(table, limits, fault, seen_by):
    numbers = read(table, fault=fault)
    assert seen_by in failed(numbers, limits), numbers
