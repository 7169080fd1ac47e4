"""`correct` of the ranking comparison (`compare/xgb_rank.py`) has to
come out false for the control and for every planted fault, each by the
number that is there to see it, at a size a test run can hold (60,000
rows in 500 ragged queries); and true for the plain reference itself."""

import numpy as np
import pytest

import rehearse
import run
from reference import lambdamart_plain
from registry import Registry

ROWS, QUERIES, TREES, SEED = 60_000, 500, 2, 23
reg = Registry(rehearse.REPO)
comparison = reg.comparison("xgb_rank")
mslr = reg.traffic("train_jobs_rank").table_module("mslr_like")
# the hessians of 60,000 rows sum to a fortieth of the cell's
PARAMS = dict(reg.config("xgb-mslr")["params"], max_depth=4,
              min_child_weight=2.5)
CELL = {"check_trees": TREES, "regret_trees": 2}


@pytest.fixture(scope="module")
def limits():
    return reg.cell("xgb-mslr.train")["limits"]


@pytest.fixture(scope="module")
def table():
    X, y, qid = mslr.mslr_like(ROWS, QUERIES, SEED)
    return np.ascontiguousarray(X.T), y, qid


def read(table, **kw):
    Xr, y, qid = table
    model = lambdamart_plain.train(Xr, y, qid, PARAMS, TREES, **kw)
    return comparison.compare(model, Xr, y, {"params": PARAMS, "qid": qid},
                              CELL, SEED)


def failed(numbers, limits):
    _, compared = run.verdict(numbers, limits)
    return {k for k, (v, lim) in compared.items() if not v <= lim}


def test_table_is_the_mslr_tables_shape(table):
    Xr, y, qid = table
    assert Xr.shape == (ROWS, 136) and Xr.dtype == np.float32
    assert len(mslr.NAMES) == len(set(mslr.NAMES)) == 136
    sizes = np.bincount(qid)
    assert len(sizes) == QUERIES and sizes.min() == 1
    assert sizes.max() == 1251 and (np.diff(qid) >= 0).all()
    shares = np.bincount(y, minlength=5) / ROWS
    assert y.max() <= 4 and 0.45 < shares[0] < 0.58
    assert 0.27 < shares[1] < 0.38 and 0.005 < shares[4] < 0.02
    again = mslr.mslr_like(ROWS, QUERIES, SEED)
    assert (again[0].T == Xr).all() and (again[2] == qid).all()
    # another seed: other rows, the same multiset of query sizes
    other = mslr.mslr_like(ROWS, QUERIES, SEED + 1)
    assert not (other[0].T == Xr).all()
    assert (np.bincount(other[2]) != sizes).any()
    assert (np.sort(np.bincount(other[2])) == np.sort(sizes)).all()
    # the kinds: small counts, 0/1 columns, an anchor stream half empty,
    # clicks mostly zero
    col = lambda name: Xr[:, mslr.NAMES.index(name)]  # noqa: E731
    assert set(np.unique(col("boolean_title"))) == {0.0, 1.0}
    assert len(np.unique(col("covered_terms_body"))) <= 7
    assert 0.4 < (col("length_anchor") == 0).mean() < 0.6
    assert (col("query_url_clicks") == 0).mean() > 0.7
    # the whole published split's sizes: 18,919 queries, 2,270,296 rows
    full = mslr.query_sizes(2_270_296, 18_919)
    assert full.sum() == 2_270_296 and full.min() == 1
    assert full.max() == 1251 and len(full) == 18_919


@pytest.mark.parametrize("precision", ["float64", "float32"])
def test_reference_in_place_is_correct(table, limits, precision):
    ok, compared = run.verdict(read(table, precision=precision), limits)
    assert ok, compared


def test_control_bfloat16_is_not_correct(table, limits):
    numbers = read(table, precision="bfloat16")
    assert "value_gap" in failed(numbers, limits), numbers
    assert numbers["cover_gap"] == 0


@pytest.mark.parametrize("fault,seen_by", [
    ("pointwise", "value_gap"),
    ("no_delta_ndcg", "value_gap"),
    ("cross_query", "value_gap"),
    ("truncated_query", "value_gap"),
    ("stale_rank", "value_gap"),
    ("unstable_ties", "value_gap"),
    ("stale_state", "value_gap"),
    ("half_batch", "cover_gap"),
    ("second_best", "regret_gap"),
    ("altered_answer", "regret_gap"),
])
def test_fault_is_not_correct(table, limits, fault, seen_by):
    numbers = read(table, fault=fault)
    assert seen_by in failed(numbers, limits), numbers


def test_only_the_regret_sees_a_valid_split_that_is_not_the_best(
        table, limits):
    assert failed(read(table, fault="second_best"), limits) \
        == {"regret_gap"}
