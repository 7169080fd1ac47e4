"""`work.py` against cases computed by hand."""

import json
import os

import pytest

import work

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_level_counts_by_hand():
    # 1000 rows, 28 one-byte codes + 16 bytes of row state = 44 KB
    assert work.level_bytes(1000, 28) == 44_000
    # each of the 28 codes adds into 3 channels
    assert work.level_adds(1000, 28, 3) == 84_000


def test_level_is_bound_by_bytes_on_a_v5e():
    s, bound = work.level_min_seconds(4_194_304, 28, 3, PEAK)
    assert bound == "bytes"
    # 4,194,304 x 44 B = 184,549,376 B over 819 GB/s
    assert s == pytest.approx(184_549_376 / 819e9)
    assert s == pytest.approx(2.2533e-4, rel=1e-4)


def test_level_bound_by_adds_when_bytes_are_cheap():
    fast = {"hbm_bytes_per_s": 1e18, "bf16_flops_per_s": 1e6}
    s, bound = work.level_min_seconds(10, 2, 3, fast)
    assert bound == "adds" and s == pytest.approx(60 / 1e6)


SHAPE = {"rows": 4_194_304, "features": 28, "trees": 20, "max_depth": 6,
         "channels": 3}


def test_job_counts_by_hand():
    assert work.job_levels(SHAPE) == 120
    assert work.job_rowtrees(SHAPE) == 83_886_080
    one = work.job_min_seconds(SHAPE, PEAK, chips=1)
    assert one == pytest.approx(120 * 184_549_376 / 819e9)
    # four chips, a quarter of the rows each: a quarter of the time
    four = work.job_min_seconds(dict(SHAPE, rows=4 * SHAPE["rows"]), PEAK,
                                chips=4)
    assert four == pytest.approx(one)


def test_peaks_table_names_its_source():
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
