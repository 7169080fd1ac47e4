"""The interval arithmetic of `trace_reduce.py`, on cases by hand."""

import pytest

import trace_reduce as tr


def test_union_and_total():
    iv = [(0, 10, "a"), (5, 12, "b"), (20, 30, "c"), (30, 31, "d")]
    assert tr.union(iv) == [(0, 12), (20, 31)]
    assert tr.total(iv) == 23


def test_clip_keeps_names_and_drops_outside():
    iv = [(0, 10, "a"), (50, 60, "b")]
    assert tr.clip(iv, 5, 40) == [(5, 10, "a")]


def test_gaps_are_the_complement():
    iv = [(2, 4, "a"), (3, 6, "b"), (8, 9, "c")]
    assert tr.gaps(iv, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    busy = tr.total(tr.clip(iv, 0, 10))
    idle = sum(e - s for s, e in tr.gaps(iv, 0, 10))
    assert busy + idle == 10


def test_sum_by_name():
    iv = [(0, 2, "k"), (5, 6, "k"), (7, 9, "x")]
    assert tr.sum_by_name(iv) == {"k": 3, "x": 2}


def test_inside_takes_what_starts_in_an_outer():
    ops = [(1, 2, "a"), (5, 6, "b"), (11, 12, "c"), (19, 25, "d")]
    outer = [(0, 3, "m"), (10, 20, "m")]
    assert tr.inside(ops, outer) == [(1, 2, "a"), (11, 12, "c"),
                                     (19, 25, "d")]


def test_exposed_is_what_nothing_else_covers():
    ar = [(0, 10, "all-reduce"), (20, 30, "all-reduce")]
    rest = [(5, 8, "fusion"), (25, 40, "fusion")]
    # 10 - 3 hidden, 10 - 5 hidden
    assert tr.exposed(ar, rest) == 12


def test_span_at_names_the_innermost():
    spans = [(0, 100, "bench.window"), (10, 50, "bench.job"),
             (10, 20, "bench.from_arrays"), (20, 50, "bench.train")]
    assert tr.span_at(spans, 15) == "bench.from_arrays"
    assert tr.span_at(spans, 30) == "bench.train"
    assert tr.span_at(spans, 70) == "bench.window"
    assert tr.span_at(spans, 200) == "outside"


def test_window_needs_its_span():
    t = tr.Trace([], [(3, 9, "bench.window")])
    assert tr.window(t) == (3, 9)
    with pytest.raises(ValueError):
        tr.window(tr.Trace([], []))
