"""`tools/hist_forms.py` on the CPU: the three forms of the histogram
kernel it times on the chip (interpret mode here) against
`_hist_segment`, and the shipped form against the one it replaced,
bitwise — so that the tool the next kernel PR reruns still runs."""

import pytest

from tools import hist_forms

# (rows, F, C, n_bins, code dtype), nodes
_CASES = {
    "root_256": ((1300, 5, 3, 256, "uint8"), 1),
    "eight_nodes_256_wide_tile": ((8300, 3, 3, 256, "uint8"), 8),
    "sixteen_nodes_256": ((1100, 3, 3, 256, "uint8"), 16),
    "forest_64_bins": ((2100, 4, 2, 64, "uint8"), 32),
    "airline_512_bins": ((1200, 3, 3, 512, "uint16"), 2),
    "wide_136_columns": ((600, 136, 3, 256, "uint8"), 4),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_forms_match_segment_and_the_shipped_kernel(case):
    shape, n_nodes = _CASES[case]
    lines = {ln["form"]: ln for ln in hist_forms.measure(
        case, shape, n_nodes, ("a", "b", "c"), calls=1, seed=35)}
    assert set(lines) == {"a", "b", "c"}
    for form, ln in lines.items():
        assert "error" not in ln, ln
        assert ln["rel_err_segment"] < 1e-5, ln
    # (b) is (a) with the lo one-hot held transposed: the same sums
    assert lines["a"]["bitwise_shipped"]
    assert lines["c"]["rel_diff_shipped"] < 1e-5


# the class batch's forms (ISSUE 39): (rows, F, C, n_bins, dtype), K, nodes
_CLASS_CASES = {
    "root_7_classes": ((1300, 5, 3, 256, "uint8"), 7, 1),
    "sixteen_nodes_wide_tile": ((8300, 3, 3, 256, "uint8"), 3, 16),
    "forest_64_bins_a_class_a_call": ((2100, 4, 2, 64, "uint8"), 3, 4),
}


@pytest.mark.parametrize("case", sorted(_CLASS_CASES))
def test_class_forms_match_segment_and_the_shipped_rule(case):
    shape, K, n_nodes = _CLASS_CASES[case]
    lines = {ln["form"]: ln for ln in hist_forms.measure(
        case, shape, n_nodes, tuple(hist_forms.CLASS_FORMS), calls=1,
        seed=39, classes=K)}
    assert set(lines) == set(hist_forms.CLASS_FORMS)
    for form, ln in lines.items():
        assert "error" not in ln and "timeout" not in ln, ln
        assert ln["classes"] == K and ln["rel_err_segment"] < 1e-5, ln
    # a class a grid step, a class a call: the same products in the
    # same order as (B) wherever the K stacked classes keep the row
    # tile one class alone would take
    H = hist_forms.H
    ht = H._hi_blocks(n_nodes * shape[3])[1]
    same_tile = H._fact_row_tile(K * ht, shape[0]) == \
        H._fact_row_tile(ht, shape[0])
    assert same_tile == (case != "sixteen_nodes_wide_tile")
    for form in ("A", "map", "fold"):
        assert lines[form]["rel_diff_shipped"] < 1e-5, lines[form]
        if same_tile and form != "fold":
            assert lines[form]["bitwise_shipped"], lines[form]


# levels past one hi block over rows ordered by block:
# (rows, F, C, n_bins, dtype), nodes
_COMPACT_CASES = {
    "forest_512_bins_4_blocks": ((2500, 3, 2, 512, "uint16"), 256),
    "airline_512_bins_2_blocks": ((2100, 2, 3, 512, "uint16"), 128),
    "forest_64_bins_2_blocks": ((3000, 4, 2, 64, "uint8"), 1024),
}


@pytest.mark.parametrize("case", sorted(_COMPACT_CASES))
def test_compact_forms_match_segment_and_each_other(case):
    shape, n_nodes = _COMPACT_CASES[case]
    lines = {ln["form"]: ln for ln in hist_forms.measure(
        case, shape, n_nodes, tuple(hist_forms.COMPACT_FORMS), calls=1,
        seed=42, compact=True)}
    assert set(lines) == {"compacted", "blocked"}
    for ln in lines.values():
        assert "error" not in ln, ln
        assert ln["rel_err_segment"] < 1e-5, ln
    # a tile a block skips holds none of its rows: the same sums
    assert lines["blocked"]["bitwise_shipped"], lines["blocked"]


@pytest.mark.parametrize("shape", [(5000, 8, 3, "uint16", 4),
                                   (4000, 28, 2, "uint8", 6)])
def test_order_pieces_run_and_agree(shape):
    """The ordering's pieces at the two code widths: the packed gather
    moves the same rows as the gathers apart, and a sort puts the leaf
    back where the scatter does."""
    lines = {ln["form"]: ln for ln in hist_forms.measure_order(
        "small", shape, calls=1, seed=3)}
    assert set(lines) == {"order", "sort", "gather", "packed", "columns",
                          "carried", "back", "back_sort"}
    for ln in lines.values():
        assert "error" not in ln and ln["ns_a_row"] > 0, ln
    for form in ("packed", "columns", "carried"):
        assert lines[form]["bitwise_gather"], lines[form]
    assert lines["back_sort"]["bitwise_back"]


def test_custom_shape_states_rows_columns_and_optionally_bins_channels():
    assert hist_forms._custom_shape("581632x54", 7)[:5] == (
        581632, 54, 3, 256, "uint8")
    assert hist_forms._custom_shape("581632x54x64x2", 7)[:5] == (
        581632, 54, 2, 64, "uint8")
    assert hist_forms._custom_shape("1000X8x128", None)[:5] == (
        1000, 8, 3, 128, "uint8")


def test_a_form_past_its_limit_says_timeout(monkeypatch):
    import time

    monkeypatch.setitem(hist_forms.CLASS_FORMS, "map",
                        lambda *a, **kw: time.sleep(5))
    (line,) = hist_forms.measure(
        "slow", (256, 2, 3, 256, "uint8"), 1, ("map",), calls=1, seed=1,
        segment=False, classes=2, limit=1)
    assert line["timeout"] == "over 1 s" and "min_s" not in line


def test_shipped_form_is_the_packages_kernel():
    assert hist_forms.FORMS[hist_forms.SHIPPED] is \
        hist_forms.H._hist_pallas
    assert hist_forms.CLASS_FORMS[hist_forms.CLASS_SHIPPED] is \
        hist_forms.H._hist_pallas
    assert set(hist_forms.SHAPES) == {
        "higgs256", "forest64", "airline512", "mslr136"}
    assert hist_forms.COMPACT_FORMS["blocked"] is hist_forms.H._hist_pallas
    assert set(hist_forms.COMPACT_SHAPES) == set(hist_forms.ORDER_SHAPES) \
        == {"forest512", "airline512", "forest64"}


def test_refuses_to_run_without_a_tpu():
    with pytest.raises(SystemExit):
        hist_forms.main(["--shape", "higgs256"])
