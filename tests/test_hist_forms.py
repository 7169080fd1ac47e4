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


def test_shipped_form_is_the_packages_kernel():
    assert hist_forms.FORMS[hist_forms.SHIPPED] is \
        hist_forms.H._hist_pallas
    assert set(hist_forms.SHAPES) == {
        "higgs256", "forest64", "airline512", "mslr136"}


def test_refuses_to_run_without_a_tpu():
    with pytest.raises(SystemExit):
        hist_forms.main(["--shape", "higgs256"])
