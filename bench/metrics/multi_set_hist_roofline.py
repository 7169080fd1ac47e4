"""The histogram kernels' share of their roofline in a K-class job
whose columns split by sets, on the fullest device: `multi_hist_roofline`
with the bin codes counted at the width the matrix stores them, one
byte a code up to 256 bins a feature and two past it (the matrix of an
enum job holds a bin a level: `binning.matrix_bins`). At one level of
one round every row's F codes are read once, its gradient, hessian,
weight and node id once a class tree, and each code takes one add into
each channel of each of the K trees:

    bytes = rows x (F x code bytes + 16 K)     adds = rows x F x C x K

over the kernels' device seconds inside `_boost_multi_jit`'s module
events in the window's jobs. Reports nothing where the jobs are not
K-class jobs with sets."""

import trace_reduce as tr
import work
from _common import fullest, job_spans
from hist_kernel_share import kernel_ops
from multi_hist_roofline import round_level_adds
from multi_rest_s import only_multi
from multi_set_split_share import k_class_sets


def code_bytes(bins: int) -> int:
    return 1 if bins <= 256 else 2


def round_level_bytes(rows: int, features: int, classes: int,
                      bins: int) -> int:
    return rows * (features * code_bytes(bins)
                   + work.ROW_STATE_BYTES * classes)


def round_level_min_seconds(rows: int, features: int, channels: int,
                            classes: int, bins: int,
                            peak: dict) -> tuple[float, str]:
    """(least seconds one chip could take for one level of one round's
    K trees, the bound that sets it: "bytes" or "adds")."""
    by = round_level_bytes(rows, features, classes, bins) \
        / peak["hbm_bytes_per_s"]
    ad = round_level_adds(rows, features, channels, classes) \
        / peak["bf16_flops_per_s"]
    return (by, "bytes") if by >= ad else (ad, "adds")


def read(ctx):
    root = k_class_sets()
    jobs = job_spans(ctx)
    if not root or not root.get("bins") or not jobs or not only_multi(ctx):
        return None
    ks = tr.inside(kernel_ops(ctx, fullest(ctx)), jobs)
    if not ks:
        return None
    K, sh = int(root["classes"]), ctx["shape"]
    per_level, bound = round_level_min_seconds(
        sh["rows"] // ctx["chips"], sh["features"], sh["channels"], K,
        int(root["bins"]), ctx["peak"])
    ctx["say"](f"multi_set_hist_roofline is bound by {bound}: "
               f"{per_level * 1e3:.4f} ms a level of {K} class trees")
    levels = work.job_levels(sh) // K            # a round's, not a tree's
    return 100.0 * len(jobs) * levels * per_level / (tr.total(ks) / 1e9)
