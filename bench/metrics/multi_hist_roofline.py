"""The histogram kernels' share of their roofline in a K-class job, on
the fullest device: the least seconds one chip could take for the
histogram levels of the traced jobs WITH THE BIN CODES READ ONCE FOR
THE K TREES OF A ROUND, over the kernels' device seconds inside
`_boost_multi_jit`'s module events in those jobs.

At one level of one round every row's F bin codes (uint8) are read
once, its gradient, hessian and weight (float32) and its node id
(int32) once a class tree, and each of the F codes takes one add into
each histogram channel of each of the K trees:

    bytes = rows x (F + 16 K)        adds = rows x F x C x K

`hist_kernel_roofline` and `train_step_mfu` count a level a TREE
(`work.level_bytes`: rows x (F + 16)), so K times the codes: up to
(K F + 16 K) / (F + 16 K) as high as this where bytes bind."""

import trace_reduce as tr
import work
from _common import fullest, job_spans
from hist_kernel_share import kernel_ops
from multi_rest_s import only_multi


def round_level_bytes(rows: int, features: int, classes: int) -> int:
    return rows * (features + work.ROW_STATE_BYTES * classes)


def round_level_adds(rows: int, features: int, channels: int,
                     classes: int) -> int:
    return rows * features * channels * classes


def round_level_min_seconds(rows: int, features: int, channels: int,
                            classes: int, peak: dict) -> tuple[float, str]:
    """(least seconds one chip could take for one level of one round's
    K trees, the bound that sets it: "bytes" or "adds")."""
    by = round_level_bytes(rows, features, classes) \
        / peak["hbm_bytes_per_s"]
    ad = round_level_adds(rows, features, channels, classes) \
        / peak["bf16_flops_per_s"]
    return (by, "bytes") if by >= ad else (ad, "adds")


def read(ctx):
    jobs = job_spans(ctx)
    K = _classes()
    if not jobs or not K or not only_multi(ctx):
        return None
    ks = tr.inside(kernel_ops(ctx, fullest(ctx)), jobs)
    if not ks:
        return None
    sh = ctx["shape"]
    per_level, bound = round_level_min_seconds(
        sh["rows"] // ctx["chips"], sh["features"], sh["channels"], K,
        ctx["peak"])
    ctx["say"](f"multi_hist_roofline is bound by {bound}: "
               f"{per_level * 1e3:.4f} ms a level of {K} class trees")
    levels = work.job_levels(sh) // K            # a round's, not a tree's
    return 100.0 * len(jobs) * levels * per_level / (tr.total(ks) / 1e9)


def _classes():
    """K of the newest K-class job, from its `train` root; None where
    the program's spans do not say."""
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER

        roots = TRACER.by_root("train")
        return int(roots[-1]["spans"][0].get("classes") or 0) or None
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
