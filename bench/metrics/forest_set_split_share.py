"""Share of a forest's trained splits that send a SET of a categorical's
levels left, in percent: the program's counter
`h2o_train_splits_total{kind}` (added up in `train.read_model`) over
the warm-up job and the window's jobs, as `set_split_share` reads it,
where those jobs are forests (the newest `train` root's estimator is
`DRF`). A program without the counter, or whose jobs here are no
forest, reports nothing."""

import set_split_share


def read(ctx):
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER

        root = TRACER.by_root("train")[-1]["spans"][0]
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    if root.get("estimator") != "DRF":
        return None
    return set_split_share.read(ctx)
