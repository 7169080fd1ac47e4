"""Share of a K-class job's trained splits that send a SET of a
categorical's levels left, in percent, over every class tree: the
program's counter `h2o_train_splits_total{kind}` (added up in
`train.read_model`) over the warm-up job and the window's jobs, as
`set_split_share` reads it, where those jobs grow K class trees a round
on columns that split by sets (the newest `train` root says `classes`
and `set_features`). A program without the counter, or whose jobs here
are no such job, reports nothing."""

import set_split_share


def _root() -> dict:
    """The newest `train` root's attributes; {} where there is none."""
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import TRACER

        return TRACER.by_root("train")[-1]["spans"][0]
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return {}


def k_class_sets() -> dict | None:
    """The newest `train` root where it is a K-class job whose columns
    split by sets, else None."""
    root = _root()
    return root if root.get("classes") and root.get("set_features") \
        else None


def read(ctx):
    return set_split_share.read(ctx) if k_class_sets() else None
