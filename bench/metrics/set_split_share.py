"""Share of the process's trained splits that send a SET of a
categorical's levels left, in percent: the program's own counter
`h2o_train_splits_total{kind}` (added up in `train.read_model`), over
the warm-up job and the window's jobs, which train the same table. A
program without the counter (or without a job that used it) reports
nothing."""


def read(ctx):
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

        ctr = REGISTRY.counter("h2o_train_splits_total", label="kind")
        n_set = ctr.value("set")
        total = n_set + ctr.value("numeric")
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    return 100.0 * n_set / total if total else None
