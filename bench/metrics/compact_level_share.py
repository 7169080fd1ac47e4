"""Share of the histogram levels past one hi block that ran over rows
ordered by node block, in percent: the program's own counter
`h2o_train_hist_levels_total{form}` (added up once a job, in
`train.read_model`) — each such level of each tree counts one,
``compacted`` where the tree ordered its rows by node block and each
block ran over its own row tiles, ``blocked`` where every row tile met
every block — over the warm-up job and the window's jobs, which train
the same table. It follows the cell's shapes alone: a count that
repeats exactly. A program without the counter, or whose trees have no
level past one hi block, reports nothing."""


def read(ctx):
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

        ctr = REGISTRY.counter("h2o_train_hist_levels_total", label="form")
        compacted, blocked = ctr.value("compacted"), ctr.value("blocked")
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    return 100.0 * compacted / (compacted + blocked) \
        if compacted + blocked else None
