"""Share of the by-row lookups of a node table in the boost scan that
read it by a select over its entries, in percent: the program's own
counter `h2o_train_node_lookups_total{form}` (added up once a job, in
`train.read_model`) — each level's descent and each tree's margin
update counts one, ``select`` where the table has at most the entries
the program's rule allows, ``gather`` past it — over the warm-up job
and the window's jobs, which train the same table. It follows the
cell's depth alone: a count that repeats exactly. A program without
the counter reports nothing."""


def read(ctx):
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

        ctr = REGISTRY.counter("h2o_train_node_lookups_total", label="form")
        select, gather = ctr.value("select"), ctr.value("gather")
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    return 100.0 * select / (select + gather) if select + gather \
        else None
