"""Share of the class trees trained that were grown in one batch, in
percent: the program's own counter `h2o_train_class_trees_total{kind}`
(added up once a job, in `train.read_model`) — ``batched`` counts the
class trees (rounds x K) of the jobs whose rounds grew their K trees
under one `vmap`, a level's histograms in one kernel call with the bin
codes read once for the K; ``mapped`` those grown a class at a time
under `lax.map`, past the program's histogram budget — over the warm-up
job and the window's jobs, which train the same table. 100 while the
class batch engages, 0 on the fallback: a count that repeats exactly.
A program without the counter (or without a K-class job) reports
nothing."""


def read(ctx):
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

        ctr = REGISTRY.counter("h2o_train_class_trees_total", label="kind")
        batched, mapped = ctr.value("batched"), ctr.value("mapped")
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    return 100.0 * batched / (batched + mapped) if batched + mapped \
        else None
