"""Share of the pair slots the query layout computed that are pairs of
a query, in percent: the program's own counter
`h2o_train_rank_pairs_total{kind}` (added up once a job, in
`train.read_model`) — ``real`` is the sum of n_q^2 over a job's queries
and rounds, ``slots`` what the layout's size classes computed for them
— over the warm-up job and the window's jobs, which train the same
table. A layout padded to the longest query reads about 1% on
MSLR-WEB30K's sizes. A program without the counter (or without a
ranking job) reports nothing."""


def read(ctx):
    try:
        from h2o_kubernetes_tpu.runtime.telemetry import REGISTRY

        ctr = REGISTRY.counter("h2o_train_rank_pairs_total", label="kind")
        real, slots = ctr.value("real"), ctr.value("slots")
    except Exception:  # noqa: BLE001 — a reader never fails its run
        return None
    return 100.0 * real / slots if slots else None
