"""Seconds the process spent in XLA's compile entry points, persistent
cache loads among them, by the program's compile watch
(`runtime/backend.compile_watch_snapshot()["compile_s"]`). All of it is
set-up: a run that compiles inside its window fails its jobs."""


def read(ctx):
    watch = ctx.get("compile_watch")
    if watch is None:
        from h2o_kubernetes_tpu.runtime.backend import \
            compile_watch_snapshot

        watch = compile_watch_snapshot()
    return float(watch["compile_s"])
