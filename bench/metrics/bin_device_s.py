"""Device seconds of binning a job: the operations of the fullest
device inside the module events of the programs that the program's own
table lists under "bin" (`runtime/telemetry.TRAIN_PROGRAMS`, the jitted
functions' names; a module event is named `jit_<name>(<hash>)`), per
`bench.job` span, the median over the window's jobs."""

import statistics

import trace_reduce as tr
from _common import fullest, job_spans


def bin_programs(ctx):
    given = ctx.get("train_programs")
    if given is None:
        try:
            from h2o_kubernetes_tpu.runtime import telemetry
        except ImportError:
            return None
        given = getattr(telemetry, "TRAIN_PROGRAMS", None)
    return given and {f"jit_{name}" for name in given["bin"]}


def read(ctx):
    names = bin_programs(ctx)
    if not names or not ctx["trace"].devices:
        return None
    dev = fullest(ctx)
    per_job = []
    for lo, hi, _ in job_spans(ctx):
        mods = [m for m in tr.clip(dev.modules, lo, hi)
                if m[2].split("(")[0] in names]
        ops = tr.inside(tr.clip(dev.ops, lo, hi), mods)
        per_job.append(tr.total(ops) / 1e9)
    if not per_job or not any(per_job):
        return None
    return statistics.median(per_job)
