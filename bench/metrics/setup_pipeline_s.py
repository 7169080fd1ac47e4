"""Seconds the warm-up job spent in jax's compile pipeline: `trace_ms
+ lower_ms + compile_ms` of its root spans (the `frame.from_arrays`
roots and the ring's first `train`, which ended before the window;
each second under the innermost stage, so the three add up, and a
root's fields hold its children's). Earlier lines say the three
apart, the cache loads inside the third, the process's whole pipeline
time beside it (what lies under no span of the warm-up), and the five
programs that took most of it. What `setup_train_s` less `train_s`
has over this is not the pipeline's: loading executables onto the
device, and the first run of each."""

import _host_spans as hs


def read(ctx):
    roots = hs.warmup_roots(ctx)
    if not roots or not any(k in r for r in roots for k in hs.STAGES):
        return None
    trace, lower, compile_ = (sum(r.get(k, 0.0) for r in roots) / 1e3
                              for k in hs.STAGES)
    load = sum(r.get("cache_load_ms", 0.0) for r in roots) / 1e3
    ctx["say"](
        f"warm-up job in the pipeline: trace {trace:.3f}s, lower "
        f"{lower:.3f}s, backend compile {compile_:.3f}s (of it cache "
        f"loads {load:.3f}s), {sum(r.get('traces', 0) for r in roots)} "
        "programs traced")
    watch = hs.compile_watch(ctx)
    if "by_program" in watch:
        def secs(rec):
            return rec["trace_s"] + rec["lower_s"] + rec["compile_s"]

        ctx["say"](
            f"the process in the pipeline: trace {watch['trace_s']:.3f}s, "
            f"lower {watch['lower_s']:.3f}s, backend compile "
            f"{watch['compile_s']:.3f}s (cache loads "
            f"{watch['cache_load_s']:.3f}s); most of it: " + "; ".join(
                f"{name} {secs(rec):.3f}s (trace {rec['trace_s']:.3f} "
                f"lower {rec['lower_s']:.3f} compile "
                f"{rec['compile_s']:.3f}, loads {rec['cache_load_s']:.3f})"
                for name, rec in sorted(
                    watch["by_program"].items(),
                    key=lambda kv: -secs(kv[1]))[:5]))
    return trace + lower + compile_
