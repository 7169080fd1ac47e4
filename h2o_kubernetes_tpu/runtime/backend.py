"""Backend plumbing: where the persistent compile cache lives, the
TPU requirement of the on-chip scripts, and XLA compile accounting.

Nothing here chooses a platform on the caller's behalf: a process runs
on whatever JAX finds (``JAX_PLATFORMS`` or the default), and one that
was meant to own a chip fails when there is none (``require_tpu``).
"""

from __future__ import annotations

import os
import sys
import threading

# the module, not its names: telemetry loads this file for the host
# fingerprint while it is itself half imported
from . import telemetry


def host_features_fingerprint(cpuinfo_path: str = "/proc/cpuinfo") -> str:
    """Stable short hash of this host's CPU feature set (ISA flags).

    XLA:CPU AOT-compiles with the build host's features: a round-5
    bench caught a cache entry compiled with +amx-*/+avx512* loading on a
    host WITHOUT them ("could lead to execution errors such as
    SIGILL").  jax's persistent-cache key does not include host
    features, so the cache DIRECTORY must — a copied cache dir can then
    never serve a mismatched binary (the lookup simply misses).

    Order-insensitive over the flag set (kernel flag ordering is not
    stable across reboots); falls back to the platform tuple where
    /proc/cpuinfo is unavailable (macOS, containers without procfs)."""
    import hashlib

    feats = ""
    try:
        with open(cpuinfo_path) as f:
            for line in f:
                # x86 says "flags", arm64 says "Features"
                if line.lower().startswith(("flags", "features")):
                    feats = " ".join(sorted(set(
                        line.split(":", 1)[1].split())))
                    break
    except OSError:
        pass
    if not feats:
        import platform

        feats = f"{platform.machine()}|{platform.processor()}"
    return hashlib.sha1(feats.encode()).hexdigest()[:10]


def repo_cache_dir() -> str:
    """THE default compile-cache directory: ``tools/_jax_cache/`` of
    this checkout, one sub-directory per host CPU feature set. The
    path is part of what makes a cache hit (a directory that moves
    never hits), so there is exactly one; the ``hostfp-`` level keeps
    an XLA:CPU AOT entry from loading on a host without its ISA
    extensions (see host_features_fingerprint) and is deterministic
    per host, so two runs in one checkout share it."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "tools", "_jax_cache",
                        f"hostfp-{host_features_fingerprint()}")


def enable_persistent_compile_cache(
        min_compile_secs: float | None = None) -> None:
    """Turn on jax's persistent compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache lives there
    and nothing here names another directory; otherwise it goes to
    ``repo_cache_dir()``. The disk cache keys on hardware + HLO, so a
    second process (a sibling pod, the next run in the same checkout)
    reads what the first compiled.

    ``min_compile_secs`` (or ``H2O_TPU_PCACHE_MIN_SECS``) overrides
    the 0.5 s persistence threshold. Serving pods pass 0.0: the
    byte-budgeted scorer cache's evict→promote contract ("an eviction
    costs a pcache hit, never a cold compile") needs even sub-second
    tenant-model compiles persisted, or a promotion would silently
    recompile from scratch.

    Never IMPORTS jax (callers run this before choosing a platform):
    env vars cover a not-yet-imported jax, and when jax IS already
    imported (its config no longer reads env) the config is updated
    through sys.modules, which touches no backend."""
    if min_compile_secs is None:
        raw = os.environ.get("H2O_TPU_PCACHE_MIN_SECS")
        if raw:
            try:
                min_compile_secs = float(raw)
            except ValueError:
                min_compile_secs = None
    j = sys.modules.get("jax")
    if min_compile_secs is not None:
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = \
            str(min_compile_secs)
        if j is not None:
            j.config.update("jax_persistent_cache_min_compile_time_secs",
                            float(min_compile_secs))
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    cache_dir = repo_cache_dir()
    os.makedirs(cache_dir, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    # 0.5s threshold: catches every real XLA compile (the cheapest
    # boost-step compile is ~1s) while keeping the trivial scalar
    # dispatches from growing the dir without bound
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    if j is not None:
        j.config.update("jax_compilation_cache_dir", cache_dir)
        # post-setdefault value: a user-exported threshold wins
        j.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(os.environ[
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"]))


def require_tpu(what: str) -> None:
    """Fail unless this process runs on a TPU — for the scripts whose
    numbers only mean something on the chip (bench/run.py, the kernel
    gate, chip_smoke.py). There is no CPU fallback: a run that wanted
    a chip and found none is an error, not a slower run."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"{what}: needs a TPU, but JAX found platform "
            f"'{platform}' — run it on the chip (tests run on CPU "
            "through pytest; see README 'Running')")


# ---------------------------------------------------------------------------
# XLA compile accounting (jax.monitoring listeners)
# ---------------------------------------------------------------------------
#
# The pipelined AutoML scheduler (runtime/scheduler.py) needs to know
# how much XLA compilation ran on WHICH thread: compiles on the device
# stream are critical-path compile-wait, compiles on the compile-ahead
# stream are overlapped cache fills.  jax.monitoring emits exactly the
# events needed ('/jax/core/compile/backend_compile_duration' per
# compile request, '/jax/compilation_cache/cache_hits|misses' for the
# persistent cache) without the stderr spam of jax_log_compiles, so the
# watch is a few listeners feeding per-thread counters.  Listeners
# are registered once per process and are pure accounting — they can
# never raise into jax.
#
# Beside the backend compile jax times the two stages before it, with
# the function's name on each event: tracing to a jaxpr and lowering
# the jaxpr to a module. A stage can hold others (a traced function
# calls jitted ones; an eager operation met while tracing is traced,
# lowered and compiled on the spot), so every stage is also announced
# when it starts (a scalar event), and a thread's open stages are kept
# as a stack: a stage's own seconds are its duration less the stages
# inside it, and they are paid by the program at the bottom of the
# stack. `trace_s + lower_s + compile_s` is then the time a thread
# spent in the pipeline, each second counted once; the same seconds go
# to the innermost open span of the thread (telemetry.credit_open_span:
# the listeners run on the tracing thread, in its context).

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_STAGES = {_TRACE_EVENT: "trace", _LOWER_EVENT: "lower",
           _BACKEND_COMPILE_EVENT: "compile"}
# a persistent-cache load's seconds: emitted inside the backend-compile
# stage that asked for it, so `cache_load_s` lies inside `compile_s`
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_PCACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_PCACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# names `by_program` keeps apart; the rest go under "other"
MAX_PROGRAMS_WATCHED = 64

_watch_lock = threading.Lock()
_watch_installed = False
# global counters + per-thread breakdown
# {ident: [compiles, seconds, pcache_hits, pcache_misses]}
_watch = {"compiles": 0, "compile_s": 0.0,
          "pcache_hits": 0, "pcache_misses": 0,
          "traces": 0, "trace_s": 0.0, "lower_s": 0.0,
          "cache_load_s": 0.0}
_watch_threads: dict[int, list] = {}
# {program: the counters of `_watch`}, by the jitted function's name
_by_program: dict[str, dict] = {}
# .stack: this thread's open stages, outermost first: [kind, program,
# seconds of the stages inside it, `_trace_misses()` at its start]
_open_stages = threading.local()
# jax's cache of traced functions (set with the listeners)
_trace_cache = None


def _per_thread() -> list:
    return _watch_threads.setdefault(threading.get_ident(),
                                     [0, 0.0, 0, 0])


def _stack() -> list:
    try:
        return _open_stages.stack
    except AttributeError:
        stack = _open_stages.stack = []
        return stack


def _program(name: str) -> dict:
    """``name``'s counters (the caller holds the lock)."""
    if name not in _by_program and \
            len(_by_program) >= MAX_PROGRAMS_WATCHED:
        name = "other"
    rec = _by_program.get(name)
    if rec is None:
        rec = _by_program[name] = {k: type(v)() for k, v in _watch.items()}
    return rec


def _program_name(fun_name) -> str:
    """A trace event names the function (`_boost_jit`), the two later
    stages its module (`jit(_boost_jit)`, `jit__boost_jit`): one name
    for the three."""
    name = str(fun_name)
    for prefix, suffix in (("jit(", ")"), ("jit_", "")):
        if name.startswith(prefix) and name.endswith(suffix):
            return name[len(prefix):len(name) - len(suffix)]
    return name


def _trace_misses() -> int | None:
    """jax 0.9 announces a `trace` stage around the lookup in its cache
    of traced functions, hit or miss (a call that leaves jit's fast
    path looks there again), and only a miss traces: the cache's own
    count of misses tells the two apart. None where it cannot be read;
    every such stage then counts as a trace."""
    try:
        return _trace_cache.cache_info().misses
    except Exception:   # noqa: BLE001 — another jax: accounting only
        return None


def _on_stage_start(event: str, value, fun_name=None, **kw) -> None:
    kind = _STAGES.get(event)
    if kind is not None:
        _stack().append([kind, _program_name(fun_name), 0.0,
                         _trace_misses() if kind == "trace" else None])


def _on_compile_duration(event: str, duration: float, fun_name=None,
                         **kw) -> None:
    stack = _stack()
    if event == _CACHE_LOAD_EVENT:
        with _watch_lock:
            _watch["cache_load_s"] += duration
            if stack:
                _program(stack[0][1])["cache_load_s"] += duration
        telemetry.credit_open_span(cache_load_ms=duration * 1e3)
        return
    kind = _STAGES.get(event)
    if kind is None:
        return
    inside = 0.0
    # (a watch installed inside a stage never saw that stage start)
    if stack and stack[-1][0] == kind:
        _, _, inside, misses = stack.pop()
        if misses is not None and _trace_misses() == misses:
            return                      # found in the cache: no trace
    if stack:
        stack[-1][2] += duration
    own = max(duration - inside, 0.0)
    program = stack[0][1] if stack else _program_name(fun_name)
    credit = {kind + "_ms": own * 1e3}
    count = "compiles" if kind == "compile" else None
    if kind == "trace" and not any(s[0] == "trace" for s in stack):
        # (a function traced while another is, a jitted callee, is no
        # program of its own)
        count, credit["traces"] = "traces", 1
    with _watch_lock:
        for rec in (_watch, _program(program)):
            rec[kind + "_s"] += own
            if count:
                rec[count] += 1
        if kind == "compile":
            per = _per_thread()
            per[0] += 1
            per[1] += own
    telemetry.credit_open_span(programs=(program,), **credit)


def _on_compile_event(event: str, **kw) -> None:
    # the listener runs on the compiling thread, so per-thread cache
    # attribution is exact even with a concurrent compile-ahead stream
    if event == _PCACHE_HIT_EVENT:
        key, slot = "pcache_hits", 2
    elif event == _PCACHE_MISS_EVENT:
        key, slot = "pcache_misses", 3
    else:
        return
    stack = _stack()
    with _watch_lock:
        _watch[key] += 1
        _per_thread()[slot] += 1
        if stack:           # inside the backend-compile stage it serves
            _program(stack[0][1])[key] += 1


def _compiles_group() -> dict:
    """The `compiles` stat group: the snapshot with `by_program` as a
    list (largest first), which `/3/Stats` shows and the exposition's
    flattener passes over — a program's name is no label of
    ALLOWED_LABELS, and 65 names x 8 counters are no series to add."""
    snap = compile_watch_snapshot()
    snap["by_program"] = sorted(
        (dict(rec, program=name)
         for name, rec in snap["by_program"].items()),
        key=lambda r: -(r["trace_s"] + r["lower_s"] + r["compile_s"]))
    return snap


def start_compile_watch() -> None:
    """Install the jax.monitoring listeners (idempotent, never raises).

    Counting starts at install; callers diff snapshots, so a late
    install only shortens history, never corrupts it."""
    global _watch_installed
    with _watch_lock:
        if _watch_installed:
            return
        _watch_installed = True
    telemetry.register_group("compiles", _compiles_group)
    global _trace_cache
    try:
        from jax import monitoring
        from jax._src.interpreters.partial_eval import trace_to_jaxpr

        _trace_cache = trace_to_jaxpr
        monitoring.register_scalar_listener(_on_stage_start)
        monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        monitoring.register_event_listener(_on_compile_event)
    except Exception:   # noqa: BLE001 — accounting only, never fatal
        pass


def compile_watch_snapshot(thread_ident: int | None = None) -> dict:
    """Cumulative counters of the compile pipeline: `traces` /
    `trace_s` / `lower_s` / `compiles` / `compile_s` (each second
    under the innermost stage; `cache_load_s` lies inside
    `compile_s`), `pcache_*`, and `by_program`: the same by the jitted
    function's name. With ``thread_ident``, that thread's share under
    ``thread_compiles``/``thread_compile_s`` — diff two snapshots to
    attribute a code region's compile cost."""
    with _watch_lock:
        if len(_watch_threads) > 64:
            # prune dead threads' entries: every AutoML run spawns
            # fresh scheduler workers, and a long-lived REST server
            # would otherwise grow this dict (and risk ident-reuse
            # mixing a dead stream's counters into a new thread's)
            # without bound. Callers diff snapshots over short windows,
            # so dropping finished threads' history is safe.
            live = {t.ident for t in threading.enumerate()}
            live.add(thread_ident)
            for ident in [i for i in _watch_threads if i not in live]:
                del _watch_threads[ident]
        out = dict(_watch)
        out["by_program"] = {k: dict(v) for k, v in _by_program.items()}
        if thread_ident is not None:
            per = _watch_threads.get(thread_ident, [0, 0.0, 0, 0])
            out["thread_compiles"] = per[0]
            out["thread_compile_s"] = per[1]
            out["thread_pcache_hits"] = per[2]
            out["thread_pcache_misses"] = per[3]
    return out
