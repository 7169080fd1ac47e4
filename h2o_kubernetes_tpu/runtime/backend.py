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
# watch is a pair of listeners feeding per-thread counters.  Listeners
# are registered once per process and are pure accounting — they can
# never raise into jax.

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PCACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_PCACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_watch_lock = threading.Lock()
_watch_installed = False
# global counters + per-thread breakdown
# {ident: [compiles, seconds, pcache_hits, pcache_misses]}
_watch = {"compiles": 0, "compile_s": 0.0,
          "pcache_hits": 0, "pcache_misses": 0}
_watch_threads: dict[int, list] = {}


def _per_thread() -> list:
    return _watch_threads.setdefault(threading.get_ident(),
                                     [0, 0.0, 0, 0])


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    with _watch_lock:
        _watch["compiles"] += 1
        _watch["compile_s"] += duration
        per = _per_thread()
        per[0] += 1
        per[1] += duration


def _on_compile_event(event: str, **kw) -> None:
    # the listener runs on the compiling thread, so per-thread cache
    # attribution is exact even with a concurrent compile-ahead stream
    if event == _PCACHE_HIT_EVENT:
        with _watch_lock:
            _watch["pcache_hits"] += 1
            _per_thread()[2] += 1
    elif event == _PCACHE_MISS_EVENT:
        with _watch_lock:
            _watch["pcache_misses"] += 1
            _per_thread()[3] += 1


def start_compile_watch() -> None:
    """Install the jax.monitoring listeners (idempotent, never raises).

    Counting starts at install; callers diff snapshots, so a late
    install only shortens history, never corrupts it."""
    global _watch_installed
    with _watch_lock:
        if _watch_installed:
            return
        _watch_installed = True
    try:
        # the compile watch registers with the fleet-telemetry
        # registry where it lives (lazy import: telemetry itself
        # lazily imports this module for the host fingerprint)
        from .telemetry import register_group

        register_group("compiles", compile_watch_snapshot)
    except Exception:   # noqa: BLE001 — accounting only, never fatal
        pass
    try:
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        monitoring.register_event_listener(_on_compile_event)
    except Exception:   # noqa: BLE001 — accounting only, never fatal
        pass


def compile_watch_snapshot(thread_ident: int | None = None) -> dict:
    """Cumulative compile counters; with ``thread_ident``, that
    thread's share under ``thread_compiles``/``thread_compile_s`` —
    diff two snapshots to attribute a code region's compile cost."""
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
        if thread_ident is not None:
            per = _watch_threads.get(thread_ident, [0, 0.0, 0, 0])
            out["thread_compiles"] = per[0]
            out["thread_compile_s"] = per[1]
            out["thread_pcache_hits"] = per[2]
            out["thread_pcache_misses"] = per[3]
    return out
