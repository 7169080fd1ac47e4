"""Pipelined executor — the three resource streams of an AutoML run.

A serial AutoML loop interleaves three *independent* resources on one
thread: device compute (model training), XLA compilation (every new
(program, shape) pair), and host bookkeeping (metric extraction,
leaderboard insertion, resume-manifest writes).  This module gives each
its own stream so they overlap — the same dispatch-pipelining lesson
the GBDT-accelerator literature applies one level down (PAPERS.md:
arXiv:1806.11248 overlaps host staging with device kernels,
arXiv:2005.09148 hides transfers behind compute; `models/tree/ooc.py`
already does it per chunk, this does it per MODEL):

- **device stream** — the caller's thread, holding the device *token*:
  only the token holder dispatches device computations, so device work
  stays strictly ordered (and the XLA:CPU test mesh never sees two
  concurrent collective programs, the known rendezvous-starvation
  shape — tests/conftest.py).
- **compile stream** (`CompileStream`) — a worker that AOT
  traces/lowers/compiles executables the device stream will need next
  (shapes are known from the plan + frame schema; see
  `GBM.compile_ahead_lowerings`).  Compiled binaries land in the
  persistent XLA cache (runtime/backend.py), so the device stream's
  later dispatch is a cache *hit*: on a cold run the stream is a cache
  fill, on a warm one a no-op.
- **host stream** (`HostStream`) — a worker applying completion
  callbacks (leaderboard insertion, `_save_step` manifest writes,
  logging) strictly in *submission-sequence order*, whatever order
  they become runnable: the pipelined leaderboard and resume manifest
  must be identical to the sequential run's (insertion order by plan
  index, not completion order).

Overlap accounting: `PipelinedExecutor.stats()` reports device-busy /
compile-ahead / host-busy seconds plus the compile-watch counters
(runtime/backend.py), so a bench can state exactly how much work left
the critical path.  On a host with one core the streams time-slice and
the wall gain is bounded by scheduler overhead (~0); the design targets
multi-core hosts with a chip, where the device stream is a genuine
second resource.

Knobs (read at use time, documented in config.py):

- ``H2O_TPU_AUTOML_PIPELINE``       1 (on) | 0 — the kill switch: 0
  restores the serial AutoML path bit-for-bit.
- ``H2O_TPU_AUTOML_COMPILE_AHEAD``  plan entries pre-lowered ahead of
  the training cursor (default 1; 0 disables the compile stream).
- ``H2O_TPU_AUTOML_QUEUE_DEPTH``    bound on each stream's pending
  queue (default 4): backpressure, so completed-but-unapplied models
  and stale compile requests cannot accumulate without bound.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Any, Callable

from .backend import compile_watch_snapshot, start_compile_watch

__all__ = ["pipeline_enabled", "compile_ahead_depth", "queue_depth",
           "HostStream", "CompileStream", "PipelinedExecutor"]


def pipeline_enabled() -> bool:
    """H2O_TPU_AUTOML_PIPELINE != "0" — one switch for the AutoML
    executor AND the CV fold pipeline (models/cv.py), so the kill
    switch restores the whole serial path at once."""
    return os.environ.get("H2O_TPU_AUTOML_PIPELINE", "1") != "0"


def _int_env(name: str, default: int, lo: int) -> int:
    try:
        return max(lo, int(os.environ.get(name, str(default))))
    except ValueError:
        return default


def compile_ahead_depth() -> int:
    return _int_env("H2O_TPU_AUTOML_COMPILE_AHEAD", 1, 0)


def persistent_cache_enabled() -> bool:
    """Compile-ahead is only run with the persistent XLA cache on. On
    jax 0.9.0 the same process gets an AOT ``lower().compile()``
    executable back in memory (jit's lowering cache hands the dispatch
    the same computation object), and the disk cache is what carries
    it to another process or past a ``jax.clear_caches()``. Earlier
    jaxlibs shared nothing in memory, so without a cache dir the
    stream compiled every program twice; the executor still keys on
    the dir (h2o.init() sets it in every real process —
    runtime/backend.enable_persistent_compile_cache)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return True
    try:
        import sys

        j = sys.modules.get("jax")
        return bool(j is not None and
                    j.config.jax_compilation_cache_dir)
    except Exception:   # noqa: BLE001
        return False


def queue_depth() -> int:
    return _int_env("H2O_TPU_AUTOML_QUEUE_DEPTH", 4, 1)


class HostStream:
    """Single worker applying callables strictly in sequence order.

    ``submit(seq, fn)`` may arrive in any order; the worker holds a
    task back until every lower sequence number has been applied or
    explicitly ``skip()``-ed (a step that failed or fell out of budget
    produces no completion).  Task exceptions are captured — not
    raised on the worker — and surfaced via ``pop_errors``/``drain``,
    mirroring the serial loop where a failed step never kills the run.
    """

    def __init__(self, name: str = "h2o-automl-host",
                 max_pending: int | None = None):
        self._cond = threading.Condition()
        self._tasks: dict[int, tuple[Callable[[], None], str]] = {}
        self._skipped: set[int] = set()
        self._next = 0
        self._inflight = False
        self._stopped = False
        self._errors: list[tuple[int, str, BaseException]] = []
        self._max_pending = max_pending or queue_depth()
        self.stats = {"applied": 0, "skipped": 0, "busy_s": 0.0,
                      "max_pending": 0}
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def submit(self, seq: int, fn: Callable[[], None],
               label: str = "") -> None:
        """Queue ``fn`` for in-order application; blocks (backpressure)
        while the pending queue is full AND the worker has runnable
        work — a starving worker (held back by a missing lower seq)
        admits immediately, otherwise blocking the very submit that
        fills the gap would deadlock the producer against its own
        backlog."""
        with self._cond:
            if self._stopped:
                raise RuntimeError("host stream is stopped")
            if seq < self._next or seq in self._tasks \
                    or seq in self._skipped:
                raise ValueError(f"seq {seq} already submitted/applied")
            while len(self._tasks) >= self._max_pending \
                    and not self._stopped \
                    and (self._inflight or self._next in self._tasks
                         or self._next in self._skipped):
                self._cond.wait(timeout=0.5)
            if self._stopped:
                # stop() raced the backpressure wait: refuse loudly —
                # appending now would silently drop the task (the
                # worker is gone) and misreport a wedge at drain
                raise RuntimeError("host stream is stopped")
            self._tasks[seq] = (fn, label)
            self.stats["max_pending"] = max(self.stats["max_pending"],
                                            len(self._tasks))
            self._cond.notify_all()

    def skip(self, seq: int) -> None:
        """Mark a sequence number that will never be submitted."""
        with self._cond:
            if seq < self._next or seq in self._tasks:
                return
            self._skipped.add(seq)
            self._cond.notify_all()

    def pop_errors(self) -> list[tuple[int, str, BaseException]]:
        with self._cond:
            out, self._errors = self._errors, []
            return out

    def pending(self) -> list[int]:
        with self._cond:
            return sorted(self._tasks)

    def drain(self, timeout: float | None = None
              ) -> list[tuple[int, str, BaseException]]:
        """Block until everything submitted/skipped has been applied;
        returns the captured task errors.  Raises TimeoutError naming
        the wedge (a submit gap with no skip()) instead of hanging."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._tasks or self._skipped or self._inflight:
                remaining = None if deadline is None else \
                    deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise TimeoutError(
                        f"host stream wedged at seq {self._next}: "
                        f"pending={sorted(self._tasks)} "
                        f"skipped={sorted(self._skipped)}")
                self._cond.wait(timeout=0.5 if remaining is None
                                else min(0.5, remaining))
        return self.pop_errors()

    def stop(self, timeout: float = 30.0) -> bool:
        """Drain then stop the worker; True when the thread exited.
        A wedged drain is reported by the bool (and by an explicit
        drain() call beforehand), never raised — stop() runs on error
        paths where a fresh TimeoutError would mask the real failure."""
        try:
            self.drain(timeout=timeout)
        except TimeoutError:
            pass
        finally:
            with self._cond:
                self._stopped = True
                self._cond.notify_all()
        self._thread.join(timeout=timeout)
        return not self._thread.is_alive()

    def _run(self) -> None:
        while True:
            with self._cond:
                task = None
                while task is None:
                    while self._next in self._skipped:
                        self._skipped.discard(self._next)
                        self._next += 1
                        self.stats["skipped"] += 1
                        self._cond.notify_all()
                    if self._next in self._tasks:
                        task = self._tasks.pop(self._next)
                        self._inflight = True
                        self._cond.notify_all()
                        break
                    if self._stopped:
                        return
                    self._cond.wait(timeout=0.5)
            fn, label = task
            seq = self._next
            t0 = time.monotonic()
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — surfaced at drain
                with self._cond:
                    self._errors.append((seq, label, e))
            finally:
                with self._cond:
                    self.stats["busy_s"] += time.monotonic() - t0
                    self.stats["applied"] += 1
                    self._next = seq + 1
                    self._inflight = False
                    self._cond.notify_all()


class CompileStream:
    """Daemon worker that AOT-compiles executables ahead of use.

    ``submit(key, builder)`` enqueues a request (deduped by ``key``);
    the worker calls ``builder()`` — which returns a list of zero-arg
    lowering thunks — and runs each thunk.  Tracing/lowering happens on
    THIS thread too, keeping even the Python-side compile cost off the
    device stream.  Per thunk the compile-watch diff classifies the
    outcome: backend-compile events observed → a cache ``fill`` (cold
    run), none → ``warm`` (executable/persistent cache already had it —
    the promised no-op warm path).  Builder/thunk exceptions are
    counted, never raised: compile-ahead is an accelerator, the device
    stream compiles on-demand exactly as before when it misfires."""

    def __init__(self, name: str = "h2o-automl-compile",
                 max_queue: int | None = None):
        start_compile_watch()
        self._cond = threading.Condition()
        self._queue: collections.deque = collections.deque()
        self._seen: set = set()
        self._stopped = False
        self._idle = True
        self.stats = {"requested": 0, "deduped": 0, "dropped": 0,
                      "unsupported": 0, "jobs": 0, "programs": 0,
                      "fills": 0, "warm": 0, "errors": 0,
                      "busy_s": 0.0, "compile_s": 0.0}
        self._max_queue = max_queue or queue_depth()
        self._thread = threading.Thread(target=self._run, name=name,
                                        daemon=True)
        self._thread.start()

    def submit(self, key: Any, builder: Callable[[], list],
               label: str = "") -> bool:
        """True when queued; False when deduped/dropped/stopped."""
        with self._cond:
            self.stats["requested"] += 1
            if self._stopped:
                return False
            if key in self._seen:
                self.stats["deduped"] += 1
                return False
            if len(self._queue) >= self._max_queue:
                # never block the device stream on compile-ahead
                # backpressure: a dropped request just compiles
                # on-demand later
                self.stats["dropped"] += 1
                return False
            self._seen.add(key)
            self._queue.append((builder, label))
            self._cond.notify_all()
            return True

    def mark_unsupported(self) -> None:
        """Count a plan entry with no compile-ahead support (GLM/DL:
        their iterative programs are shape-shared across configs, so
        pre-lowering buys little — the accounting keeps that visible)."""
        with self._cond:
            self.stats["unsupported"] += 1

    def idle(self) -> bool:
        with self._cond:
            return self._idle and not self._queue

    def wait_idle(self, timeout: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout
        with self._cond:
            while not (self._idle and not self._queue):
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._cond.wait(timeout=min(0.5, remaining))
        return True

    def stop(self, timeout: float = 30.0) -> bool:
        """Signal stop and join; an in-flight AOT compile finishes
        first (nothing can interrupt XLA), so the timeout bounds the
        wait — the thread is a daemon either way."""
        with self._cond:
            self._stopped = True
            self._queue.clear()
            self._cond.notify_all()
        self._thread.join(timeout=timeout)
        return not self._thread.is_alive()

    def _run(self) -> None:
        ident = threading.get_ident()
        while True:
            with self._cond:
                while not self._queue and not self._stopped:
                    self._idle = True
                    self._cond.notify_all()
                    self._cond.wait(timeout=0.5)
                if self._stopped:
                    self._idle = True
                    self._cond.notify_all()
                    return
                builder, label = self._queue.popleft()
                self._idle = False
            t0 = time.monotonic()
            before = compile_watch_snapshot(ident)
            try:
                thunks = builder() or []
                for thunk in thunks:
                    pre = compile_watch_snapshot(ident)
                    # each pre-lowered program is a compile-ahead-fill
                    # phase span (h2o_train_phase_seconds + timeline):
                    # the overlapped compile work the scheduler_stats
                    # totals previously reported only in aggregate
                    from .telemetry import phase_span

                    with phase_span("compile_ahead_fill",
                                    label=label or None):
                        thunk()
                    post = compile_watch_snapshot(ident)
                    with self._cond:
                        self.stats["programs"] += 1
                        # a "fill" is a genuinely new binary: a
                        # persistent-cache miss, or (cache disabled) any
                        # backend compile. A persistent-cache HIT or a
                        # fully in-memory reuse is the promised warm
                        # no-op.
                        misses = post["thread_pcache_misses"] \
                            - pre["thread_pcache_misses"]
                        hits = post["thread_pcache_hits"] \
                            - pre["thread_pcache_hits"]
                        compiled = post["thread_compiles"] \
                            - pre["thread_compiles"]
                        if misses > 0 or (hits == 0 and compiled > 0):
                            self.stats["fills"] += 1
                        else:
                            self.stats["warm"] += 1
            except Exception:   # noqa: BLE001 — accelerator only
                with self._cond:
                    self.stats["errors"] += 1
            finally:
                after = compile_watch_snapshot(ident)
                with self._cond:
                    self.stats["jobs"] += 1
                    self.stats["busy_s"] += time.monotonic() - t0
                    self.stats["compile_s"] += \
                        after["thread_compile_s"] - before["thread_compile_s"]


class PipelinedExecutor:
    """Device token + the two worker streams, with overlap accounting.

    The device *token* is a lock: whoever holds it may dispatch device
    computations.  The AutoML driver (the owning thread) wraps every
    training step in ``device()``, which also attributes wall time and
    critical-path compile-wait (compiles observed on the token-holding
    thread) to the device stream."""

    def __init__(self, compile_ahead: int | None = None,
                 queue: int | None = None):
        start_compile_watch()
        self._t0 = time.monotonic()
        self._token = threading.Lock()
        self._depth = compile_ahead_depth() if compile_ahead is None \
            else max(0, compile_ahead)
        self.host = HostStream(max_pending=queue)
        self.compiles = CompileStream(max_queue=queue) \
            if self._depth > 0 and persistent_cache_enabled() else None
        self._dev = {"busy_s": 0.0, "steps": 0, "compiles": 0,
                     "compile_wait_s": 0.0}
        self._watch0 = compile_watch_snapshot()

    @property
    def compile_ahead(self) -> int:
        return self._depth

    @contextlib.contextmanager
    def device(self, label: str = ""):
        """Hold the device token for one training step."""
        ident = threading.get_ident()
        with self._token:
            t0 = time.monotonic()
            before = compile_watch_snapshot(ident)
            try:
                yield
            finally:
                after = compile_watch_snapshot(ident)
                self._dev["busy_s"] += time.monotonic() - t0
                self._dev["steps"] += 1
                self._dev["compiles"] += \
                    after["thread_compiles"] - before["thread_compiles"]
                self._dev["compile_wait_s"] += \
                    after["thread_compile_s"] - before["thread_compile_s"]

    def compile_ahead_submit(self, key: Any,
                             builder: Callable[[], list],
                             label: str = "") -> bool:
        if self.compiles is None:
            return False
        return self.compiles.submit(key, builder, label)

    def shutdown(self, timeout: float = 30.0) -> None:
        """Stop both streams (drain the host stream first)."""
        try:
            self.host.stop(timeout=timeout)
        finally:
            if self.compiles is not None:
                self.compiles.stop(timeout=timeout)

    def stats(self) -> dict:
        """Overlap accounting: wall vs per-stream busy seconds, the
        device stream's critical-path compile-wait, and the
        compile-ahead fill/warm counts."""
        watch = compile_watch_snapshot()
        out = {
            "enabled": True,
            "wall_s": round(time.monotonic() - self._t0, 3),
            "device_busy_s": round(self._dev["busy_s"], 3),
            "device_steps": self._dev["steps"],
            "device_compiles": self._dev["compiles"],
            "device_compile_wait_s": round(
                self._dev["compile_wait_s"], 3),
            "host_busy_s": round(self.host.stats["busy_s"], 3),
            "host_applied": self.host.stats["applied"],
            "host_max_pending": self.host.stats["max_pending"],
            "compile_events": watch["compiles"] - self._watch0["compiles"],
            "compile_s": round(
                watch["compile_s"] - self._watch0["compile_s"], 3),
            "pcache_hits": watch["pcache_hits"]
            - self._watch0["pcache_hits"],
            "pcache_misses": watch["pcache_misses"]
            - self._watch0["pcache_misses"],
            "compile_ahead": None,
        }
        if self.compiles is not None:
            cs = dict(self.compiles.stats)
            cs["busy_s"] = round(cs["busy_s"], 3)
            cs["compile_s"] = round(cs["compile_s"], 3)
            out["compile_ahead"] = cs
        else:
            out["compile_ahead"] = {
                "disabled": "H2O_TPU_AUTOML_COMPILE_AHEAD=0"
                if self._depth == 0 else "no persistent compile cache"}
        return out
