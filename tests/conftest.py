"""Test bootstrap: force an 8-device CPU platform BEFORE jax imports.

Mirrors the reference's test trick (SURVEY.md §4): H2O tests boot a real
multi-JVM cloud on localhost; we boot a real 8-device mesh on CPU so
shard_map/psum semantics are exercised for real — no mocked collectives.
"""

import collections
import os
import threading

os.environ["JAX_PLATFORMS"] = "cpu"   # tests never touch a chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_collective_call_terminate_timeout_seconds" not in flags:
    # XLA:CPU's collective rendezvous TERMINATES the process when the 8
    # shard threads of a psum don't all arrive in time; under a loaded
    # box (6 xdist workers x 8 device threads on 8 cores) thread
    # starvation can hold one back for a while. Give starvation room —
    # minutes, not hours: a rendezvous that can never complete must
    # abort with the collective's name well inside the suite's wall
    # cap, not wait it out in silence (pyproject's faulthandler_timeout
    # names the Python side of the same stall).
    flags += (" --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
              " --xla_cpu_collective_call_terminate_timeout_seconds=300"
              " --xla_cpu_collective_timeout_seconds=300")
os.environ["XLA_FLAGS"] = flags

import jax  # noqa: E402

# a pytest plugin may have imported jax (latching its platform from the
# environment) before this file ran; backends are still uninitialized
# at conftest time, so the live config still takes effect
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def _bound_cpu_runahead(limit: int = 16) -> None:
    """Keep at most ``limit`` dispatched computations un-awaited.

    XLA:CPU's client (jaxlib 0.9.0) deadlocks once more than 32
    computations are in flight on a device while one of them holds a
    collective: the next multi-device dispatch runs on the client's
    thread pool and BLOCKS a pool thread on the in-flight limit, the
    pool has max(cores, devices) threads — 8 and 8 here — and the
    psum's eight participants then never all get a thread ("Expected 8
    threads to join the rendezvous, but only N of them arrived").
    Nothing on the Python side is wrong: `for _ in range(40): x = f(x)`
    with a psum in `f` is enough, from one thread. Whether a test
    reaches 32 depends on how far dispatch outruns execution, i.e. on
    the load on the box — a full `-n 6` run hung in most runs, a file
    alone never (26 tests dispatch more than 32 sharded programs
    between two host syncs; IsolationForest 168, DeepLearning 96).
    A chip has no such pool, so the bound lives here, not in the
    package. It needs every dispatch to come through Python: pjit's
    C++ fast path is turned off and ExecuteReplicated wrapped — private
    API of the one jax this repo targets, so a jax that renames either
    fails at this import, loudly, not as a hang
    (tests/test_runtime.py::test_dispatch_runahead_is_bounded)."""
    from jax._src import pjit
    from jax._src.interpreters import pxla

    pjit._get_fastpath_data          # noqa: B018 — AttributeError if gone
    pjit._get_fastpath_data = lambda *a, **k: None
    dispatch = pxla.ExecuteReplicated.__call__
    pending: collections.deque = collections.deque()
    lock = threading.Lock()

    def bounded(self, *args):
        out = dispatch(self, *args)
        leaf = next((o for o in jax.tree.leaves(out)
                     if isinstance(o, jax.Array)), None)
        if leaf is None:
            return out
        with lock:
            pending.append(leaf)
            oldest = pending.popleft() if len(pending) > limit else None
        if oldest is not None:
            try:
                oldest.block_until_ready()
            except RuntimeError:
                pass    # donated to a later dispatch meanwhile: deleted
        return out

    pxla.ExecuteReplicated.__call__ = bounded


_bound_cpu_runahead()


def pytest_runtest_logreport(report):
    """Per-test wall-clock lines (opt-in via H2O_TPU_TEST_TIMINGS):
    tools/run_tests.py turns these into a "slowest 5 tests" digest when
    a module TIMES OUT — pytest's own --durations only prints at
    session end, which a killed module never reaches (the known
    XLA:CPU rendezvous stalls present exactly like that)."""
    if report.when == "call" and os.environ.get("H2O_TPU_TEST_TIMINGS"):
        print(f"[time] {report.duration:.2f}s {report.nodeid}",
              flush=True)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches():
    """Free compiled executables after every test module.

    Root cause of round-1's roaming full-suite SIGABRT: each train()
    call jit-compiles fresh executables whose memory mappings are never
    released (~600-1500 maps/test), and the process walks into the
    kernel's vm.max_map_count (65530) around test ~120 — mmap then
    fails inside eager dispatch and XLA aborts without a message.
    Clearing per module caps the accumulation at single-module scale.
    """
    yield
    import gc

    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def mesh8():
    from h2o_kubernetes_tpu.runtime import make_mesh, set_global_mesh

    mesh = make_mesh()
    set_global_mesh(mesh)
    return mesh
