"""h2o_kubernetes_tpu — a TPU-native rebuild of the H2O-3 + h2o-kubernetes
capability surface: distributed columnar Frames as sharded JAX arrays, an
MRTask-style map/reduce runtime on ICI collectives, histogram tree learners
(GBM/DRF/XGBoost-hist) and GLM/DeepLearning/Word2Vec on JAX/Pallas, AutoML
with stacked ensembles, and a C++ Kubernetes deployment stack (native/:
tpuk CLI + h2o-tpu-operator reconciling the H2OTpu CRD).

See SURVEY.md for the reference blueprint this is built against.
"""

# the `import` span's start: stamped before anything else is imported,
# filed at this file's last line (`sys` and `time` the interpreter has
# loaded already)
import sys as _sys
import time as _time

_IMPORT_STARTED = (_time.perf_counter_ns(), _time.thread_time_ns(),
                   len(_sys.modules))

from .automl import AutoML, Job, Leaderboard, jobs
from .config import get_config, set_config
from .grid import GridSearch, H2OGridSearch
from .diagnostics import device_memory, log, profile, timeline
from .frame import Frame, Vec, import_file, parse_setup
from .mojo import MojoModel, export_mojo, import_mojo
from .persist import (export_file, load_frame, load_model, save_frame,
                      save_model)
from .runtime import (ClusterHealthError, global_mesh, health_status,
                      heartbeat, initialize_distributed, make_mesh,
                      set_global_mesh, start_heartbeat, stop_heartbeat,
                      use_mesh)

__version__ = "0.2.0"


def init(coordinator: str | None = None, **kw) -> None:
    """Connect/boot the cluster (analog of h2o.init()).

    On TPU the 'cluster' is the pod slice this process can see; multi-host
    formation goes through the JAX distributed runtime using env injected
    by the operator (see runtime/mesh.py).

    Also turns on JAX's persistent compilation cache (at
    JAX_COMPILATION_CACHE_DIR when set, else tools/_jax_cache/ of the
    checkout): a cold AutoML run is otherwise dominated by XLA
    compiles — the disk cache keys on hardware+HLO, so a SECOND
    process pays none of them.
    """
    from .runtime.backend import (enable_persistent_compile_cache,
                                  start_compile_watch)
    from .runtime.telemetry import phase_span

    # the compile watch first (idempotent): what the spans of this
    # process pay in jax's trace / lower / compile stages is theirs
    start_compile_watch()
    with phase_span("init"):
        with phase_span("init.cache"):
            enable_persistent_compile_cache()
        with phase_span("init.distributed"):
            initialize_distributed(coordinator, **kw)
        # the first `jax.devices()` of a process that has not called
        # it (the backend's start) lands here
        with phase_span("init.mesh") as mesh:
            mesh["devices"] = len(global_mesh().devices.flat)


def cluster_status() -> dict:
    """Analog of GET /3/Cloud."""
    import jax

    mesh = global_mesh()
    from .runtime.health import health_status as _hs

    return {
        "version": __version__,
        "cloud_healthy": bool(_hs()["healthy"]),
        "cloud_size": len(mesh.devices.flat),
        "mesh_shape": dict(mesh.shape),
        "process_count": jax.process_count(),
        "devices": [str(d) for d in mesh.devices.flat],
    }


def _file_import_span() -> None:
    """The `import` root span: this package's import from its first
    line to here, by the stamps above; `modules` is how many of
    `sys.modules` it added, `cpu_ms` the importing thread's CPU."""
    from .runtime.telemetry import record_root_span

    t0, cpu0, modules0 = _IMPORT_STARTED
    record_root_span(
        "import", t0, _time.perf_counter_ns(),
        modules=len(_sys.modules) - modules0,
        cpu_ms=round((_time.thread_time_ns() - cpu0) / 1e6, 3))


_file_import_span()
