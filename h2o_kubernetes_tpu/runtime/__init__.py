from . import faults, lifecycle, retry
from .mesh import (COLS, ROWS, global_mesh, initialize_distributed, make_mesh,
                   n_row_shards, replicated, row_sharding, set_global_mesh,
                   use_mesh)
from .health import (ClusterHealthError, device_dispatch, health_status,
                     heartbeat, start_heartbeat, stop_heartbeat)
from .mrtask import doall, shard_rows

__all__ = [
    "COLS", "ROWS", "global_mesh", "initialize_distributed", "make_mesh",
    "n_row_shards", "replicated", "row_sharding", "set_global_mesh",
    "use_mesh", "doall", "shard_rows", "ClusterHealthError",
    "device_dispatch", "heartbeat", "health_status", "start_heartbeat",
    "stop_heartbeat",
    "faults", "lifecycle", "retry",
]
