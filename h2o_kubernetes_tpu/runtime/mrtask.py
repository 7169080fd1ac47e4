"""MRTask-equivalent: per-shard map + collective reduce.

The reference's compute primitive is `MRTask.doAll(frame)` — map over each
node's local Chunks, reduce locally, then reduce up a binary tree of RPCs
over the node ring (water/MRTask.java, SURVEY.md §3.5). The TPU-native
equivalent is exactly `shard_map`: the `map(Chunk[])` body becomes the
per-shard function, and the software tree-allreduce becomes an ICI
collective (`psum`/`pmin`/`pmax`).

`doall(fn, *cols)` runs `fn` on each device's row-shard of the column
arrays and reduces the returned pytree across shards. Per-leaf reduce ops
are declared with a matching pytree of {"sum","min","max","mean","concat"}
(a bare string applies to every leaf) — the analog of an MRTask subclass's
`reduce()` method.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import ROWS, global_mesh

_REDUCERS = {
    "sum": lambda x: lax.psum(x, ROWS),
    "min": lambda x: lax.pmin(x, ROWS),
    "max": lambda x: lax.pmax(x, ROWS),
    "mean": lambda x: lax.pmean(x, ROWS),
    "concat": lambda x: lax.all_gather(x, ROWS, axis=0, tiled=True),
    "none": lambda x: x,
}


# jitted doall callables, per mesh (weak: a replaced mesh's entries die
# with it) then by (cache_key, map_fn, reduce-structure, donate).
# jax.jit keys its executable cache on the CALLABLE's identity, so the
# fresh `body` closure each doall() call builds means a fresh
# trace+compile even for byte-identical computations — CV fold frames
# re-deriving rollups paid ~25 warm recompiles per AutoML run. Callers
# whose map_fn is a stable module-level function opt in with
# `cache_key`; per-shape retracing inside one cached callable is jit's
# normal behavior.
import weakref

_DOALL_CACHE: "weakref.WeakKeyDictionary[Mesh, dict]" = \
    weakref.WeakKeyDictionary()


def _freeze(reduce) -> Any:
    leaves, treedef = jax.tree.flatten(reduce)
    return tuple(leaves), str(treedef)


def doall(map_fn: Callable[..., Any], *cols: jax.Array,
          reduce: Any = "sum", mesh: Mesh | None = None,
          donate: bool = False, cache_key: Any = None) -> Any:
    """Map `map_fn` over aligned row-shards of `cols`, reduce across shards.

    Returns the fully reduced pytree, replicated on every device (like
    `MRTask.getResult()` returning the reduced task object to the caller).

    `cache_key`: opt-in reuse of the jitted callable across calls (the
    caller asserts map_fn's computation is fully determined by the key,
    the reduce spec, and the operand shapes).
    """
    from . import faults
    from .health import device_dispatch, require_healthy
    from .lifecycle import breaker_guard

    # fail fast on a broken cloud (SURVEY.md §5.3); doall fires its OWN
    # site, so it must not also consume train.step fault counts
    require_healthy(fault_site=None)
    faults.fire("mrtask.doall")
    mesh = mesh or global_mesh()

    if cache_key is not None:
        # map_fn identity in the key: two callers sharing a cache_key
        # string with different (module-level) map_fns must not get
        # each other's computation
        key = (cache_key, map_fn, _freeze(reduce), donate)
        cached = _DOALL_CACHE.get(mesh, {}).get(key)
        if cached is not None:
            # breaker outside the device guard: a dispatch error
            # (converted to ClusterHealthError by the guard) counts one
            # consecutive failure; an open breaker rejects before any
            # device work — MRTask traffic respects the cooldown too
            with breaker_guard("doall dispatch"), \
                    device_dispatch("doall dispatch"):
                # block inside the guard: async dispatch would surface
                # a mid-execution device error at the CALLER's first
                # read, outside the guard. doall results are small
                # fully-reduced pytrees callers read immediately, so
                # the sync costs nothing real.
                return jax.block_until_ready(cached(*cols))

    def body(*shards):
        out = map_fn(*shards)
        reds = reduce
        if isinstance(reds, str):
            reds = jax.tree.map(lambda _: reduce, out)
        return jax.tree.map(lambda x, r: _REDUCERS[r](x), out, reds)

    # shard_map needs out_specs up front; "none"/"concat" leaves differ.
    # Trace map_fn (collective-free user code) on shard-shaped abstractions.
    shard_shapes = tuple(
        jax.ShapeDtypeStruct((c.shape[0] // mesh.shape[ROWS],) + c.shape[1:],
                             c.dtype) for c in cols)
    res = jax.eval_shape(map_fn, *shard_shapes)
    reds = reduce if not isinstance(reduce, str) else jax.tree.map(
        lambda _: reduce, res)
    out_specs = jax.tree.map(
        lambda _, r: P(ROWS) if r == "none" else P(), res, reds)

    fn = jax.shard_map(body, mesh=mesh, in_specs=P(ROWS), out_specs=out_specs)
    jfn = jax.jit(fn, donate_argnums=tuple(range(len(cols)))
                  if donate else ())
    if cache_key is not None:
        _DOALL_CACHE.setdefault(mesh, {})[key] = jfn
    with breaker_guard("doall dispatch"), \
            device_dispatch("doall dispatch"):
        # block inside the guard (see the cached branch above)
        return jax.block_until_ready(jfn(*cols))


@functools.lru_cache(maxsize=None)
def _padded_len(n: int, shards: int) -> int:
    return ((n + shards - 1) // shards) * shards


def pad_rows(x, mesh: Mesh | None = None, pad_value=None):
    """The host half of `shard_rows`: pad the leading dim to a multiple
    of the ROWS axis.

    Default padding is NaN for floats, -1 for signed ints, 0 otherwise
    (np.full would silently turn NaN into INT_MIN for int dtypes).
    """
    import numpy as np

    mesh = mesh or global_mesh()
    shards = mesh.shape[ROWS]
    n = x.shape[0]
    m = _padded_len(n, shards)
    if m != n:
        if pad_value is None:
            kind = np.dtype(x.dtype).kind
            pad_value = (np.nan if kind == "f" else -1 if kind == "i" else 0)
        pad = np.full((m - n,) + tuple(x.shape[1:]), pad_value, dtype=x.dtype)
        x = np.concatenate([np.asarray(x), pad], axis=0)
    return x


def put_rows(x, mesh: Mesh | None = None) -> jax.Array:
    """The device half of `shard_rows`: rows already padded, sharded
    over the ROWS axis."""
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh or global_mesh(), P(ROWS))
    if not sharding.is_fully_addressable:
        # multi-host (DCN) mesh: device_put cannot target devices owned
        # by other processes; every process holds the same host array
        # and contributes its local shards (multi-controller SPMD)
        import numpy as np

        xnp = np.asarray(x)
        return jax.make_array_from_callback(
            xnp.shape, sharding, lambda idx: xnp[idx])
    return jax.device_put(jnp.asarray(x), sharding)


def shard_rows(x, mesh: Mesh | None = None, pad_value=None) -> jax.Array:
    """Pad the leading dim to a multiple of the ROWS axis and shard it
    (`pad_rows`, then `put_rows`)."""
    mesh = mesh or global_mesh()
    return put_rows(pad_rows(x, mesh, pad_value), mesh)
