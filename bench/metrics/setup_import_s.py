"""Seconds of `import h2o_kubernetes_tpu`: the program's `import` root
span, the package's `__init__` from its first line to its last (jax
and numpy among what it loads, unless the caller loaded them first).
An earlier line says how many modules it added and the importing
thread's CPU."""

import _host_spans as hs
import _program_spans as ps


def read(ctx):
    root = hs.first_root(ctx, "import")
    if root is None:
        return None
    ctx["say"](f"import: {ps.seconds(root):.3f}s, {root.get('modules')} "
               f"modules, cpu {root.get('cpu_ms', float('nan')) / 1e3:.3f}s")
    return ps.seconds(root)
