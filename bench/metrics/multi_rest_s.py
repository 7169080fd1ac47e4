"""Device seconds a job spends inside `_boost_multi_jit`'s module
events OUTSIDE its Mosaic kernels: the softmax gradients and their
`[K, rows]` transposes, the value stacks a class, split finding over
K x nodes, row descent, the `[rows, K]` margin update — everything of
a round's K trees but their histograms. `boost_rest_s`'s reading (the
neighbour does the work), reported where the traced jobs ran the
K-class boost program and nowhere else."""

import boost_rest_s
from _common import boost_modules, fullest

MODULE = "_boost_multi"


def only_multi(ctx) -> bool:
    """The window's boost modules are there and are all the K-class
    program's."""
    mods = boost_modules(ctx, fullest(ctx))
    return bool(mods) and all(MODULE in m[2] for m in mods)


def read(ctx):
    return boost_rest_s.read(ctx) if only_multi(ctx) else None
