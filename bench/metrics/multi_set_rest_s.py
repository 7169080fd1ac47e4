"""Device seconds a job spends inside `_boost_multi_jit`'s module
events OUTSIDE its Mosaic kernels, where the K class trees of a round
split on sets: `multi_rest_s`'s reading, which there holds every class
tree's level order (`set_order`), the set lookups of its rows
(`set_descend`), split finding over K x nodes and the softmax. Reported
where the traced jobs ran the K-class boost program on columns that
split by sets, and nowhere else."""

import multi_rest_s
from multi_set_split_share import k_class_sets


def read(ctx):
    return multi_rest_s.read(ctx) if k_class_sets() else None
