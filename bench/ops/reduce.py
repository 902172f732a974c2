"""Reduction over ``spec["axes"]``: every input cell feeds the output cell
of its kept coordinates (one cell when every axis goes)."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    axes = {a % len(shape) for a in spec["axes"]}
    return tuple(d for a, d in enumerate(shape) if a not in axes) or (1,)


def rows(spec, shape, data_seed):
    axes = {a % len(shape) for a in spec["axes"]}
    keep = [a for a in range(len(shape)) if a not in axes]
    n = int(np.prod(shape))
    c = coords(shape)
    out = ravel([c[a] for a in keep], out_shape(spec, shape)) if keep \
        else np.zeros(n, np.int64)
    return out, np.arange(n, dtype=np.int64)
