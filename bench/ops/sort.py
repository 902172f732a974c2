"""``np.sort`` along ``spec["axis"]`` of uniform random values drawn from
``data_seed``: ``out[.., r, ..] <- in[.., argsort(values)[r], ..]``."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    return shape


def rows(spec, shape, data_seed):
    axis = spec.get("axis", -1) % len(shape)
    c = coords(shape)
    values = np.random.default_rng(data_seed).random(shape)
    c[axis] = np.argsort(values, axis=axis, kind="stable").reshape(-1)
    return np.arange(int(np.prod(shape)), dtype=np.int64), ravel(c, shape)
