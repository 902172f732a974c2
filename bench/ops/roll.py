"""``np.roll`` by ``spec["shift"]`` along ``spec["axis"]``."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    return shape


def rows(spec, shape, data_seed):
    axis = spec.get("axis", -1) % len(shape)
    c = coords(shape)
    c[axis] = (c[axis] - int(spec["shift"])) % shape[axis]
    return np.arange(int(np.prod(shape)), dtype=np.int64), ravel(c, shape)
