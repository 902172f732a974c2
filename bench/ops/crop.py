"""A centre crop that drops ``spec["margin"]`` cells on every side:
``out[i, j] <- in[i + m, j + m]``.  The margin, not the size, is the
argument, so one configuration crops alike at every input side."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    m = int(spec["margin"])
    return tuple(d - 2 * m for d in shape)


def rows(spec, shape, data_seed):
    m = int(spec["margin"])
    out = out_shape(spec, shape)
    in_c = [c + m for c in coords(out)]
    return np.arange(int(np.prod(out)), dtype=np.int64), ravel(in_c, shape)
