"""A 2-D up-convolution of ``spec["stride"]`` (kernel = stride), each output
cell read from the one input cell under it: ``out[i, j] <- in[i // s, j // s]``."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    s = int(spec["stride"])
    return tuple(d * s for d in shape)


def rows(spec, shape, data_seed):
    s = int(spec["stride"])
    out = out_shape(spec, shape)
    in_c = [c // s for c in coords(out)]
    return np.arange(int(np.prod(out)), dtype=np.int64), ravel(in_c, shape)
