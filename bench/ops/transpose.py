"""Axis permutation ``spec["perm"]``: ``out[c] <- in[c permuted back]``."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    return tuple(shape[p % len(shape)] for p in spec["perm"])


def rows(spec, shape, data_seed):
    out = out_shape(spec, shape)
    out_c = coords(out)
    in_c = [None] * len(shape)
    for o_ax, i_ax in enumerate(p % len(shape) for p in spec["perm"]):
        in_c[i_ax] = out_c[o_ax]
    return np.arange(int(np.prod(out)), dtype=np.int64), ravel(in_c, shape)
