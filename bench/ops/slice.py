"""Strided slice from the origin with steps ``spec["step"]``."""

import numpy as np

from bench.ops import coords, ravel


def out_shape(spec, shape):
    return tuple(-(-d // int(s)) for d, s in zip(shape, spec["step"]))


def rows(spec, shape, data_seed):
    out = out_shape(spec, shape)
    in_c = [ci * int(s) for ci, s in zip(coords(out), spec["step"])]
    return np.arange(int(np.prod(out)), dtype=np.int64), ravel(in_c, shape)
