"""C-order reshape to ``spec["shape"]`` (one ``-1`` allowed): every flat id
keeps its place."""

import numpy as np

from bench.ops import identity


def out_shape(spec, shape):
    n = int(np.prod(shape))
    out = tuple(int(d) for d in spec["shape"])
    if -1 in out:
        rest = int(np.prod([d for d in out if d != -1]))
        out = tuple(n // rest if d == -1 else d for d in out)
    if int(np.prod(out)) != n:
        raise ValueError(f"cannot reshape {shape} to {out}")
    return out


def rows(spec, shape, data_seed):
    return identity(shape)
