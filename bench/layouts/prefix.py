"""The first ``k`` cells of the array in row-major order, as the paper's
Figs 8/9 query them (``np.arange(n_cells)[:k]``)."""

import numpy as np


def draw(rng, shape, k, cls):
    return np.arange(k, dtype=np.int64)
