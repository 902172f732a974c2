"""``k`` distinct cells drawn uniformly from the whole array with the run's
rng, sorted: the outlier cells a user picks when debugging a result."""

import numpy as np


def draw(rng, shape, k, cls):
    n = int(np.prod(shape))
    return np.sort(rng.choice(n, size=k, replace=False)).astype(np.int64)
