"""A valid 2-D convolution window ``spec["kernel"]`` with ``stride``:
``out[i, j] <- in[i*s + di, j*s + dj]``."""

import numpy as np


def out_shape(spec, shape):
    kh, kw = (int(k) for k in spec["kernel"])
    s = int(spec.get("stride", 1))
    return ((shape[0] - kh) // s + 1, (shape[1] - kw) // s + 1)


def rows(spec, shape, data_seed):
    kh, kw = (int(k) for k in spec["kernel"])
    s = int(spec.get("stride", 1))
    out = out_shape(spec, shape)
    out_cells = np.arange(int(np.prod(out)), dtype=np.int64)
    i, j = out_cells // out[1], out_cells % out[1]
    di, dj = (a.reshape(-1) for a in np.meshgrid(
        np.arange(kh), np.arange(kw), indexing="ij"))
    in_i = (i * s)[:, None] + di[None, :]
    in_j = (j * s)[:, None] + dj[None, :]
    return np.repeat(out_cells, kh * kw), (in_i * shape[1] + in_j).reshape(-1)
