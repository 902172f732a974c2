"""One module per array operation, found by the ``op`` name of a config.

``bench/ops/<op>.py`` defines

* ``out_shape(spec, shape) -> tuple`` — the shape the operation gives an
  input of ``shape``;
* ``rows(spec, shape, data_seed) -> (out_flat, in_flat)`` — its raw lineage,
  one row per contribution ``out[b] <- in[a]``, both sides as flat
  (raveled) cell ids, in plain numpy.

This is the benchmark's own copy of the semantics; it shares no code with
the program under test, so it can serve as the reference.
"""

from __future__ import annotations

import importlib

import numpy as np


def get(op: str):
    """The module of operation ``op``."""
    return importlib.import_module(f"{__name__}.{op}")


def coords(shape: tuple[int, ...]) -> list[np.ndarray]:
    """Every cell's coordinates, one array per axis, in row-major order."""
    n = int(np.prod(shape))
    return list(np.unravel_index(np.arange(n, dtype=np.int64), shape))


def ravel(cs: list[np.ndarray], shape: tuple[int, ...]) -> np.ndarray:
    return np.ravel_multi_index(tuple(cs), shape).astype(np.int64)


def identity(shape: tuple[int, ...]) -> tuple[np.ndarray, np.ndarray]:
    ident = np.arange(int(np.prod(shape)), dtype=np.int64)
    return ident, ident.copy()
