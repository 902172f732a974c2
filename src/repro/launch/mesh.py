"""Production meshes.

Everything is a function — importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh", "local_mesh"]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def local_mesh(model_parallel: int = 1):
    """Best-effort mesh over whatever devices exist (CPU runs: 1 device)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return make_mesh((n // model_parallel, model_parallel), ("data", "model"))
