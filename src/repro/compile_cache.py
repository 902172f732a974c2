"""JAX's persistent compilation cache for the repository's entry points.

Scripts that drive the chip (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; importing the library sets
nothing.  ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX keeps the
cache and is left alone.  Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout — a fixed path, since the path is part of the cache
key and a directory that moves never hits.
"""

from __future__ import annotations

import os

__all__ = ["enable_compile_cache"]

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it lives in."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # the Pallas kernels compile in well under JAX's default one-second
    # floor, which would keep every one of them out of the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
