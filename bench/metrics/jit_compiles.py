"""XLA programs the set-up's warm-up built (compiled, or loaded from the
persistent cache), counted by a ``jax.monitoring`` listener.

The window builds none (the ``window_programs`` check holds it to 0), so
per-shape compilation shows here and in ``setup_s``.
"""


def read(ctx):
    return float(ctx.setup_programs)
