"""Dense-join dispatches per query: the ``kernel_launches`` counter.

The counter counts compiled launches and numpy-twin dispatches alike
(``twin_launches`` counts the twin's alone), so it is the whole count.
"""


def read(ctx):
    if not ctx.latencies_s:
        return None
    return ctx.counters.get("kernel_launches", 0) / len(ctx.latencies_s)
