"""Device time of the range-join programs per query, from the profiler trace.

The ``jit_range_join_mask`` and ``jit_range_join_tile_masks`` module events
on the device plane, summed over the window.
"""


def read(ctx):
    if ctx.device is None or not ctx.latencies_s:
        return None
    s = ctx.device.kernel_s("jit_range_join_mask", "jit_range_join_tile_masks")
    if s <= 0:
        return None
    return 1e3 * s / len(ctx.latencies_s)
