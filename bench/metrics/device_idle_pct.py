"""Share of the traced window in which no program ran on the device.

``device_idle_pct.<part>`` names the same share in cells that report
another end-to-end metric; this reader serves both.
"""


def read(ctx):
    if ctx.device is None or ctx.device.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.device.busy_s / ctx.device.window_s)
