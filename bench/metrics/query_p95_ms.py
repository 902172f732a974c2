"""95th percentile of ``prov_query`` latency over every query of the window."""

import numpy as np


def read(ctx):
    if not ctx.latencies_s:
        return None
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3
