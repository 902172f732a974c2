"""Mean host wall time per query inside dense-join dispatches.

The summed ``kernel_launch`` and ``twin`` span durations of each query:
pack, upload, kernel, readback and pair extraction, not kernel time.
"""


def read(ctx):
    if not ctx.spans:
        return None
    return 1e3 * sum(s.get("kernel", 0.0) for s in ctx.spans) / len(ctx.spans)
