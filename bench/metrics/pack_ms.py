"""Mean time per query in ``pack`` spans: packing a frontier and its tables
into the kernels' int32 rows and tile lists on the host."""


def read(ctx):
    return ctx.span_ms("pack")
