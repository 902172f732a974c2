"""Mean time per query in ``readback`` spans: the wait for the device and
the copy of each mask or tile stack to the host."""


def read(ctx):
    return ctx.span_ms("readback")
