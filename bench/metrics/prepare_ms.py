"""Mean time per query in the batched executor's ``prepare`` spans: pooling
each wave's probe boxes and deduplicating them."""


def read(ctx):
    return ctx.span_ms("prepare")
