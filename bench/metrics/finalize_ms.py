"""Mean time per query in the batched executor's ``finalize`` spans: joined
pairs turned back into each hop's boxes, on both routes."""


def read(ctx):
    return ctx.span_ms("finalize")
