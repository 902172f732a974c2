"""Mean time per query in ``pair_extract`` spans: the pairs and their
per-segment split, taken from a read-back mask on the host."""


def read(ctx):
    return ctx.span_ms("pair_extract")
