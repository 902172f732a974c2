"""Mean time per query in ``index_probe`` spans: candidate pairs from the
interval index, on the hops the planner routes to it."""


def read(ctx):
    return ctx.span_ms("index_probe")
