"""Mean time per query in the planner's ``plan`` spans (``trace=True``)."""


def read(ctx):
    if not ctx.spans:
        return None
    return 1e3 * sum(s.get("plan", 0.0) for s in ctx.spans) / len(ctx.spans)
