"""Mean time per query in the planner's ``merge`` spans (``at=init``, ``hop``,
``canonical``): ``merge_boxes`` deduplicating each frontier."""


def read(ctx):
    return ctx.span_ms("merge")
