"""Mean time per query in the planner's ``fanin_merge`` spans: the
``merge_boxes`` pass at a node where two or more plan steps converge (a
U-Net crop-and-concat array).  Other nodes' merges stay in ``merge_ms``."""


def read(ctx):
    return ctx.span_ms("fanin_merge")
