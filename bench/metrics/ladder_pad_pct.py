"""What launching on ladder shapes costs: the program's ``ladder_pad_rows``
counter (rows padded past each frontier to reach its launch shape) over its
``batch_rows`` counter (the rows the dense joins packed), in percent, summed
over the window.  A program without the counter reports nothing."""


def read(ctx):
    rows = ctx.counters.get("batch_rows", 0)
    if "ladder_pad_rows" not in ctx.counters or rows <= 0:
        return None
    return 100.0 * ctx.counters["ladder_pad_rows"] / rows
