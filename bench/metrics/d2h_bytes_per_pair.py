"""Bytes the range-join dispatches read back per pair they emitted.

The program's ``d2h_bytes`` counter (the int32 masks and tile stacks copied
to the host) over its ``join_pairs`` counter (pairs the dense joins
emitted), both summed over the window.  A program without the counters
reports nothing.
"""


def read(ctx):
    pairs = ctx.counters.get("join_pairs", 0)
    if "d2h_bytes" not in ctx.counters or pairs <= 0:
        return None
    return ctx.counters["d2h_bytes"] / pairs
