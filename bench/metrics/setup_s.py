"""Seconds from process start to the window: load, store, warm-up, compiles."""


def read(ctx):
    return ctx.setup_s
