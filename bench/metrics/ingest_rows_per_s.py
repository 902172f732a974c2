"""Raw lineage rows committed per second over the whole ingest window."""


def read(ctx):
    if ctx.ingest is None or ctx.window_s <= 0:
        return None
    return ctx.ingest["rows"] / ctx.window_s
