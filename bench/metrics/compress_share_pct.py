"""Share of the ingest window spent inside ``add_lineage`` (capture hand-off,
ProvRC compression and the WAL append), timed by the harness."""


def read(ctx):
    if ctx.ingest is None or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.ingest["add_s"] / ctx.window_s
