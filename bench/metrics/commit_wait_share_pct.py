"""Share of the ingest window spent waiting in ``commit()`` (group commit and
fsync of the write-ahead log), timed by the harness."""


def read(ctx):
    if ctx.ingest is None or ctx.window_s <= 0:
        return None
    return 100.0 * ctx.ingest["commit_s"] / ctx.window_s
