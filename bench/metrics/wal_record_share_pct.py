"""Share of the ingest window in ``wal.record`` spans: serializing and
compressing each entry's tables for the write-ahead log (``--trace 1``
only)."""


def read(ctx):
    return ctx.ingest_share_pct("wal.record")
