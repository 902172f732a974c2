"""Bytes of the pristine store over the raw rows' bytes (rows x dims x 8)."""


def read(ctx):
    if not ctx.raw_bytes:
        return None
    return 100.0 * ctx.stored_bytes / ctx.raw_bytes
