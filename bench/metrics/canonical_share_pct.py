"""Share of the ingest window in ``canonical`` spans: each captured
relation deduplicated and sorted before ProvRC (``--trace 1`` only)."""


def read(ctx):
    return ctx.ingest_share_pct("canonical")
