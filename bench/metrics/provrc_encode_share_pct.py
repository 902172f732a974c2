"""Share of the ingest window in ProvRC's two encoding steps, the
``provrc.step1`` and ``provrc.step2`` spans (``--trace 1`` only)."""


def read(ctx):
    return ctx.ingest_share_pct("provrc.step1", "provrc.step2")
