"""Share of the device's idle time that no program stage accounts for.

Each idle stretch of the traced window is charged to the innermost program
span open on the host (``bench.devtrace``).  This is the part charged to a
trace's root, ``query`` or ``ingest``, or to no span at all, over all idle
time: the host work the program's stage spans do not yet name.
``idle_unattributed_pct.<part>`` names it in cells that report another
end-to-end metric; this reader serves both.  A trace without program spans
reports nothing.
"""

UNATTRIBUTED = (None, "query", "ingest")


def read(ctx):
    idle = getattr(ctx.device, "idle_by_span", {})
    total = sum(idle.values())
    if total <= 0 or not set(idle) - {None}:
        return None
    return 100.0 * sum(idle.get(k, 0.0) for k in UNATTRIBUTED) / total
