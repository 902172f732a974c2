"""Mean ``prov_query`` latency over every query of the window (host clock).

One closed-loop client, so this is the window's time over its queries.  The
mix's six fixed queries come in equal shares, so a median would fall on the
boundary between the third and fourth query's latencies and jump between
them with the count of queries in the window; the mean does not.
"""


def read(ctx):
    if not ctx.latencies_s:
        return None
    return 1e3 * sum(ctx.latencies_s) / len(ctx.latencies_s)
