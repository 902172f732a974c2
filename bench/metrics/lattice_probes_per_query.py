"""Index-route probes per query that the interval index answered by grid
arithmetic on a full-lattice table: the ``index_lattice_probes`` counter.
None where the program keeps no such counter."""


def read(ctx):
    if not ctx.latencies_s or "index_lattice_probes" not in ctx.counters:
        return None
    return ctx.counters["index_lattice_probes"] / len(ctx.latencies_s)
