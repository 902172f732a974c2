"""One reader per metric, found by the metric's name in ``BENCHMARK.json``.

``bench/metrics/<name>.py`` defines ``read(ctx) -> float | None``; ``ctx``
is the run's :class:`bench.context.RunContext`.  A reader that finds nothing to
read returns None, and the metric is left out of the result line.  A name
with a suffix, ``<name>.<part>``, falls back to the reader of ``<name>``.
"""
