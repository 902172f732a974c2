"""Telemetry subsystem: metrics registry, structured tracing, exporters.

``repro.obs`` is deliberately free of ``repro.core`` imports at module
level so core modules can depend on it without cycles.  The three
pieces:

- :mod:`repro.obs.metrics` — typed counters/gauges/log-bucketed
  histograms behind a single internally-locked :class:`MetricsRegistry`;
  ``DSLog.io_stats`` is a live read-only view over it.
- :mod:`repro.obs.trace` — off-by-default span trees over a query's
  and an ingest's host stages, with wall time and per-stage counts,
  each span also a ``dslog.<name>`` profiler annotation.
- :mod:`repro.obs.export` — ``telemetry.json`` snapshot schema,
  Prometheus text exposition, and the combined ``health()`` report.
"""

from repro.obs.metrics import (
    Histogram,
    IoStatsView,
    MetricsRegistry,
    StatsView,
)
from repro.obs.trace import QueryTrace, Span, maybe_span
from repro.obs.export import (
    TELEMETRY_SCHEMA,
    health,
    parse_prometheus,
    render_prometheus,
    telemetry_snapshot,
    validate_telemetry,
)

__all__ = [
    "Histogram",
    "IoStatsView",
    "MetricsRegistry",
    "StatsView",
    "QueryTrace",
    "Span",
    "maybe_span",
    "TELEMETRY_SCHEMA",
    "health",
    "parse_prometheus",
    "render_prometheus",
    "telemetry_snapshot",
    "validate_telemetry",
]
