"""What a loop shares with the harness: the run's state and its clock.

A loop (``bench/loops/<name>.py``) defines ``run(r: Run) -> (attempted,
failed, memory_peak_bytes)``: it warms up, opens the window with
:meth:`Run.open_window`, drives the system under test for ``--seconds``,
closes it, and appends to ``r.checks`` every number it compares with its
limit.  Metric readers read ``r.ctx``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from . import store


def note(msg: str) -> None:
    print(msg, flush=True)


def steady_allocator() -> None:
    """Fix glibc's mmap threshold at 32 MiB, its own ceiling, and the trim
    threshold at twice that: the state glibc moves to once a process frees
    a large block.  Left dynamic, it depends on the run's history (whether
    anything compiled, how large the tables were), and every array above
    the threshold costs fresh pages; host-bound queries then read up to a
    fifth slower in runs that compiled nothing."""
    import ctypes

    try:
        libc = ctypes.CDLL(None)
        mallopt = libc.mallopt
    except (OSError, AttributeError):  # not glibc: nothing to fix
        return
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt(-3, 32 << 20)  # M_MMAP_THRESHOLD
    mallopt(-1, 64 << 20)  # M_TRIM_THRESHOLD


def add_span_seconds(into: dict, tr) -> dict:
    """Add the seconds of every timed span of the program trace ``tr`` to
    ``into`` by span kind, the root (``query``, ``ingest``) included."""
    for s in tr.spans():
        if s.kind and s.duration is not None:
            into[s.kind] = into.get(s.kind, 0.0) + s.duration
    return into


@dataclass
class RunContext:
    """Everything a metric reader may read about one run."""

    setup_s: float = 0.0
    window_s: float = 0.0
    setup_programs: int = 0
    window_programs: int = 0
    latencies_s: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)  # per traced query: {span kind: s}
    device: object = None  # devtrace.Reduced of the traced window
    # {"rows", "add_s", "commit_s"}, traced also {"spans": {kind: s},
    # "canonical_routes": {route: n}}
    ingest: dict | None = None
    raw_bytes: int = 0
    stored_bytes: int = 0

    def span_ms(self, kind: str) -> float | None:
        """Mean milliseconds per traced query in spans of ``kind``; None
        where no query's trace holds one."""
        if not any(kind in q for q in self.spans):
            return None
        return 1e3 * sum(q.get(kind, 0.0) for q in self.spans) / len(self.spans)

    def ingest_share_pct(self, *kinds: str) -> float | None:
        """Percent of the ingest window spent in program spans of ``kinds``;
        None where the traced hops hold none of them."""
        spans = (self.ingest or {}).get("spans", {})
        if self.window_s <= 0 or not any(k in spans for k in kinds):
            return None
        return 100.0 * sum(spans.get(k, 0.0) for k in kinds) / self.window_s


class CompileMeter:
    """Counts XLA programs built (``backend_compile`` events, which fire on a
    compile and on a persistent-cache load alike), the persistent-cache hits
    among them, the programs written to that cache, and every compile-path
    second."""

    BUILD = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    WRITE = "/jax/compilation_cache/cache_misses"  # fires as an entry is written

    def __init__(self):
        import jax

        self.programs = 0
        self.hits = 0
        self.written = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event == self.BUILD:
                self.programs += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.HIT:
            self.hits += 1
        elif event == self.WRITE:
            self.written += 1

    @property
    def compiled(self) -> int:
        """Programs built by compiling, not loaded from the persistent cache."""
        return self.programs - self.hits


def profile(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


class Run:
    """One run of one cell."""

    WARM_PASSES = 4

    def __init__(self, args, cfg: dict, mix: dict, t0: float, scratch: str):
        self.args, self.cfg, self.mix, self.t0 = args, cfg, mix, t0
        self.scratch = scratch
        self.trace_dir = scratch + "-trace"
        self.ctx = RunContext()
        self.meter = CompileMeter()
        self.checks: list[tuple[str, float, float]] = []
        self._t_start = 0.0
        self._programs0 = 0

    def warm_up(self, once) -> None:
        """Call ``once()``, the cell's whole warm-up, until a call after the
        first builds no program the calls before it lacked.  Where a call
        compiled, every program is dropped from memory before the next: the
        window then runs programs loaded from the persistent cache, as every
        later run in the checkout does, and never one compiled in-process."""
        import jax

        for i in range(self.WARM_PASSES):
            compiled, written = self.meter.compiled, self.meter.written
            with jax.profiler.TraceAnnotation("bench.warm_up"):
                once()
            if i and self.meter.compiled == compiled:
                break
            if self.meter.written > written:
                jax.clear_caches()
        self.ctx.setup_programs = self.meter.programs
        note(f"warm-up: {i + 1} passes, {self.meter.programs} programs built, "
             f"{self.meter.compiled} of them compiled, "
             f"{self.meter.seconds:.3f} s in compile paths")

    def open_window(self) -> float:
        if self.args.trace:
            profile(self.trace_dir)
        self._programs0 = self.meter.programs
        self._t_start = time.perf_counter()
        self.ctx.setup_s = self._t_start - self.t0
        return self._t_start

    def close_window(self) -> None:
        self.ctx.window_s = time.perf_counter() - self._t_start
        if self.args.trace:
            import jax

            jax.profiler.stop_trace()
        self.ctx.window_programs = self.meter.programs - self._programs0

    def reduce_trace(self) -> None:
        if not self.args.trace:
            return
        from .devtrace import find_xplane, reduce_file

        self.ctx.device = reduce_file(find_xplane(self.trace_dir), by_span=True)
        store.remove(self.trace_dir)

    def cleanup(self) -> None:
        for suffix in ("", "-warm", "-trace", "-crash"):
            store.remove(self.scratch + suffix)


def memory_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))
