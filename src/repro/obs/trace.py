"""Structured tracing: span trees on the profiler's clock.

A :class:`QueryTrace` is installed as ``log._active_trace`` by
:meth:`DSLog.trace_scope` (``prov_query(..., trace=True)`` runs under
one).  Hot paths check ``self._active_trace is not None`` — a single
attribute load — so the tracing-off cost is effectively zero and is
bounded by a microbenchmark in ``tests/test_obs.py``.

Spans form a tree rooted at the scope's label (``query`` for
``prov_query``).  Each span records wall time (``perf_counter``) and the
counts its stage produced, as ``attrs``.  While a span is open it also
holds a ``jax.profiler.TraceAnnotation("dslog.<name>")``: under a
profiler session every span lands on the host plane of the device
trace, on the device events' clock, so device-idle gaps can be charged
to the host stage that was running.  Outside a session an annotation
costs about a microsecond and records nothing.  Worker threads
(``prov_query(..., parallel=N)``, the group-commit flusher) have no span
stack of their own; their spans attach to the root, which keeps the
tree race-free without cross-thread coordination.

The span-stack lock is minted through ``repro.core._locks`` (name
``trace._lock``, rank 90 — a leaf above ``metrics._lock``) so the
dynamic race detector watches it too.
"""

from __future__ import annotations

import contextlib
import threading
import time
from contextlib import contextmanager
from typing import Iterator

__all__ = ["ANNOTATION_PREFIX", "QueryTrace", "Span", "maybe_span"]

# profiler annotations of program spans: "dslog.<span name>"
ANNOTATION_PREFIX = "dslog."


class Span:
    __slots__ = ("name", "kind", "attrs", "start", "duration", "children")

    def __init__(self, name: str, kind: str = "", attrs: dict | None = None) -> None:
        self.name = name
        self.kind = kind
        self.attrs = attrs or {}
        self.start = 0.0
        self.duration: float | None = None
        self.children: list[Span] = []

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "kind": self.kind,
            "attrs": self.attrs,
            "duration_ms": None if self.duration is None else self.duration * 1e3,
            "children": [c.to_dict() for c in self.children],
        }

    def walk(self) -> Iterator["Span"]:
        yield self
        for child in self.children:
            yield from child.walk()


class _Discard(dict):
    """A mapping that keeps nothing written to it."""

    __slots__ = ()

    def __setitem__(self, key, value) -> None:
        pass

    def update(self, *args, **kwargs) -> None:
        pass


class _NullSpan:
    """Span stand-in so untraced code can set ``sp.attrs``; one shared
    instance, so the untraced path allocates nothing per stage."""

    __slots__ = ("attrs",)

    def __init__(self) -> None:
        self.attrs: dict = _Discard()


_NULL_SPAN = _NullSpan()


class _NullSpanCtx:
    __slots__ = ()

    def __enter__(self) -> _NullSpan:
        return _NULL_SPAN

    def __exit__(self, *exc) -> bool:
        return False


_NULL_CTX = _NullSpanCtx()


def maybe_span(trace: "QueryTrace | None", name: str, kind: str = "", **attrs):
    """``trace.span(...)`` when tracing, a no-op context otherwise."""
    if trace is None:
        return _NULL_CTX
    return trace.span(name, kind=kind, **attrs)


def _annotation_type():
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover - standalone use without jax
        return lambda name: contextlib.nullcontext()
    return TraceAnnotation


class QueryTrace:
    """Span tree for one query or one traced block of work.

    Used as a context manager, the root span holds its own annotation
    (``dslog.<label>``) from ``__enter__`` to ``__exit__``.
    """

    def __init__(self, label: str = "query") -> None:
        try:
            from repro.core import _locks

            self._lock = _locks.new_lock("trace._lock")
        except ImportError:  # pragma: no cover - standalone use
            self._lock = threading.Lock()
        self._tls = threading.local()
        self._annotation = _annotation_type()
        self._root_annotation = None
        self.root = Span(label, kind=label)
        self.root.start = time.perf_counter()

    def __enter__(self) -> "QueryTrace":
        self._root_annotation = self._annotation(ANNOTATION_PREFIX + self.root.name)
        self._root_annotation.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self.finish()
        self._root_annotation.__exit__(*exc)
        return False

    # -- span stack (per thread) -----------------------------------------

    def _stack(self) -> list[Span]:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    def current(self) -> Span:
        stack = self._stack()
        return stack[-1] if stack else self.root

    def _attach(self, parent: Span, span: Span) -> None:
        with self._lock:
            parent.children.append(span)

    # -- recording API ----------------------------------------------------

    @contextmanager
    def span(self, name: str, kind: str = "", **attrs):
        """Open a child span, annotated on the profiler's timeline."""
        sp = Span(name, kind=kind, attrs=attrs)
        parent = self.current()
        stack = self._stack()
        stack.append(sp)
        with self._annotation(ANNOTATION_PREFIX + name):
            sp.start = time.perf_counter()
            try:
                yield sp
            finally:
                sp.duration = time.perf_counter() - sp.start
                stack.pop()
                self._attach(parent, sp)

    def event(self, name: str, kind: str = "", **attrs) -> Span:
        """Record an instant leaf (no duration) under the current span."""
        sp = Span(name, kind=kind, attrs=attrs)
        self._attach(self.current(), sp)
        return sp

    def finish(self) -> "QueryTrace":
        if self.root.duration is None:
            self.root.duration = time.perf_counter() - self.root.start
        return self

    # -- inspection -------------------------------------------------------

    def spans(self, kind: str | None = None) -> list[Span]:
        return [s for s in self.root.walk() if kind is None or s.kind == kind]

    def kinds(self) -> set[str]:
        return {s.kind for s in self.root.walk() if s.kind}

    def to_dict(self) -> dict:
        return self.finish().root.to_dict()

    def render(self, max_depth: int = 8) -> str:
        """Indented tree view of the trace."""
        self.finish()
        lines: list[str] = []

        def fmt(span: Span, depth: int) -> None:
            if depth > max_depth:
                return
            dur = "" if span.duration is None else f" {span.duration * 1e3:.3f}ms"
            attrs = ""
            if span.attrs:
                attrs = " " + " ".join(f"{k}={v}" for k, v in span.attrs.items())
            lines.append(f"{'  ' * depth}{span.name}{dur}{attrs}")
            for child in span.children:
                fmt(child, depth + 1)

        fmt(self.root, 0)
        return "\n".join(lines)
