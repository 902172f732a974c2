"""Reduce a JAX profiler trace to device busy time, kernel time and idle gaps.

The profiler writes an ``.xplane.pb`` file.  On a TPU its device plane
(``/device:TPU:<n>``) has an ``XLA Modules`` line, one event per program
execution (``jit_range_join_mask(<hash>)``), and an ``XLA Ops`` line, one
event per operation inside it (``%range_join_mask.1 = s32[...]
custom-call(...)``).  The host plane (``/host:CPU``) carries the harness's
``TraceAnnotation`` spans (``bench.window``, ``bench.query``, ...) and the
program's own (``dslog.query``, ``dslog.merge``, ...).  Device and host
events share one clock, in nanoseconds from the start of the trace.

* busy: the union of the device's module intervals inside the window;
* kernel time: the summed durations of the modules a name selects;
* idle gaps: the stretches between busy intervals, each labelled by the
  harness span that covers most of it on the host;
* idle by program span: every idle stretch charged to the innermost program
  span open on the host at that moment, or to none.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."
PROGRAM_PREFIX = "dslog."


@dataclass
class Reduced:
    """What the readers need from one trace, in seconds."""

    window_s: float
    busy_s: float  # averaged over the device planes
    n_devices: int
    module_s: dict = field(default_factory=dict)  # program name -> seconds
    op_s: dict = field(default_factory=dict)  # operation name -> seconds
    gaps: list = field(default_factory=list)  # [(label, seconds)], longest first
    # program span name (None: no span open) -> idle seconds; filled only
    # when asked for (``by_span``)
    idle_by_span: dict = field(default_factory=dict)

    def kernel_s(self, *prefixes: str) -> float:
        """Summed device seconds of the programs whose name starts so."""
        return sum(s for name, s in self.module_s.items()
                   if any(name.startswith(p) for p in prefixes))


def _program(name: str) -> str:
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def reduce_planes(planes, window: str = "bench.window", by_span: bool = False) -> Reduced:
    """Reduce profiler planes (``ProfileData.planes``, or any objects with
    ``name``/``lines``/``events``/``start_ns``/``duration_ns``).  Only with
    ``by_span`` do the program's ``dslog.*`` spans fill ``idle_by_span``;
    nothing else of the reduction reads them."""
    host_spans = []  # (start, end, name) of harness annotations
    program = []  # (start, end, name) of the program's spans
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(ANNOTATION_PREFIX):
                        s = float(ev.start_ns)
                        host_spans.append((s, s + float(ev.duration_ns), ev.name))
                    elif by_span and ev.name.startswith(PROGRAM_PREFIX):
                        s = float(ev.start_ns)
                        program.append((s, s + float(ev.duration_ns),
                                        ev.name[len(PROGRAM_PREFIX):]))
    win = [(s, e) for s, e, n in host_spans if n == window]
    if not win:
        raise ValueError(f"the trace holds no {window!r} span")
    w0, w1 = win[0]
    inner = sorted((s, e, n) for s, e, n in host_spans if n != window)
    starts = [s for s, _, _ in inner]
    module_s: dict = {}
    op_s: dict = {}
    busy_total = 0.0
    gaps = []
    stretches = _innermost([sp for sp in program if sp[1] > w0 and sp[0] < w1])
    idle_by_span: dict = {}
    for dev in devices:
        busy = []
        for line in dev.lines:
            if line.name not in (MODULES_LINE, OPS_LINE):
                continue
            for ev in line.events:
                s = float(ev.start_ns)
                e = s + float(ev.duration_ns)
                if e <= w0 or s >= w1:
                    continue
                s, e = max(s, w0), min(e, w1)
                if line.name == MODULES_LINE:
                    busy.append((s, e))
                    key = _program(ev.name)
                    module_s[key] = module_s.get(key, 0.0) + (e - s) * 1e-9
                else:
                    key = _op(ev.name)
                    op_s[key] = op_s.get(key, 0.0) + (e - s) * 1e-9
        merged = _merge(busy)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps.append((_label(inner, starts, g0, g1), (g1 - g0) * 1e-9))
                if by_span:
                    _charge(idle_by_span, stretches, g0, g1)
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n, n_devices=len(devices),
        module_s=module_s, op_s=op_s, gaps=gaps, idle_by_span=idle_by_span,
    )


def _label(spans, starts, g0: float, g1: float) -> str:
    """The harness span that covers most of the gap ``[g0, g1]``.

    The spans inside the window run one after another, so the ones a gap
    overlaps are found by bisecting their sorted starts.
    """
    overlap: dict = {}
    for s, e, n in spans[max(bisect.bisect_right(starts, g0) - 1, 0):
                         bisect.bisect_left(starts, g1)]:
        o = min(e, g1) - max(s, g0)
        if o > 0:
            overlap[n] = overlap.get(n, 0.0) + o
    label = max(overlap, key=overlap.get) if overlap else "bench.window"
    return label[len(ANNOTATION_PREFIX):]


def _innermost(spans) -> list:
    """Cut the time the spans cover into stretches ``[t0, t1, name]``, each
    named by the innermost span open in it: the one that started last (on
    a tie, the one that ends first).  Time no span covers is left out."""
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    spans = sorted(spans)
    open_: list = []  # heap of (-start, end, name); closed ones leave lazily
    out: list = []
    i = 0
    for t0, t1 in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= t0:
            s, e, name = spans[i]
            heapq.heappush(open_, (-s, e, name))
            i += 1
        while open_ and open_[0][1] <= t0:
            heapq.heappop(open_)
        if not open_:
            continue
        name = open_[0][2]
        if out and out[-1][1] == t0 and out[-1][2] == name:
            out[-1][1] = t1
        else:
            out.append([t0, t1, name])
    return out


def _charge(idle: dict, stretches: list, g0: float, g1: float) -> None:
    """Add the idle stretch ``[g0, g1]`` to ``idle``, split by the innermost
    program span over each part of it; what no span covers goes to None."""
    covered = 0.0
    k = max(bisect.bisect_right(stretches, g0, key=lambda st: st[0]) - 1, 0)
    for t0, t1, name in stretches[k:]:
        if t0 >= g1:
            break
        o = min(t1, g1) - max(t0, g0)
        if o > 0:
            idle[name] = idle.get(name, 0.0) + o * 1e-9
            covered += o
    if g1 - g0 > covered:
        idle[None] = idle.get(None, 0.0) + (g1 - g0 - covered) * 1e-9


def reduce_file(path: str, window: str = "bench.window", by_span: bool = False) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window, by_span)
