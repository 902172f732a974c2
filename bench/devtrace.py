"""Reduce a JAX profiler trace to device busy time, kernel time and idle gaps.

The profiler writes an ``.xplane.pb`` file.  On a TPU its device plane
(``/device:TPU:<n>``) has an ``XLA Modules`` line, one event per program
execution (``jit_range_join_mask(<hash>)``), and an ``XLA Ops`` line, one
event per operation inside it (``%range_join_mask.1 = s32[...]
custom-call(...)``).  The host plane (``/host:CPU``) carries the harness's
``TraceAnnotation`` spans (``bench.window``, ``bench.query``, ...).  Device
and host events share one clock, in nanoseconds from the start of the trace.

* busy: the union of the device's module intervals inside the window;
* kernel time: the summed durations of the modules a name selects;
* idle gaps: the stretches between busy intervals, each labelled by the
  harness span that covers most of it on the host.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ANNOTATION_PREFIX = "bench."


@dataclass
class Reduced:
    """What the readers need from one trace, in seconds."""

    window_s: float
    busy_s: float  # averaged over the device planes
    n_devices: int
    module_s: dict = field(default_factory=dict)  # program name -> seconds
    op_s: dict = field(default_factory=dict)  # operation name -> seconds
    gaps: list = field(default_factory=list)  # [(label, seconds)], longest first

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


def reduce_planes(planes, window: str = "bench.window") -> Reduced:
    """Reduce profiler planes (``ProfileData.planes``, or any objects with
    ``name``/``lines``/``events``/``start_ns``/``duration_ns``)."""
    host_spans = []  # (start, end, name) of harness annotations
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
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n, n_devices=len(devices),
        module_s=module_s, op_s=op_s, gaps=gaps,
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


def reduce_file(path: str, window: str = "bench.window") -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, window)
