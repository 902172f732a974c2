"""The program's own spans and counters, as the benchmark meets them."""

from types import SimpleNamespace as NS

import pytest

from bench import devtrace, run
from bench.context import RunContext


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes(program_spans):
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[
            _ev("bench.window", 1000, 9000),
            _ev("bench.query", 1000, 4000),
            _ev("bench.query", 5000, 5000),
        ]),
        NS(name="program", events=program_spans),
    ])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[
        _ev("jit_range_join_mask(1)", 2000, 1500),
        _ev("jit_range_join_mask(2)", 6000, 500),
    ])])
    return [host, dev]


def test_program_annotations_leave_the_harness_reduction_unchanged():
    """``dslog.*`` spans nested under ``bench.query`` (the program's
    ``TraceAnnotation``s) change neither the busy time nor a gap or its
    label: the reduction reads ``bench.*`` annotations alone."""
    nested = [
        _ev("dslog.query", 1000, 3900),
        _ev("dslog.execute", 1200, 3600),
        _ev("dslog.wave", 1300, 3400),
        _ev("dslog.pack", 1400, 500),
        _ev("dslog.readback", 3500, 600),
        _ev("dslog.query", 5100, 4800),
        _ev("dslog.merge", 6600, 3000),
    ]
    plain = devtrace.reduce_planes(_planes([]))
    traced = devtrace.reduce_planes(_planes(nested))
    assert traced == plain
    assert [(label, round(s * 1e9)) for label, s in traced.gaps] == [
        ("query", 3500), ("query", 2500), ("query", 1000)]


@pytest.mark.parametrize("counters,want", [
    ({"d2h_bytes": 4096, "join_pairs": 64}, 64.0),
    ({"d2h_bytes": 4096, "join_pairs": 0}, None),  # no dense join ran
    ({"kernel_launches": 3}, None),  # a program without the counters
])
def test_d2h_bytes_per_pair_reader(counters, want):
    ctx = RunContext(counters=counters, latencies_s=[0.01, 0.02])
    assert run._reader("d2h_bytes_per_pair")(ctx) == want
