"""The trace reduction gives known numbers."""

import os
from types import SimpleNamespace as NS

import pytest

from bench import devtrace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)), "testdata")


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def test_busy_kernel_time_and_gaps_of_a_synthetic_trace():
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[
        _ev("bench.window", 1000, 9000),
        _ev("bench.query", 1000, 4000),
        _ev("bench.query", 5000, 5000),
        _ev("unrelated", 0, 20000),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_range_join_mask(123)", 2000, 1000),
            _ev("jit_range_join_mask(456)", 2500, 1000),  # overlaps the first
            _ev("jit_other(7)", 6000, 500),
            _ev("jit_range_join_mask(8)", 9500, 1000),  # runs past the window
        ]),
        NS(name="XLA Ops", events=[
            _ev("%range_join_mask.1 = s32[8,8] custom-call()", 2000, 800),
            _ev("%range_join_mask.3 = s32[8,8] custom-call()", 2500, 700),
            _ev("%fusion.2 = s32[8] fusion()", 6000, 400),
        ]),
    ])
    red = devtrace.reduce_planes([host, dev])
    assert red.window_s == pytest.approx(9e-6)
    # busy: [2000, 3500] + [6000, 6500] + [9500, 10000]
    assert red.busy_s == pytest.approx(2.5e-6)
    assert red.kernel_s("jit_range_join_mask") == pytest.approx(2.5e-6)
    assert red.op_s["range_join_mask"] == pytest.approx(1.5e-6)
    # gaps: [1000,2000] query, [3500,6000] query, [6500,9500] query
    assert [round(s * 1e9) for _, s in red.gaps] == [3000, 2500, 1000]
    assert {label for label, _ in red.gaps} == {"query"}


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite by ``make_testdata.py``: six
    range-join launches, each under a ``bench.query`` span inside
    ``bench.window``."""
    path = os.path.join(TESTDATA, "v5e_range_join.xplane.pb")
    red = devtrace.reduce_file(path)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.013145178, abs=1e-9)
    assert red.busy_s == pytest.approx(6.3976e-05, abs=1e-10)
    assert red.module_s == {"jit_range_join_mask": pytest.approx(6.3976e-05, abs=1e-10)}
    assert red.op_s["range_join_mask"] == pytest.approx(5.121e-05, abs=1e-10)
    assert [label for label, _ in red.gaps] == ["query"] * 7
    assert red.gaps[0][1] == pytest.approx(0.002434156, abs=1e-9)
    assert red.busy_s + sum(s for _, s in red.gaps) == pytest.approx(red.window_s, rel=1e-6)
