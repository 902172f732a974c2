"""Query cells: a sound run is correct; a planted fault makes it incorrect."""

import json

import pytest

from bench import run
from bench.loops import closed
from bench.tests import faults


@pytest.mark.parametrize("workload,fault", [
    ("numpy.fig89_forward", None),
    ("numpy.fig89_forward", "approximate"),
    ("numpy.fig89_forward", "drop_box"),
    ("conv.fig89_forward", None),
    ("conv.fig89_forward", "approximate"),
    ("conv.fig89_forward", "drop_box"),
])
def test_correct_catches_the_fault(small_bench, capsys, monkeypatch, workload, fault):
    if fault:
        faults.plant(fault, monkeypatch.setattr)
    rc, res, err = small_bench(workload, 2**31 + 7, trace=0, capsys=capsys)
    assert rc == 0 and res is not None
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert "check wrong_answers=" in err
    assert res["checks"]["window_programs"] == {"value": 0, "limit": 0}
    assert res["device"]["platform"] == "tpu"
    with open(run.BENCHMARK_FILE) as f:
        want = {m["name"] for m in json.load(f)["end_to_end"]
                if workload in m.get("workloads", [workload])}
    assert set(res["metrics"]) == want


@pytest.mark.parametrize("workload", ["numpy.fig89_forward", "conv.fig89_forward"])
def test_traced_run_reports_the_cells_layers(small_bench, capsys, monkeypatch, workload):
    kinds = set()  # span kinds the window's queries reached
    real = closed.add_span_seconds

    def recorded(into, tr):
        out = real(into, tr)
        kinds.update(out)
        return out

    monkeypatch.setattr(closed, "add_span_seconds", recorded)
    rc, res, _ = small_bench(workload, 2**31 + 13, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]
    with open(run.BENCHMARK_FILE) as f:
        bench = json.load(f)
    e2e = {m["name"] for m in bench["end_to_end"] if workload in m.get("workloads", [workload])}
    want = {m["name"] for m in bench["per_layer"]
            if workload in m.get("workloads", [workload]) and m["moves"] in e2e}
    # the CPU trace has no device plane, so kernel device time and idle time
    # have nothing to read; index_probe_ms reads the index route alone,
    # which tables this small take only on some seeds' queries
    if "index_probe" not in kinds:
        want -= {"index_probe_ms"}
    assert set(res["metrics"]) == want - {"range_join_device_ms", "idle_unattributed_pct"}
    assert {"busy_s", "window_s"} <= set(res["device"])
