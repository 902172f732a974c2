"""The ingest cell: a sound run is correct; a planted fault makes it incorrect."""

import pytest

from bench.tests import faults


@pytest.mark.parametrize("fault", [None, "unwritten_log", "skip_add", "half_rows",
                                   "drop_box", "skip_fsync"])
def test_correct_catches_the_fault(small_bench, capsys, monkeypatch, fault):
    if fault:
        faults.plant(fault, monkeypatch.setattr)
    rc, res, err = small_bench("numpy.ingest", 2**31 + 9, trace=0, capsys=capsys)
    assert rc == 0 and res is not None
    assert res["correct"] is (fault is None), res["checks"]
    assert res["attempted"] > 0
    assert {"ingest_rows_per_s", "setup_s"} <= set(res["metrics"])


def test_a_box_missing_from_a_stored_table_is_caught(small_bench, capsys, monkeypatch):
    """A table that loses one stored box after the write reads short."""
    from repro.core import catalog

    load = catalog.DSLog.load

    def lossy(root):
        log = load(root)
        e = log.lineage[max(log.lineage)]
        e.forward.key_hi[-1] = e.forward.key_lo[-1] - 1  # the last box covers nothing
        return log

    monkeypatch.setattr(catalog.DSLog, "load", staticmethod(lossy))
    rc, res, _ = small_bench("numpy.ingest", 5, trace=0, capsys=capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["tables_not_covering_their_rows"]["value"] > 0


def test_traced_ingest_reports_its_layers(small_bench, capsys):
    rc, res, _ = small_bench("numpy.ingest", 3, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"]
    assert {"compress_share_pct", "commit_wait_share_pct", "jit_compiles",
            "device_idle_pct.ingest"} <= set(res["metrics"])
    assert "window_s" in res["device"]
