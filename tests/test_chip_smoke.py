"""chip_smoke.py rehearsed on the CPU: its refusal, and its checks at a tiny size.

On the CPU the dense joins run on the numpy twin, so the chip-only checks
of ``main`` (compiled launches, ``batched(tpu:…)`` plans) are not reached;
``run`` still drives the whole ingest → reopen → query path and compares
every answer with the uncompressed-row oracle.
"""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke

    return chip_smoke


def test_chip_smoke_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line
    assert "needs a TPU" in captured.err


def test_chip_smoke_run_matches_oracle_on_cpu(chip_smoke):
    lines = []
    rep = chip_smoke.run(side=48, cells=128, out=lines.append)
    # 5 workloads x (forward, backward) x (first, warm), each oracle-checked
    assert rep["queries_checked"] == 20 and len(lines) == 20
    assert set(rep["phases"]) == {
        "ingest_s", "ingest_close_s", "reopen_s",
        "first_queries_s", "warm_queries_s",
    }
    # the twin ran every dense dispatch here, and says so
    assert rep["kernel_launches"] > 0
    assert rep["twin_launches"] == rep["kernel_launches"]
    assert rep["max_mean_joins_per_launch"] >= 16
    assert rep["batch_tiles_skipped"] > 0
    assert any("batched(np:cpu" in p for p in rep["plans"])
