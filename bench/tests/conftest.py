"""Run benchmark cells on the CPU at a small size, inside a test.

The harness refuses to run without a TPU; these fixtures steer it past that
check, shrink the configurations, keep stores in a temporary directory, and
route the dense joins through the Pallas kernels in interpret mode (the path
the chip compiles), so ``twin_launches`` stays 0 as it does on the chip.
"""

from __future__ import annotations

import json
import os

import pytest

from bench import run, store, workflows


def declared_test_side(cfg: dict) -> int:
    """The side a configuration declares for CPU tests (``test_side``)."""
    if "test_side" not in cfg:
        raise AssertionError(f"config {cfg['name']} declares no test_side")
    return int(cfg["test_side"])


class FakeTPU:
    platform = "tpu"
    device_kind = "TPU (stand-in for a CPU test)"


@pytest.fixture
def small_bench(tmp_path, monkeypatch):
    """Shrunk copies of the configurations, stores under tmp_path.

    Each configuration runs at the side it declares as ``test_side``.

    Returns ``run_cell(workload, seed, seconds=0.5, trace=0)``, which gives
    ``(exit code, result dict or None, stderr text)``.
    """
    cfg_dir = tmp_path / "configs"
    cfg_dir.mkdir()
    for name in os.listdir(workflows.CONFIG_DIR):
        with open(os.path.join(workflows.CONFIG_DIR, name)) as f:
            cfg = json.load(f)
        cfg["side"] = declared_test_side(cfg)
        (cfg_dir / name).write_text(json.dumps(cfg))
    monkeypatch.setattr(workflows, "CONFIG_DIR", str(cfg_dir))
    monkeypatch.setattr(store, "STORES", str(tmp_path / "stores"))
    monkeypatch.setattr(run, "_accelerators", lambda: [FakeTPU()])
    # the persistent compile cache stays out of the checkout and the test
    # process's JAX configuration stays as it was
    monkeypatch.setattr(run, "COMPILE_CACHE", str(tmp_path / "jax_cache"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    from repro import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")

    from repro.core import query

    init = query.BatchedJoinExecutor.__init__

    def kernel_engine(self, *a, **kw):
        init(self, *a, **{**kw, "engine": "kernel"})

    monkeypatch.setattr(query.BatchedJoinExecutor, "__init__", kernel_engine)

    def run_cell(workload, seed, seconds=0.5, trace=0, capsys=None):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)])
        out, err = capsys.readouterr() if capsys else ("", "")
        lines = [ln for ln in out.splitlines() if ln.startswith("{")]
        return rc, (json.loads(lines[-1]) if lines else None), err

    return run_cell
