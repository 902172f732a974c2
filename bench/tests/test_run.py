"""The harness refuses to run without a chip."""

import json

from bench import run


def test_refuses_without_a_tpu(capsys, monkeypatch, tmp_path):
    monkeypatch.setattr(run, "COMPILE_CACHE", str(tmp_path))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = run.main(["--workload", "numpy.fig89_forward", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "TPU" in err
    assert not [ln for ln in out.splitlines() if ln.startswith("{")]


def test_every_cell_names_files_that_exist():
    import os

    with open(run.BENCHMARK_FILE) as f:
        bench = json.load(f)
    from bench import layouts, loops, mix, ops, workflows

    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(run.reader_path(m["name"]))
    for w in bench["workloads"]:
        cfg = workflows.load_config(w["config"])
        m = mix.load_mix(w["traffic"])
        assert loops.get(m["loop"]).run
        for cls in m.get("classes", []):
            assert layouts.get(cls["cells"]).draw
        for pipe in cfg["pipelines"]:
            for spec in pipe["ops"]:
                assert ops.get(spec["op"]).rows


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ exits non-zero
    and prints no result."""
    import os
    import shutil
    import subprocess
    import sys

    shutil.copy(run.BENCHMARK_FILE, tmp_path / "BENCHMARK.json")
    shutil.copytree(run.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".stores", ".jax_cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "numpy.fig89_forward",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
