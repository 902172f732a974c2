"""The harness refuses to run without a chip, and finds a cell's files by name."""

import json
import os
import shutil

import pytest

from bench import run, store, workflows
from bench.tests.conftest import declared_test_side


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


CONFIG_FILES = sorted(f for f in os.listdir(workflows.CONFIG_DIR) if f.endswith(".json"))


@pytest.mark.parametrize("name", CONFIG_FILES)
def test_every_config_declares_its_test_side(name):
    """The CPU tests run each configuration at the side it declares."""
    with open(os.path.join(workflows.CONFIG_DIR, name)) as f:
        assert declared_test_side(json.load(f)) > 0


def test_a_config_without_test_side_is_named():
    with pytest.raises(AssertionError, match="config bare declares no test_side"):
        declared_test_side({"name": "bare", "side": 1024})


@pytest.fixture
def one_more_config(tmp_path, monkeypatch):
    """The committed configurations plus one new file, ``added``, and a
    benchmark that gives it a cell: nothing else under ``bench/`` changes."""
    src = tmp_path / "configs_committed"
    shutil.copytree(workflows.CONFIG_DIR, src)
    with open(src / "fig89_numpy.json") as f:
        cfg = json.load(f)
    cfg.update(name="added", test_side=24, pipelines=cfg["pipelines"][:1])
    (src / "added.json").write_text(json.dumps(cfg))
    monkeypatch.setattr(workflows, "CONFIG_DIR", str(src))
    with open(run.BENCHMARK_FILE) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "added", "source": cfg["source"],
                             "file": "bench/configs/added.json", "reduced": [],
                             "why": "a configuration added as a file alone"})
    bench["workloads"].append({"name": "added.fig89_forward", "config": "added",
                               "traffic": "fig89_forward", "chips": 1,
                               "why": "the Figs 8/9 mix on the added configuration"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(run, "BENCHMARK_FILE", str(tmp_path / "BENCHMARK.json"))


def test_a_new_config_file_runs_with_no_other_edit(one_more_config, small_bench, capsys):
    rc, res, _ = small_bench("added.fig89_forward", 2**31 + 21, capsys=capsys)
    assert rc == 0 and res["correct"], res and res["checks"]
    assert workflows.load_config("added")["side"] == 24
    assert [n for n in os.listdir(store.STORES) if n.startswith("added-")]
