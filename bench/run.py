"""Run one benchmark cell on the accelerator JAX finds, and check its answers.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  ``BENCHMARK.json`` names the cell's
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``); the mix names the loop that drives it
(``bench/loops/<loop>.py``), and each metric is read by
``bench/metrics/<metric>.py`` (a name with a suffix, ``<metric>.<part>``,
falls back to the reader of ``<metric>``).  A run loads, warms up, measures
for ``--seconds``, checks what the window produced against the plain
reference, and prints one JSON line last.  With ``--trace 1`` the window
runs under the JAX profiler and the line carries the per-layer metrics; with
``--trace 0`` it carries the end-to-end ones.

JAX keeps its persistent compilation cache in ``JAX_COMPILATION_CACHE_DIR``
where that is set, and in ``bench/.jax_cache`` otherwise.  Without a TPU, or
with fewer chips than the cell asks for, the run exits with 2 and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from . import loops, store  # noqa: E402
from .context import Run, note, steady_allocator  # noqa: E402
from .mix import load_mix  # noqa: E402
from .workflows import load_config  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
METRICS_DIR = os.path.join(BENCH, "metrics")
BENCHMARK_FILE = os.path.join(ROOT, "BENCHMARK.json")
COMPILE_CACHE = os.path.join(BENCH, ".jax_cache")


def reader_path(name: str) -> str:
    path = os.path.join(METRICS_DIR, f"{name}.py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(METRICS_DIR, f"{name.rsplit('.', 1)[0]}.py")
    return path


def _reader(name: str):
    spec = importlib.util.spec_from_file_location(f"bench.metrics.{name}",
                                                  reader_path(name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _cell_metrics(bench: dict, workload: str, trace: bool) -> list[dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if workload in m.get("workloads", [workload])]


def _accelerators() -> list:
    import jax

    return jax.devices()


def _breakdown(red) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:10]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    steady_allocator()

    with open(BENCHMARK_FILE) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"bench.run: no workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("bench.run: the program under test (src/repro) is missing",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE)
    devices = _accelerators()
    if devices[0].platform != "tpu" or len(devices) < int(cell["chips"]):
        print(f"bench.run: the cell needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = load_config(cell["config"])
    mix = load_mix(cell["traffic"])
    dev = devices[0]
    note(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}; workload {args.workload} seed {args.seed}; "
         f"compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}")
    r = Run(args, cfg, mix, T0, os.path.join(store.STORES, f".run-{os.getpid()}"))
    try:
        attempted, failed, mem = loops.get(mix["loop"]).run(r)
    finally:
        r.cleanup()
    # every shape the window uses was built in the warm-up
    r.checks.append(("window_programs", r.ctx.window_programs, 0))

    metrics = {}
    for m in _cell_metrics(bench, args.workload, bool(args.trace)):
        value = _reader(m["name"])(r.ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": mem}
    result = {"correct": all(v <= lim for _, v, lim in r.checks),
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace and r.ctx.device is not None:
        device["busy_s"] = r.ctx.device.busy_s
        device["window_s"] = r.ctx.device.window_s
        result["breakdown"] = _breakdown(r.ctx.device)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in r.checks}
    for n, v, lim in r.checks:
        print(f"check {n}={v} limit={lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
