"""Drive DSLog's ingest-and-query path once on one TPU chip, and check it.

    python chip_smoke.py [--side 1024] [--cells 4096] [--seed 0]

The path is the one a user takes: ``DSLog.open`` with group commit →
``add_lineage`` → ``commit`` / ``close`` → reopen → ``prov_query`` in graph
form, with the dense θ-joins of every plan frontier packed into one Pallas
kernel launch.  The data is made from ``--seed``:

* the Figs 8/9 workflows of the paper at ``side × side`` cells per array
  (the image and ResNet workflows, a 5-op and a 10-op random numpy
  pipeline) — ``side=1024`` is about 10⁶ cells, the paper's largest;
* a wide fan-in DAG of ``BRANCHES`` permutation chains, whose every plan
  wave packs ``BRANCHES`` dense joins into one launch.

Each workflow answers a forward query over ``--cells`` contiguous cells and
a backward query over ``--cells`` scattered ones, twice (the second pass
with fresh cells, warm), and the DAG a forward and a backward query.  Every
answer must equal the hash join over the uncompressed lineage rows.  On the
chip the dense joins must all have run as compiled kernels: launches > 0,
no numpy-twin dispatch, ``batched(tpu:…)`` hops in the plans, and
block-diagonal launches (skipped tiles > 0) beside the dense ones.

Earlier lines give phase wall times, launches and joins per launch; the
last line is ``{"ok": true, "device": {...}}``.  Without a TPU the script
exits with 1 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
BRANCHES = 32  # permutation chains of the fan-in DAG: 32 joins per wave


def _cells(flat: np.ndarray, shape) -> np.ndarray:
    return np.stack(np.unravel_index(flat, shape), axis=1)


def _workloads(side: int, seed: int):
    """``[(prefix, edges)]``: each workload's ``(src, dst, relation)`` edges."""
    from benchmarks.fig89_query import (
        _accel_dag_edges,
        _image_workflow,
        _random_workflow,
        _resnet_workflow,
    )

    flows = [
        _image_workflow(side),
        _resnet_workflow(side),
        _random_workflow(5, seed, n_cells=side * side),
        _random_workflow(10, 100 + seed, n_cells=side * side),
    ]
    out = []
    for name, rels in flows:
        names = [f"{name}_a{k}" for k in range(len(rels) + 1)]
        out.append((name, [(names[k], names[k + 1], r) for k, r in enumerate(rels)]))
    dag = _accel_dag_edges((32, 31), BRANCHES, hops=2, seed=seed)
    out.append(("accel", [(f"accel_{s}", f"accel_{d}", r) for s, d, r in dag]))
    return out


def _paths(edges, src: str, dst: str):
    """Every relation chain from ``src`` to ``dst`` (the DAG's branches)."""
    nxt: dict[str, list] = {}
    for s, d, r in edges:
        nxt.setdefault(s, []).append((d, r))
    if src == dst:
        return [[]]
    return [
        [r] + rest
        for d, r in nxt.get(src, [])
        for rest in _paths(edges, d, dst)
    ]


def _oracle(edges, src: str, dst: str, cells: np.ndarray, forward: bool):
    """Raveled answer cells by hash join over the uncompressed rows."""
    from benchmarks.fig89_query import _backward_join_rows, _forward_join_rows

    a, b = (src, dst) if forward else (dst, src)
    hits = [
        (_forward_join_rows if forward else _backward_join_rows)(p, cells)
        for p in _paths(edges, a, b)
    ]
    return np.unique(np.concatenate(hits))


def run(side=1024, cells=4096, seed=0, out=print) -> dict:
    """Ingest, reopen and query; raises on a wrong answer.  Returns a report."""
    from repro.core.catalog import DSLog

    rng = np.random.default_rng(seed)
    report: dict = {"phases": {}, "plans": []}
    phases = report["phases"]
    loads = _workloads(side, seed)
    with tempfile.TemporaryDirectory() as root:
        t0 = time.perf_counter()
        with DSLog.open(root, durability="group", store_forward=True) as log:
            for _, edges in loads:
                for src, dst, rel in edges:
                    for name, shape in ((src, rel.in_shape), (dst, rel.out_shape)):
                        if name not in log.arrays:
                            log.define_array(name, shape)
                    log.add_lineage(src, dst, rel)
            log.commit()
            phases["ingest_s"] = time.perf_counter() - t0
        phases["ingest_close_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        log = DSLog.open(root, store_forward=True)
        phases["reopen_s"] = time.perf_counter() - t0
        try:
            queries = []  # (prefix, edges, src, dst, forward)
            for prefix, edges in loads:
                first, last = edges[0][0], edges[-1][1]
                if prefix == "accel":
                    first, last = "accel_src", "accel_out"
                # backward from the last array that holds the query cells:
                # the workflows end in reductions to a handful of cells
                big = [d for _, d, r in edges
                       if int(np.prod(r.out_shape)) >= 3 * cells]
                queries += [
                    (prefix, edges, first, last, True),
                    (prefix, edges, big[-1] if big else last, first, False),
                ]
            base = dict(log.io_stats)
            # the largest per-query mean of joins per launch
            max_mean = 0.0
            for label in ("first", "warm"):
                t0 = time.perf_counter()
                n_checked = 0
                for prefix, edges, src, dst, forward in queries:
                    shape = log.arrays[src].shape
                    n = int(np.prod(shape))
                    k = min(cells, n // 3)
                    if forward:  # a contiguous run of cells: few boxes
                        start = int(rng.integers(0, n - k))
                        flat = np.arange(start, start + k)
                    else:  # scattered cells: one box each
                        flat = np.sort(rng.choice(n, size=k, replace=False))
                    q = _cells(flat, shape)
                    before = dict(log.io_stats)
                    tq = time.perf_counter()
                    got = log.prov_query(src, dst, q)
                    dt = time.perf_counter() - tq
                    launches = (log.io_stats["kernel_launches"]
                                - before["kernel_launches"])
                    joins = log.io_stats["joins_packed"] - before["joins_packed"]
                    if launches:
                        max_mean = max(max_mean, joins / launches)
                    want = _oracle(edges, src, dst, q, forward)
                    got_flat = np.unique(
                        np.ravel_multi_index(got.cells().T, got.shape)
                    )
                    if not np.array_equal(got_flat, want):
                        raise AssertionError(
                            f"{prefix} {src}->{dst} ({label}): answer differs "
                            f"from the uncompressed-row oracle "
                            f"({got_flat.size} vs {want.size} cells)"
                        )
                    n_checked += 1
                    out(f"query {label} {prefix} {'fwd' if forward else 'bwd'} "
                        f"cells={k} answer_cells={want.size} "
                        f"launches={launches} joins={joins} wall_s={dt:.6f}")
                phases[f"{label}_queries_s"] = time.perf_counter() - t0
                report["queries_checked"] = (
                    report.get("queries_checked", 0) + n_checked
                )
            for key in ("kernel_launches", "twin_launches", "joins_packed",
                        "batch_tiles_visited", "batch_tiles_skipped"):
                report[key] = log.io_stats[key] - base[key]
            for _, _, src, dst, _ in queries:
                report["plans"].append(log.planner.plan(src, [dst]).describe())
        finally:
            log.close()
    report["joins_per_launch"] = report["joins_packed"] / max(
        report["kernel_launches"], 1
    )
    report["max_mean_joins_per_launch"] = max_mean
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--side", type=int, default=1024,
                    help="array side of the Figs 8/9 workflows")
    ap.add_argument("--cells", type=int, default=4096,
                    help="query cells per prov_query call")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from repro.compile_cache import enable_compile_cache

    print(f"device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"sizes: side={args.side} cells={args.cells} "
          f"branches={BRANCHES} seed={args.seed}", flush=True)
    rep = run(args.side, args.cells, args.seed,
              out=lambda s: print(s, flush=True))
    for name, sec in rep["phases"].items():
        print(f"phase {name}={sec:.6f}")
    print(f"queries_checked={rep['queries_checked']} "
          f"kernel_launches={rep['kernel_launches']} "
          f"twin_launches={rep['twin_launches']} "
          f"joins_packed={rep['joins_packed']} "
          f"joins_per_launch={rep['joins_per_launch']:.3f} "
          f"max_mean_joins_per_launch={rep['max_mean_joins_per_launch']:.3f} "
          f"batch_tiles_visited={rep['batch_tiles_visited']} "
          f"batch_tiles_skipped={rep['batch_tiles_skipped']}")
    notes = [ln.strip() for p in rep["plans"] for ln in p.splitlines()
             if "batched(" in ln]
    for ln in notes[:8]:
        print(f"plan hop: {ln}")
    failures = []
    if rep["kernel_launches"] <= rep["twin_launches"]:
        failures.append("no compiled kernel launch")
    if rep["twin_launches"]:
        failures.append(f"{rep['twin_launches']} dense dispatches ran on the "
                        "numpy twin")
    if not any("batched(tpu:" in ln for ln in notes):
        failures.append("no batched(tpu:...) hop in the plans")
    if any("batched(np:" in ln for ln in notes):
        failures.append("a plan routes a batched hop to numpy")
    if rep["max_mean_joins_per_launch"] < 16:
        failures.append("no launch packed 16 joins "
                        f"(best mean: {rep['max_mean_joins_per_launch']:.1f})")
    if rep["batch_tiles_skipped"] <= 0:
        failures.append("no block-diagonal launch skipped a tile")
    if failures:
        print("chip_smoke FAILED: " + "; ".join(failures), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
