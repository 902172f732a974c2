"""Figs 8/9 analog: multi-hop forward-query latency vs selectivity.

Workflows: image-like (5 steps), relational-like (5 steps), ResNet-block
(7 steps), and randomly generated numpy pipelines (5 and 10 ops).

Methods:
  * ``dslog``         — in-situ θ-joins over ProvRC tables (this paper),
  * ``dslog_nomerge`` — ablation without the between-hop row merge,
  * ``raw``           — hash-join over uncompressed rows,
  * ``parquet_like``  — decode the columnar blobs, then hash-join,
  * ``rle_like``      — decode RLE blobs, then hash-join,
  * ``array``         — vectorized equality scan (np.isin) per hop.

``run_dag_ablation`` extends the figure beyond the paper: a diamond
pipeline (fan-out, fan-in, shared heavy tail) queried through the
cost-based planner (one plan over the DAG, frontiers merged at the fan-in
array) vs the naive per-path union (one path query per simple path, results
unioned), plus the lazy-persistence measurement: reloading the catalog and
counting how many table blobs one query actually deserializes.
"""

from __future__ import annotations

import os
import tempfile
import time

import numpy as np

from repro.core import capture as C
from repro.core.catalog import DSLog
from repro.core.provrc import compress
from repro.core.query import QueryBox, merge_boxes, theta_join, theta_join_batch
from repro.core.relation import LineageRelation

from .baselines import (
    decode_parquet_like,
    decode_rle_like,
    encode_parquet_like,
    encode_rle_like,
)

__all__ = [
    "build_workflows",
    "run_fig89",
    "run_index_ablation",
    "run_dag_ablation",
    "run_shard_ablation",
    "run_wal_ablation",
    "run_accel_ablation",
]


# --------------------------------------------------------------------------- #
# Workflow construction
# --------------------------------------------------------------------------- #
def _image_workflow(side=256):
    h = side
    rels = [
        C.slice_lineage((h, h), (0, 0), (h, h), (2, 2)),
        C.identity_lineage((h // 2, h // 2)),
        C.transpose_lineage((h // 2, h // 2), (1, 0)),
        C.flip_lineage((h // 2, h // 2), 1),
        C.reduce_lineage((h // 2, h // 2), 1),
    ]
    return "image", rels


def _relational_workflow(n=20_000):
    rng = np.random.default_rng(3)
    lk = rng.integers(0, n // 2, n)
    rk = rng.integers(0, n // 2, n // 2)
    join_l, _ = C.inner_join_lineage(lk, rk, 3, 2)
    n_out = join_l.out_shape[0]
    rels = [
        join_l,
        C.identity_lineage(join_l.out_shape),            # filter NaN (pass)
        C.reduce_lineage(join_l.out_shape, 1),           # add two columns
        C.identity_lineage((n_out,)),                    # one-hot core dep
        C.identity_lineage((n_out,)),                    # add constant
    ]
    return "relational", rels


def _resnet_workflow(side=128):
    s = side
    rels = [
        C.conv2d_lineage(s, s, 3, 3),
        C.identity_lineage((s - 2, s - 2)),
        C.conv2d_lineage(s - 2, s - 2, 3, 3),
        C.identity_lineage((s - 4, s - 4)),
        C.conv2d_lineage(s - 4, s - 4, 3, 3),
        C.identity_lineage((s - 6, s - 6)),
        C.reduce_lineage((s - 6, s - 6), (0, 1)),
    ]
    return "resnet", rels


_RANDOM_OPS = [
    lambda shape, rng: ("neg", C.identity_lineage(shape)),
    lambda shape, rng: ("exp", C.identity_lineage(shape)),
    lambda shape, rng: ("clip", C.identity_lineage(shape)),
    lambda shape, rng: ("flip", C.flip_lineage(shape, 0)),
    lambda shape, rng: ("roll", C.roll_lineage(shape, int(rng.integers(1, 5)), 0)),
    lambda shape, rng: (
        "transpose",
        C.transpose_lineage(shape, tuple(reversed(range(len(shape))))),
    ),
    lambda shape, rng: (
        "reshape",
        C.reshape_lineage(shape, (int(np.prod(shape)),)),
    ),
    lambda shape, rng: ("sort", C.sort_lineage(rng.random(shape), axis=-1)),
]


def _random_workflow(n_ops: int, seed: int, n_cells: int = 40_000):
    rng = np.random.default_rng(seed)
    side = int(np.sqrt(n_cells))
    shape = (side, side)
    rels = []
    for _ in range(n_ops):
        name, rel = _RANDOM_OPS[int(rng.integers(0, len(_RANDOM_OPS)))](shape, rng)
        rels.append(rel)
        shape = rel.out_shape
    return f"random{n_ops}_s{seed}", rels


def build_workflows(n_random: int = 6):
    flows = [_image_workflow(), _relational_workflow(), _resnet_workflow()]
    for seed in range(n_random):
        flows.append(_random_workflow(5, seed))
    for seed in range(n_random // 2):
        flows.append(_random_workflow(10, 100 + seed))
    return flows


# --------------------------------------------------------------------------- #
# Query engines
# --------------------------------------------------------------------------- #
def _ravel(idx, shape):
    return np.ravel_multi_index(idx.T, shape)


def _forward_join_rows(rels, query_cells):
    """Hash-join forward propagation over uncompressed row matrices."""
    cur = _ravel(query_cells, rels[0].in_shape)
    for rel in rels:
        in_r = _ravel(rel.in_idx, rel.in_shape)
        out_r = _ravel(rel.out_idx, rel.out_shape)
        mask = np.isin(in_r, cur)
        cur = np.unique(out_r[mask])
    return cur


def _backward_join_rows(rels, query_cells):
    """Hash-join backward propagation over uncompressed row matrices."""
    cur = _ravel(query_cells, rels[-1].out_shape)
    for rel in reversed(rels):
        in_r = _ravel(rel.in_idx, rel.in_shape)
        out_r = _ravel(rel.out_idx, rel.out_shape)
        mask = np.isin(out_r, cur)
        cur = np.unique(in_r[mask])
    return cur


def _forward_array_scan(rels, query_cells):
    """Vectorized equality scan per query cell (the Array baseline)."""
    cur = _ravel(query_cells, rels[0].in_shape)
    for rel in rels:
        in_r = _ravel(rel.in_idx, rel.in_shape)
        out_r = _ravel(rel.out_idx, rel.out_shape)
        hits = np.zeros(in_r.shape[0], bool)
        for batch_start in range(0, cur.size, 1000):
            q = cur[batch_start : batch_start + 1000]
            hits |= (in_r[:, None] == q[None, :]).any(axis=1)
        cur = np.unique(out_r[hits])
    return cur


def run_fig89(selectivities=(0.001, 0.01, 0.1), n_random: int = 6,
              verbose: bool = True):
    rows = []
    for wf_name, rels in build_workflows(n_random):
        # ingest once per workflow
        log = DSLog(store_forward=True)
        names = [f"{wf_name}_a0"]
        log.define_array(names[0], rels[0].in_shape)
        encoded_pq, encoded_rle, raw_blobs = [], [], []
        for k, rel in enumerate(rels):
            names.append(f"{wf_name}_a{k + 1}")
            log.define_array(names[k + 1], rel.out_shape)
            log.register_operation(
                f"{wf_name}_op{k}", [names[k]], [names[k + 1]],
                capture=lambda r=rel: {(0, 0): r}, reuse=False,
            )
            raw = rel.rows()
            raw_blobs.append((raw, rel))
            encoded_pq.append(encode_parquet_like(raw))
            encoded_rle.append(encode_rle_like(raw))

        in_shape = rels[0].in_shape
        n_cells = int(np.prod(in_shape))
        for sel in selectivities:
            k = max(1, int(n_cells * sel))
            flat = np.arange(n_cells)[: k]
            cells = np.stack(np.unravel_index(flat, in_shape), axis=1)

            timings = {}
            t0 = time.perf_counter()
            res_dslog = log.prov_query(names, cells)
            timings["dslog"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            log.prov_query(names, cells, merge=False)
            timings["dslog_nomerge"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            want = _forward_join_rows(rels, cells)
            timings["raw"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            decoded = [decode_parquet_like(b) for b in encoded_pq]
            _forward_join_rows(rels, cells)
            timings["parquet_like"] = time.perf_counter() - t0

            t0 = time.perf_counter()
            decoded = [decode_rle_like(b) for b in encoded_rle]
            _forward_join_rows(rels, cells)
            timings["rle_like"] = time.perf_counter() - t0

            if n_cells <= 70_000 and k <= 5000:
                t0 = time.perf_counter()
                _forward_array_scan(rels, cells)
                timings["array"] = time.perf_counter() - t0

            # correctness: in-situ result == oracle
            got = {
                int(np.ravel_multi_index(c, rels[-1].out_shape))
                for c in res_dslog.cells()
            }
            assert got == set(want.tolist()), f"{wf_name} sel={sel} mismatch"

            rec = {"workflow": wf_name, "selectivity": sel, **timings}
            rows.append(rec)
            if verbose:
                print(
                    f"  {wf_name:16s} sel={sel:6.3f} "
                    + " ".join(f"{m}={t*1e3:8.2f}ms" for m, t in timings.items()),
                    flush=True,
                )
    return rows


# --------------------------------------------------------------------------- #
# Indexed vs dense θ-join ablation (the query-engine routing heuristic)
# --------------------------------------------------------------------------- #
def _scatter_table(n_rows: int, seed: int = 0):
    """A poorly-compressible (near one row per pair) table: the worst case
    for the dense all-pairs join and the target case for the index."""
    rng = np.random.default_rng(seed)
    side = n_rows  # ~unique out cells, so compression cannot merge rows
    o = np.stack([np.arange(n_rows), rng.integers(0, 64, n_rows)], axis=1)
    i = np.stack([rng.permutation(n_rows)], axis=1)
    rel = LineageRelation((side, 64), (side,), o, i).canonical()
    return compress(rel)


# --------------------------------------------------------------------------- #
# DAG-query ablation: planner-merged execution vs naive per-path union
# --------------------------------------------------------------------------- #
def _build_diamond(side: int, branches: int, root: str | None = None, log=None):
    """src fans out to ``branches`` rolled copies, they fan back into one
    array, and a conv tail (the heavy tables) runs to the output:

        src → m0..m{B-1} → mid → t → out

    The tail is shared by every simple path, so the naive per-path union
    re-executes its expensive hops once per branch; the planner walks it
    once with the branch frontiers merged at ``mid``.  Pass ``log`` to
    build the same wide fan-in DAG into a different store (the shard
    ablation feeds ``ShardedDSLog`` instances through here).
    """
    if log is None:
        log = DSLog(root=root, store_forward=True)
    shape = (side, side)
    log.define_array("src", shape)
    mids = [f"m{b}" for b in range(branches)]
    for m in mids:
        log.define_array(m, shape)
    log.define_array("mid", shape)
    log.register_operation(
        "fanout", ["src"], mids,
        capture=lambda: {
            (b, 0): C.roll_lineage(shape, b + 1, 0) for b in range(branches)
        },
        reuse=False,
    )
    log.register_operation(
        "combine", mids, ["mid"],
        capture=lambda: {
            (0, b): C.identity_lineage(shape) for b in range(branches)
        },
        reuse=False,
    )
    log.define_array("t", (side - 2, side - 2))
    log.define_array("out", (side - 4, side - 4))
    log.register_operation(
        "conv_a", ["mid"], ["t"],
        capture=lambda: {(0, 0): C.conv2d_lineage(side, side, 3, 3)},
        reuse=False,
    )
    log.register_operation(
        "conv_b", ["t"], ["out"],
        capture=lambda: {(0, 0): C.conv2d_lineage(side - 2, side - 2, 3, 3)},
        reuse=False,
    )
    return log


def run_dag_ablation(
    side: int = 96,
    branches: int = 4,
    n_queries: int = 8,
    repeats: int = 3,
    verbose: bool = True,
) -> list[dict]:
    """Planner-ordered, frontier-merged DAG execution vs per-path union,
    plus the lazy-reload blob count.

    Returns one record with ``planner_s``, ``naive_s``, the speedup, the
    number of simple paths, and ``loaded/total`` table-blob counts for a
    reloaded catalog answering one tail query.
    """
    log = _build_diamond(side, branches)
    rng = np.random.default_rng(7)
    picks = rng.choice(side * side, size=n_queries * 4, replace=False)
    cells = np.stack(np.unravel_index(picks, (side, side)), axis=1)
    queries = [cells[k * 4 : (k + 1) * 4] for k in range(n_queries)]
    paths = log.graph.simple_paths("src", "out")
    assert len(paths) == branches

    def time_of(fn, n=repeats):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def run_planner():
        return log.prov_query_batch("src", "out", queries)

    def run_naive():
        per_path = [log.prov_query_batch(p, queries) for p in paths]
        out = []
        for k in range(n_queries):
            lo = np.concatenate([r[k].lo for r in per_path])
            hi = np.concatenate([r[k].hi for r in per_path])
            out.append(merge_boxes(QueryBox(per_path[0][k].shape, lo, hi)))
        return out

    planner_res = run_planner()
    naive_res = run_naive()
    for p, n in zip(planner_res, naive_res):
        assert p.cell_set() == n.cell_set(), "planner != per-path union"
    planner_s = time_of(run_planner)
    naive_s = time_of(run_naive)

    # lazy persistence: a reloaded catalog deserializes only what one tail
    # query touches (the two conv hops), never the branch tables
    with tempfile.TemporaryDirectory() as d:
        log_disk = _build_diamond(side, branches, root=d)
        log_disk.save()
        reloaded = DSLog.load(d)
        reloaded.prov_query("out", "mid", cells[:2])
        loaded = reloaded.io_stats["tables_loaded"]
        total = sum(
            1 + e.has_forward for e in reloaded.lineage.values()
        )
        assert loaded < total, "lazy reload touched every blob"

    rec = {
        "side": side,
        "branches": branches,
        "n_paths": len(paths),
        "planner_s": planner_s,
        "naive_s": naive_s,
        "speedup": naive_s / planner_s if planner_s > 0 else float("inf"),
        "loaded_tables": loaded,
        "total_tables": total,
    }
    if verbose:
        print(
            f"  dag_ablation side={side} branches={branches} "
            f"planner={planner_s*1e3:8.2f}ms naive={naive_s*1e3:8.2f}ms "
            f"speedup={rec['speedup']:4.1f}x "
            f"lazy_reload={loaded}/{total} blobs",
            flush=True,
        )
    return [rec]


# --------------------------------------------------------------------------- #
# Shard ablation: 1 vs 4 vs 8 shards on the wide fan-in DAG
# --------------------------------------------------------------------------- #
def run_shard_ablation(
    side: int = 96,
    branches: int = 8,
    shard_counts=(1, 4, 8),
    n_queries: int = 8,
    repeats: int = 3,
    smoke: bool = False,
    verbose: bool = True,
) -> list[dict]:
    """Plan/query latency, incremental-save bytes, and partial-reload blob
    counts for the same wide fan-in DAG stored under 1/4/8 shards.

    Per shard count the record carries:

    * ``plan_s`` / ``query_s`` — cross-shard planning and batched execution
      latency (results asserted equal to the single-store oracle),
    * ``exchanges`` / ``boxes_exchanged`` — boundary traffic of one batch,
    * ``incr_bytes`` / ``full_bytes`` — bytes written by an incremental
      ``save()`` after touching ONE shard vs the initial full save (only
      dirty shard manifests rewrite, so incr shrinks as N grows),
    * ``reload_shards`` / ``reload_tables`` — how many shard manifests and
      table blobs one tail query forces a freshly loaded store to read.

    ``smoke=True`` shrinks everything for CI.
    """
    from repro.core.shard import ShardedDSLog

    if smoke:
        side, branches, n_queries, repeats = 32, 4, 4, 1
        shard_counts = tuple(n for n in shard_counts if n <= 4) or (1, 2)

    oracle = _build_diamond(side, branches)
    rng = np.random.default_rng(11)
    picks = rng.choice(side * side, size=n_queries * 4, replace=False)
    cells = np.stack(np.unravel_index(picks, (side, side)), axis=1)
    queries = [cells[k * 4 : (k + 1) * 4] for k in range(n_queries)]
    want = [r.cell_set() for r in oracle.prov_query_batch("src", "out", queries)]

    def time_of(fn, n=repeats):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    rows = []
    for n_shards in shard_counts:
        log = _build_diamond(
            side, branches, log=ShardedDSLog(n_shards=n_shards, store_forward=True)
        )
        got = log.prov_query_batch("src", "out", queries)
        assert [r.cell_set() for r in got] == want, f"{n_shards}-shard mismatch"
        boxes_one_batch = log.io_stats["boxes_exchanged"]  # one execution's
        plan = log.planner.plan("src", ["out"])
        plan_s = time_of(lambda: log.planner.plan("src", ["out"]))
        query_s = time_of(lambda: log.prov_query_batch("src", "out", queries))

        with tempfile.TemporaryDirectory() as d:
            disk = _build_diamond(
                side, branches, log=ShardedDSLog(n_shards=n_shards, root=d)
            )
            disk.save()
            full_bytes = disk.io_stats["bytes_written"]
            total_tables = sum(
                1 + e.has_forward for e in disk.lineage.values()
            )
            before = dict(disk.io_stats)
            # touch exactly one shard: a new entry hanging off the output
            out_shape = disk.arrays["out"].shape
            disk.add_lineage("out", "post", C.identity_lineage(out_shape))
            disk.save()
            after = disk.io_stats
            incr_bytes = after["bytes_written"] - before["bytes_written"]
            incr_manifests = (
                after["manifests_written"] - before["manifests_written"]
            )
            reloaded = ShardedDSLog.load(d)
            reloaded.prov_query("out", "mid", cells[:2])
            reload_shards = reloaded.io_stats["shards_loaded"]
            reload_tables = reloaded.io_stats["tables_loaded"]
            assert reload_tables < total_tables, "partial reload touched all blobs"

        rec = {
            "side": side,
            "branches": branches,
            "n_shards": n_shards,
            "plan_s": plan_s,
            "query_s": query_s,
            "exchanges": len(plan.exchanges),
            "boxes_exchanged": boxes_one_batch,
            "full_bytes": full_bytes,
            "incr_bytes": incr_bytes,
            "incr_manifests": incr_manifests,
            "reload_shards": reload_shards,
            "reload_tables": reload_tables,
            "total_tables": total_tables,
        }
        rows.append(rec)
        if verbose:
            print(
                f"  shard_ablation n={n_shards} plan={plan_s*1e3:7.2f}ms "
                f"query={query_s*1e3:8.2f}ms exch={rec['exchanges']:2d} "
                f"incr_save={incr_bytes}B/{incr_manifests}man "
                f"(full={full_bytes}B) "
                f"reload={reload_shards}sh/{reload_tables}of"
                f"{total_tables}tables",
                flush=True,
            )
    return rows


# --------------------------------------------------------------------------- #
# WAL ingest ablation: synchronous saves vs group commit, writer scaling,
# parallel vs serial sub-plan execution
# --------------------------------------------------------------------------- #
_WAL_WORKER = """
import os, sys, time
import numpy as np
from repro.core.shard import ShardedDSLog
from repro.core.capture import identity_lineage

root, writer, n, side = sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
log = ShardedDSLog.open(root, exclusive=False)
go = os.path.join(root, "go")
deadline = time.time() + 60
while not os.path.exists(go):
    if time.time() > deadline:
        raise SystemExit("rendezvous timed out")
    time.sleep(0.001)
rel = identity_lineage((side, side))
t0 = time.perf_counter()
prev = f"w{writer}c0"
for k in range(1, n + 1):
    log.add_lineage(prev, f"w{writer}c{k}", rel)
    prev = f"w{writer}c{k}"
log.commit()  # durability barrier ends the measured ingest window
dt = time.perf_counter() - t0
with open(os.path.join(root, f"elapsed_{writer}.txt"), "w") as f:
    f.write(repr(dt))
log.close()
"""


def _spawn_writers(root: str, n_writers: int, per_writer: int, side: int):
    import subprocess
    import sys as _sys

    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src")
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [_sys.executable, "-c", _WAL_WORKER, root, str(i),
             str(per_writer), str(side)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        for i in range(n_writers)
    ]
    time.sleep(0.3)  # both sides of the rendezvous are polling now
    t0 = time.perf_counter()
    with open(os.path.join(root, "go"), "w") as f:
        f.write("go")
    for p in procs:
        _, err = p.communicate(timeout=600)
        if p.returncode != 0:
            raise RuntimeError(err.decode())
    wall = time.perf_counter() - t0
    # the measured window is each writer's ingest (go -> commit); the
    # slowest writer bounds the aggregate throughput
    ingest = max(
        float(open(os.path.join(root, f"elapsed_{i}.txt")).read())
        for i in range(n_writers)
    )
    return wall, ingest


def run_wal_ablation(
    n_entries: int = 200,
    writer_counts=(1, 2, 4),
    side: int = 32,
    smoke: bool = False,
    verbose: bool = True,
) -> list[dict]:
    """Ingest durability ablation (ISSUE 4 acceptance measurement).

    * **Single-writer modes** — the same ``n_entries``-long chain ingested
      with (a) a synchronous ``save()`` after every entry (the only
      durability the store had before the WAL), (b) WAL with per-record
      fsync, (c) WAL with group commit.  Group commit must beat per-entry
      synchronous saves on entries/sec.
    * **Writer scaling** — the same *total* entry count split across
      1/2/4 concurrent writer processes ingesting into disjoint shards
      under writer-mode leases.
    * **Query execution** — serial vs ``parallel=4`` batched execution of
      a wide fan-in DAG on a 4-shard store (non-dependent sub-plans run on
      the thread pool).
    """
    import tempfile as _tmp

    from repro.core.catalog import DSLog
    from repro.core.shard import AffinityShardPolicy, ShardedDSLog

    if smoke:
        n_entries, writer_counts, side = 30, (1, 2), 16
    rows: list[dict] = []
    rel = C.identity_lineage((side, side))

    def ingest_chain(log, n, commit_every=None):
        prev = "c0"
        for k in range(1, n + 1):
            log.add_lineage(prev, f"c{k}", rel)
            if commit_every is not None and k % commit_every == 0:
                log.save()
            prev = f"c{k}"

    # -- single-writer durability modes --------------------------------- #
    modes = {}
    with _tmp.TemporaryDirectory() as d:
        log = DSLog(root=d, store_forward=False)
        t0 = time.perf_counter()
        ingest_chain(log, n_entries, commit_every=1)  # save per entry
        modes["sync_save"] = time.perf_counter() - t0
    for mode in ("sync", "group"):
        with _tmp.TemporaryDirectory() as d:
            log = DSLog.open(d, durability=mode, store_forward=False)
            t0 = time.perf_counter()
            ingest_chain(log, n_entries)
            log.commit()  # durability barrier: fair comparison point
            modes[f"wal_{mode}"] = time.perf_counter() - t0
            log.close()
    rec = {
        "kind": "modes",
        "n_entries": n_entries,
        **{f"{m}_s": s for m, s in modes.items()},
        "group_vs_sync_save_x": modes["sync_save"] / modes["wal_group"],
    }
    rows.append(rec)
    if verbose:
        print(
            f"  wal_ablation n={n_entries} "
            + " ".join(
                f"{m}={n_entries / s:8.0f}ent/s" for m, s in modes.items()
            )
            + f" group_commit_speedup={rec['group_vs_sync_save_x']:.1f}x",
            flush=True,
        )
    assert rec["group_vs_sync_save_x"] > 1.0, (
        "group commit must beat per-entry synchronous saves"
    )

    # -- concurrent writer scaling (processes, disjoint shards) ---------- #
    for w in writer_counts:
        per_writer = max(1, n_entries // w)
        with _tmp.TemporaryDirectory() as d:
            pins = {
                f"w{i}c{k}": i
                for i in range(w)
                for k in range(per_writer + 1)
            }
            with ShardedDSLog.open(
                d, max(w, 1), policy=AffinityShardPolicy(max(w, 1), pins)
            ):
                pass
            wall, ingest = _spawn_writers(d, w, per_writer, side)
            total = per_writer * w
            with ShardedDSLog.open(d) as folded:  # fold + sanity check
                assert len(folded._lid_shard) == total
        rec = {
            "kind": "writers",
            "n_writers": w,
            "total_entries": total,
            "wall_s": wall,
            "ingest_s": ingest,
            "entries_per_s": total / ingest,
        }
        rows.append(rec)
        if verbose:
            print(
                f"  wal_ablation writers={w} total={total} "
                f"ingest={ingest * 1e3:8.1f}ms (wall={wall * 1e3:7.1f}ms) "
                f"throughput={rec['entries_per_s']:8.0f}ent/s",
                flush=True,
            )

    # -- parallel vs serial sub-plan execution --------------------------- #
    qside = max(side, 48) if not smoke else 32
    log = _build_diamond(
        qside, 8 if not smoke else 4,
        log=ShardedDSLog(n_shards=4, store_forward=True),
    )
    rng = np.random.default_rng(5)
    picks = rng.choice(qside * qside, size=32, replace=False)
    cells = np.stack(np.unravel_index(picks, (qside, qside)), axis=1)
    queries = [cells[k * 4 : (k + 1) * 4] for k in range(8)]
    serial_res = log.prov_query_batch("src", "out", queries)
    par_res = log.prov_query_batch("src", "out", queries, parallel=4)
    assert [r.cell_set() for r in serial_res] == [
        r.cell_set() for r in par_res
    ]

    def time_of(fn, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    serial_s = time_of(
        lambda: log.prov_query_batch("src", "out", queries)
    )
    par_s = time_of(
        lambda: log.prov_query_batch("src", "out", queries, parallel=4)
    )
    rec = {
        "kind": "exec",
        "serial_s": serial_s,
        "parallel_s": par_s,
        "speedup": serial_s / par_s if par_s > 0 else float("inf"),
    }
    rows.append(rec)
    if verbose:
        print(
            f"  wal_ablation exec serial={serial_s * 1e3:8.2f}ms "
            f"parallel4={par_s * 1e3:8.2f}ms "
            f"speedup={rec['speedup']:.2f}x",
            flush=True,
        )
    return rows


def run_index_ablation(
    n_rows: int = 20_000,
    selectivities=(0.0005, 0.001, 0.01),
    n_queries: int = 16,
    repeats: int = 3,
    verbose: bool = True,
):
    """Time ``theta_join`` dense vs indexed (and the batched API) on one
    large compressed table, at selectivities ≤1% of the key space.

    Returns one record per selectivity with ``dense_s``, ``index_s`` (index
    prebuilt — the amortized regime), ``index_cold_s`` (includes one index
    build), ``batch_s``, and the dense/indexed speedup.
    """
    table = _scatter_table(n_rows)
    key_side = table.key_shape[0]
    rng = np.random.default_rng(1)
    rows = []
    for sel in selectivities:
        k = max(1, int(key_side * sel))
        queries = []
        for _ in range(n_queries):
            # k scattered key rows (≤ sel of the key space): stays k boxes
            # after merging, so the dense join pays k × n_rows per query
            picks = np.sort(rng.choice(key_side, size=k, replace=False))
            lo = np.stack([picks, np.zeros(k, np.int64)], axis=1)
            hi = np.stack([picks, np.full(k, 63, np.int64)], axis=1)
            queries.append(QueryBox(table.key_shape, lo, hi))

        def time_of(fn, n=repeats):
            best = float("inf")
            for _ in range(n):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        dense_s = time_of(
            lambda: [theta_join(q, table, path="dense") for q in queries]
        )
        table.invalidate_index()
        index_cold_s = time_of(
            lambda: [theta_join(q, table, path="index") for q in queries], n=1
        )
        index_s = time_of(
            lambda: [theta_join(q, table, path="index") for q in queries]
        )
        batch_s = time_of(lambda: theta_join_batch(queries, table, path="index"))
        # routing sanity: auto must pick the fast side for selective queries
        auto_s = time_of(lambda: [theta_join(q, table) for q in queries])
        for q in queries[:2]:
            assert (
                theta_join(q, table, path="index").cell_set()
                == theta_join(q, table, path="dense").cell_set()
            )
        rec = {
            "n_rows": table.n_rows,
            "selectivity": sel,
            "dense_s": dense_s,
            "index_cold_s": index_cold_s,
            "index_s": index_s,
            "batch_s": batch_s,
            "auto_s": auto_s,
            "speedup": dense_s / index_s if index_s > 0 else float("inf"),
        }
        rows.append(rec)
        if verbose:
            print(
                f"  index_ablation n_rows={table.n_rows} sel={sel:7.4f} "
                f"dense={dense_s*1e3:8.2f}ms index={index_s*1e3:8.2f}ms "
                f"batch={batch_s*1e3:8.2f}ms auto={auto_s*1e3:8.2f}ms "
                f"speedup={rec['speedup']:5.1f}x",
                flush=True,
            )
    return rows


# --------------------------------------------------------------------------- #
# Batched accelerator execution: per-hop join loop vs packed frontiers
# --------------------------------------------------------------------------- #
def _permutation_lineage(shape, rng) -> LineageRelation:
    """A random bijection between two same-shape arrays.

    Poorly compressible on purpose (≈ one table row per cell): each hop of
    the accel DAG is then a *small dense* θ-join — under
    ``INDEX_MIN_ROWS`` the router always evaluates the all-pairs mask, the
    exact per-hop inner loop batched frontier execution packs.
    """
    n = int(np.prod(shape))
    cells = np.stack(
        np.unravel_index(np.arange(n), shape), axis=1
    ).astype(np.int64)
    perm = rng.permutation(n)
    return LineageRelation(shape, shape, cells, cells[perm]).canonical()


def _accel_dag_edges(shape, branches: int, hops: int, seed: int = 0):
    """The accel DAG's ``(src, dst, relation)`` edges in ingest order:
    ``src`` fans out to ``branches`` independent permutation chains of
    ``hops`` tables each, all fanning back into ``out``:

        src → b{b}h0 → … → b{b}h{H-1} → out      (for each branch b)
    """
    rng = np.random.default_rng(seed)
    edges = []
    for b in range(branches):
        prev = "src"
        for h in range(hops):
            name = f"b{b}h{h}"
            edges.append((prev, name, _permutation_lineage(shape, rng)))
            prev = name
        edges.append((prev, "out", _permutation_lineage(shape, rng)))
    return edges


def _build_accel_dag(shape, branches: int, hops: int, seed: int = 0):
    """An in-memory store of :func:`_accel_dag_edges`.

    Every hop's table is a fresh random bijection, so each plan wave holds
    ``branches`` small dense joins — the workload the batched executor
    packs into one blocked evaluation and the per-hop loop dispatches one
    at a time.
    """
    log = DSLog(store_forward=True)
    log.define_array("src", shape)
    log.define_array("out", shape)
    for src, dst, rel in _accel_dag_edges(shape, branches, hops, seed):
        if dst not in log.arrays:
            log.define_array(dst, shape)
        log.add_lineage(src, dst, rel)
    return log


def _ragged_frontier(k: int, row_lo: int, row_hi: int, n_attrs: int,
                     seed: int = 0):
    """``k`` independent interval-overlap joins with ragged row counts.

    The segment shapes a multi-branch plan wave hands the batched
    executor: every segment a different (nq, nr), boxes overlapping
    sparsely so the pair lists are non-trivial on both layouts.
    """
    rng = np.random.default_rng(seed)
    segs = []
    for _ in range(k):
        nq = int(rng.integers(row_lo, row_hi))
        nr = int(rng.integers(row_lo, row_hi))
        q_lo = rng.integers(0, 512, size=(nq, n_attrs)).astype(np.int64)
        r_lo = rng.integers(0, 512, size=(nr, n_attrs)).astype(np.int64)
        q_hi = q_lo + rng.integers(1, 48, size=(nq, n_attrs))
        r_hi = r_lo + rng.integers(1, 48, size=(nr, n_attrs))
        segs.append((q_lo, q_hi, r_lo, r_hi))
    return segs


def run_accel_ablation(
    shape=(32, 31),
    branches: int = 20,
    hops: int = 2,
    n_cells: int = 330,
    repeats: int = 9,
    smoke: bool = False,
    verbose: bool = True,
) -> list[dict]:
    """Batched frontier execution vs the per-hop join loop (ISSUE 5 + 8).

    The DAG's hops are small dense joins (permutation tables under the
    index threshold) — the regime where dispatching one tiny mask
    evaluation per hop loses to packing a whole plan frontier into one
    blocked int32 evaluation.  Measures, over the same query batch
    (median of ``repeats`` runs — this box's timing noise is large):

    * ``perhop_s``   — serial per-hop loop (``batched=False``),
    * ``batched_s``  — serial packed frontier execution,
    * ``parallel_s`` — packed execution with ``parallel=4`` (the wave's
      mask evaluations split across workers, clamped to real cores; the
      twin's numpy inner loops release the GIL, so they overlap on CPU),

    asserts all three produce bit-identical results, and reports the
    io_stats batching meters (including the block-diagonal tile meters).

    A second record (``kind="layout"``, ISSUE 8) measures the kernel
    launch layouts head-to-head on a large ragged frontier: one masked
    cross-product launch vs the block-diagonal tile schedule, same
    segments, pair lists asserted bit-identical to each other and to a
    per-segment dense oracle.
    """
    if smoke:
        shape, branches, hops, n_cells, repeats = (24, 22), 10, 2, 192, 5
    log = _build_accel_dag(shape, branches, hops)
    # this ablation measures the join *engines* — disable the view/answer
    # cache layer, which would otherwise serve every repeat after the first
    # warmup query and time nothing but cache lookups
    log.views.enabled = False
    rng = np.random.default_rng(7)
    n = int(np.prod(shape))
    flat = rng.choice(n, size=n_cells, replace=False)
    cells = np.stack(np.unravel_index(flat, shape), axis=1)

    def run(label, **kw):
        res = log.prov_query("src", "out", cells, **kw)
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = log.prov_query("src", "out", cells, **kw)
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2], res

    perhop_s, want = run("perhop", batched=False)
    base = dict(log.io_stats)
    batched_s, got_b = run("batched", batched=True)
    # run() issues one warmup query before the `repeats` timed ones, and
    # every call dispatches the same launches
    queries_run = repeats + 1
    launches = log.io_stats["kernel_launches"] - base["kernel_launches"]
    packed = log.io_stats["joins_packed"] - base["joins_packed"]
    parallel_s, got_p = run("parallel", batched=True, parallel=4)
    for got in (got_b, got_p):
        assert got.lo.tobytes() == want.lo.tobytes(), "engine results differ"
        assert got.hi.tobytes() == want.hi.tobytes(), "engine results differ"

    total_hops = branches * (hops + 1)
    rec = {
        "kind": "exec",
        "shape": shape,
        "branches": branches,
        "hops": total_hops,
        "n_cells": n_cells,
        "perhop_s": perhop_s,
        "batched_s": batched_s,
        "parallel_s": parallel_s,
        "batched_speedup": perhop_s / batched_s,
        "parallel_speedup": batched_s / parallel_s,
        "launches_per_query": launches / queries_run,
        "joins_per_launch": packed / max(launches, 1),
        "batch_tiles_visited": log.io_stats["batch_tiles_visited"],
        "batch_tiles_skipped": log.io_stats["batch_tiles_skipped"],
    }
    if verbose:
        print(
            f"  accel_ablation {branches}x{hops + 1} hops "
            f"perhop={perhop_s * 1e3:7.1f}ms batched={batched_s * 1e3:7.1f}ms "
            f"parallel4={parallel_s * 1e3:7.1f}ms "
            f"batched={rec['batched_speedup']:4.2f}x "
            f"par={rec['parallel_speedup']:4.2f}x "
            f"joins/launch={rec['joins_per_launch']:4.1f}",
            flush=True,
        )
    return [rec, _run_layout_ablation(smoke=smoke, verbose=verbose)]


def _run_layout_ablation(smoke: bool = False, verbose: bool = True) -> dict:
    """Masked cross-product launch vs the block-diagonal tile schedule.

    One large ragged frontier (≥16 segments), both launch layouts forced
    through :func:`repro.kernels.ops.segmented_range_join_pairs` on the
    device JAX finds (compiled on a TPU, the interpreter elsewhere), pair
    lists asserted bit-identical to each other and to a per-segment
    ``range_join_pairs`` oracle.  Both charge every scheduled tile, so the
    time ratio tracks the tile ratio, reported alongside as
    ``tiles_visited`` / ``tiles_skipped``.
    """
    from repro.kernels.ops import range_join_pairs, segmented_range_join_pairs

    k, row_lo, row_hi, repeats = (16, 64, 160, 3) if smoke else (24, 96, 224, 5)
    block_q = block_r = 128
    segs = _ragged_frontier(k, row_lo, row_hi, n_attrs=2, seed=11)

    def run(layout):
        pairs, info = segmented_range_join_pairs(
            segs, block_q=block_q, block_r=block_r, layout=layout,
        )
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            pairs, info = segmented_range_join_pairs(
                segs, block_q=block_q, block_r=block_r, layout=layout,
            )
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2], pairs, info

    dense_s, dense_pairs, dense_info = run("dense")
    diag_s, diag_pairs, diag_info = run("blockdiag")
    for s, (q_lo, q_hi, r_lo, r_hi) in enumerate(segs):
        want = range_join_pairs(q_lo, q_hi, r_lo, r_hi)
        for label, got in (("dense", dense_pairs[s]), ("blockdiag", diag_pairs[s])):
            assert np.array_equal(got[0], want[0]) and np.array_equal(
                got[1], want[1]
            ), f"{label} layout pairs differ from per-segment oracle (seg {s})"
    rec = {
        "kind": "layout",
        "segments": k,
        "rows": int(dense_info["rows"]),
        "geometry": f"{block_q}x{block_r}",
        "dense_s": dense_s,
        "blockdiag_s": diag_s,
        "blockdiag_speedup": dense_s / diag_s,
        "tiles_visited": int(diag_info["tiles_visited"]),
        "tiles_skipped": int(diag_info["tiles_skipped"]),
        "cross_tiles": int(dense_info["tiles_visited"]),
    }
    if verbose:
        print(
            f"  layout_ablation k={k} rows={rec['rows']} "
            f"dense={dense_s * 1e3:7.1f}ms blockdiag={diag_s * 1e3:7.1f}ms "
            f"speedup={rec['blockdiag_speedup']:4.2f}x "
            f"tiles={rec['tiles_visited']}/{rec['cross_tiles']} "
            f"(skipped {rec['tiles_skipped']})",
            flush=True,
        )
    return rec


def _build_view_chain(shape, hops: int, seed: int = 0):
    """One hot linear route ``a0 → a1 → … → aH`` of random bijections.

    Composing the whole route stays one bijection (≈ one row per cell), so
    a materialized view collapses ``hops`` θ-joins into one — the workload
    the answer cache and view shortcut are built for.
    """
    rng = np.random.default_rng(seed)
    logs = []
    rels = [_permutation_lineage(shape, rng) for _ in range(hops)]
    for _ in range(2):
        log = DSLog()
        log.define_array("a0", shape)
        for h, rel in enumerate(rels):
            log.define_array(f"a{h + 1}", shape)
            log.add_lineage(f"a{h}", f"a{h + 1}", rel)
        logs.append(log)
    return logs


def run_views_ablation(
    shape=(48, 48),
    hops: int = 8,
    n_cells: int = 64,
    repeats: int = 9,
    smoke: bool = False,
    verbose: bool = True,
) -> list[dict]:
    """Materialized views + answer cache vs the plain planner (ISSUE 7).

    A hot route of ``hops`` bijection tables, queried backward with varying
    cells.  Measures, as medians over ``repeats`` runs:

    * ``cold_s``  — plain planner (views disabled): full multi-hop plan,
      one θ-join per hop, every query,
    * ``warm_s``  — heat-admitted materialized view: two-node plan over the
      composed route table, one θ-join (fresh cells each run, so the
      answer cache never fires),
    * ``cache_s`` — identical repeated query served from the cell-level
      answer cache, no planning at all,

    then mutates an entry mid-route (``mark_dirty``), checks the view and
    its answers die precisely, and lets the next hot streak re-materialize.
    Every timed answer is asserted bit-identical against the cold store.
    """
    if smoke:
        shape, hops, n_cells, repeats = (32, 32), 10, 48, 7
    warm_log, cold_log = _build_view_chain(shape, hops)
    cold_log.views.enabled = False
    src, dst = f"a{hops}", "a0"
    rng = np.random.default_rng(11)
    n = int(np.prod(shape))

    def fresh_cells():
        flat = rng.choice(n, size=n_cells, replace=False)
        return np.stack(np.unravel_index(flat, shape), axis=1)

    def identical(a, b, ctx):
        assert a.shape == b.shape, ctx
        assert a.lo.tobytes() == b.lo.tobytes(), ctx
        assert a.hi.tobytes() == b.hi.tobytes(), ctx

    # warm-up: varying cells miss the answer cache, build route heat, and
    # admit the composed view; every answer checked against the cold store
    for i in range(6):
        cells = fresh_cells()
        identical(warm_log.prov_query(src, dst, cells),
                  cold_log.prov_query(src, dst, cells), f"warmup {i}")
    assert warm_log.io_stats["views_materialized"] == 1, "no view admitted"

    queries = [fresh_cells() for _ in range(repeats)]
    cold_ts, warm_ts = [], []
    for i, cells in enumerate(queries):
        t0 = time.perf_counter()
        want = cold_log.prov_query(src, dst, cells)
        cold_ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        got = warm_log.prov_query(src, dst, cells)
        warm_ts.append(time.perf_counter() - t0)
        identical(got, want, f"timed {i}")
    cold_s = sorted(cold_ts)[len(cold_ts) // 2]
    warm_s = sorted(warm_ts)[len(warm_ts) // 2]

    # hot-route repeats: the identical query comes straight from the cache
    repeat_cells = queries[-1]
    base_hits = warm_log.io_stats["cache_hits"]
    cache_ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        got = warm_log.prov_query(src, dst, repeat_cells)
        cache_ts.append(time.perf_counter() - t0)
    cache_s = sorted(cache_ts)[len(cache_ts) // 2]
    assert warm_log.io_stats["cache_hits"] - base_hits == repeats
    identical(got, cold_log.prov_query(src, dst, repeat_cells), "cached")

    # mid-run mutation: precise invalidation, then re-materialization
    lid = warm_log.by_pair[(f"a{hops // 2}", f"a{hops // 2 + 1}")][0]
    warm_log.mark_dirty(lid)
    cold_log.mark_dirty(lid)
    assert warm_log.io_stats["views_invalidated"] == 1
    for i in range(6):
        cells = fresh_cells()
        identical(warm_log.prov_query(src, dst, cells),
                  cold_log.prov_query(src, dst, cells), f"post-dirty {i}")
    assert warm_log.io_stats["views_materialized"] == 2, "no re-admission"

    rec = {
        "shape": shape,
        "hops": hops,
        "n_cells": n_cells,
        "cold_s": cold_s,
        "warm_s": warm_s,
        "cache_s": cache_s,
        "view_speedup": cold_s / warm_s,
        "cache_speedup": cold_s / cache_s,
        "views_materialized": warm_log.io_stats["views_materialized"],
        "views_invalidated": warm_log.io_stats["views_invalidated"],
        "cache_hits": warm_log.io_stats["cache_hits"],
    }
    if verbose:
        print(
            f"  views_ablation {hops} hops "
            f"cold={cold_s * 1e3:7.2f}ms warm={warm_s * 1e3:7.2f}ms "
            f"cache={cache_s * 1e3:7.2f}ms "
            f"view={rec['view_speedup']:5.1f}x "
            f"cache={rec['cache_speedup']:5.1f}x",
            flush=True,
        )
    return [rec]
