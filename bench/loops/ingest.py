"""One writer: the configuration's hops, added in a fixed repeating order.

Set-up generates every hop's rows.  The window adds them one by one under
fresh array names each round, with ``commit()`` after each hop, until the
first hop boundary after ``--seconds``, and ends with one backward query
over the first hop.  Then the store's files are copied as the process left
them (what a crash right after the last acknowledged ``commit()`` leaves),
the copy is reopened, and every committed hop is checked: its stored tables
cover exactly its raw rows, and seeded backward and forward queries over it
match the reference.  Every acknowledged ``commit()`` must have been
preceded by an ``fsync`` since the one before.
"""

from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
import threading
import time
import traceback

import numpy as np

from .. import oracle, store
from ..context import add_span_seconds, memory_peak, note
from ..workflows import pipeline_hops


def stored_pairs(table) -> int:
    """Rows a compressed table covers: per stored box, its key extent times
    its value extent (a value range shifts with the key, it does not grow)."""
    if table is None:
        return 0
    keys = np.prod(table.key_hi - table.key_lo + 1, axis=1)
    vals = np.prod(table.val_hi - table.val_lo + 1, axis=1)
    return int((keys * vals).sum())


class FsyncCounter:
    """Counts ``os.fsync`` calls, from any thread, while installed."""

    def __init__(self):
        self.n = 0
        self._real = os.fsync
        self._lock = threading.Lock()

    def _fsync(self, fd):
        with self._lock:
            self.n += 1
        return self._real(fd)

    def __enter__(self):
        os.fsync = self._fsync
        return self

    def __exit__(self, *exc):
        os.fsync = self._real


def _add_spans(spans: dict, routes: dict, tr) -> None:
    """Add one hop's program trace: seconds by span kind, and ``canonical``
    spans counted by route."""
    add_span_seconds(spans, tr)
    for s in tr.spans("canonical"):
        route = s.attrs.get("route")
        routes[route] = routes.get(route, 0) + 1


def _query(log, src, dst, cells):
    try:
        return log.prov_query(src, dst, cells)
    except Exception:  # a fault under test, counted as a wrong answer
        print(traceback.format_exc(limit=3), file=sys.stderr)
        return None


def _wrong(res, want) -> bool:
    return res is None or not np.array_equal(
        oracle.boxes_to_flat(res.lo, res.hi, res.shape), want)


def _cells(flat, shape):
    return np.stack(np.unravel_index(flat, shape), axis=1)


def run(r) -> tuple:
    import jax
    from repro.core.catalog import DSLog

    ctx, mix = r.ctx, r.mix
    durability = r.cfg["guarantees"]["durability"]
    forward = r.cfg["store_forward"]
    hops = []  # (hop, relation), in window order
    for pipe in r.cfg["pipelines"]:
        hops += [(h, store.to_relation(h))
                 for h in pipeline_hops(r.cfg, pipe, data_seed=r.args.seed)]
    first, first_rel = hops[0]
    rb_flat = np.arange(int(mix["readback_cells"]))
    rb_cells = _cells(rb_flat, first.out_shape)

    def warm_once():  # the first hop into a throwaway store, and the read-back
        store.remove(r.scratch + "-warm")
        with DSLog.open(r.scratch + "-warm", durability=durability,
                        store_forward=forward) as log:
            log.define_array(f"w_{first.src}", first.in_shape)
            log.define_array(f"w_{first.dst}", first.out_shape)
            log.add_lineage(f"w_{first.src}", f"w_{first.dst}", first_rel)
            log.commit()
            _query(log, f"w_{first.dst}", f"w_{first.src}", rb_cells)
        store.remove(r.scratch + "-warm")

    r.warm_up(warm_once)

    store.remove(r.scratch)
    log = DSLog.open(r.scratch, durability=durability, store_forward=forward)
    committed = []  # (round, hop position in the window order)
    rows = 0
    add_s = commit_s = 0.0
    unsynced = 0  # commits with no fsync since the one before
    # traced, each hop runs in a program trace of its own; untraced, in none
    scope = ((lambda: log.trace_scope("ingest")) if r.args.trace
             else contextlib.nullcontext)
    spans: dict = {}  # span kind -> seconds, over the window's hops
    routes: dict = {}  # canonical route -> spans
    with FsyncCounter() as fsyncs:
        t_start = r.open_window()
        with jax.profiler.TraceAnnotation("bench.window"):
            rnd = 0
            while time.perf_counter() - t_start < r.args.seconds:
                for j, (hop, rel) in enumerate(hops):
                    if committed and time.perf_counter() - t_start >= r.args.seconds:
                        break
                    src, dst = f"r{rnd}_{hop.src}", f"r{rnd}_{hop.dst}"
                    for name, shape in ((src, hop.in_shape), (dst, hop.out_shape)):
                        if name not in log.arrays:
                            log.define_array(name, shape)
                    synced = fsyncs.n
                    with scope() as tr:
                        with jax.profiler.TraceAnnotation("bench.add_lineage"):
                            t = time.perf_counter()
                            log.add_lineage(src, dst, rel, op_name=hop.op)
                            add_s += time.perf_counter() - t
                        with jax.profiler.TraceAnnotation("bench.commit"):
                            t = time.perf_counter()
                            log.commit()
                            commit_s += time.perf_counter() - t
                    if tr is not None:
                        _add_spans(spans, routes, tr)
                    unsynced += fsyncs.n == synced
                    rows += hop.n_rows
                    committed.append((rnd, j))
                rnd += 1
            with jax.profiler.TraceAnnotation("bench.readback"):
                got = _query(log, f"r0_{first.dst}", f"r0_{first.src}", rb_cells)
        r.close_window()
    ctx.ingest = {"rows": rows, "add_s": add_s, "commit_s": commit_s}
    if r.args.trace:
        ctx.ingest.update(spans=spans, canonical_routes=routes)
    mem = memory_peak()

    # what a crash right after the last acknowledged commit() leaves on disk:
    # the files as they stand, without the writer's unflushed buffers
    crash = r.scratch + "-crash"
    store.remove(crash)
    shutil.copytree(r.scratch, crash)
    log.close(checkpoint=False)
    del log
    gc.collect()
    store.remove(r.scratch)
    r.reduce_trace()

    t_check = time.perf_counter()
    reopened = DSLog.load(crash)
    t_open = time.perf_counter() - t_check
    rng = np.random.default_rng([r.args.seed % 2**63, 2])
    n = int(mix["check_cells"])
    missing = short = wrong = 0
    for rnd, j in committed:
        hop, _ = hops[j]
        src, dst = f"r{rnd}_{hop.src}", f"r{rnd}_{hop.dst}"
        ids = reopened.by_pair.get((src, dst))
        if not ids:
            missing += 1
            continue
        entries = [reopened.lineage[i] for i in ids]
        # every stored box: the tables cover the hop's rows, no more, no less
        short += sum(stored_pairs(e.backward) for e in entries) != hop.n_rows
        if forward:
            short += sum(stored_pairs(e.forward) for e in entries) != hop.n_rows
        ref = oracle.HopIndex(hop)
        n_out, n_in = int(np.prod(hop.out_shape)), int(np.prod(hop.in_shape))
        out_flat = np.unique(rng.choice(n_out, min(n, n_out), replace=False))
        in_flat = np.unique(rng.choice(n_in, min(n, n_in), replace=False))
        wrong += _wrong(_query(reopened, dst, src, _cells(out_flat, hop.out_shape)),
                        ref.join(out_flat, forward=False))
        wrong += _wrong(_query(reopened, src, dst, _cells(in_flat, hop.in_shape)),
                        ref.join(in_flat, forward=True))
    del reopened
    store.remove(crash)
    wrong_answer = int(_wrong(got, oracle.HopIndex(first).join(rb_flat, forward=False)))
    note(f"reopen: {t_open:.3f} s; reopen and reference check: "
         f"{time.perf_counter() - t_check:.3f} s")
    note(f"window: {len(committed)} hops, {rows} rows in {ctx.window_s:.3f} s; "
         f"add_lineage {add_s:.3f} s, commit {commit_s:.3f} s, fsyncs {fsyncs.n}")
    r.checks += [
        ("hops_missing_after_reopen", missing, 0),
        ("tables_not_covering_their_rows", short, 0),
        ("hop_queries_wrong_after_reopen", wrong, 0),
        ("commits_without_fsync", unsynced, 0),
        ("wrong_answers", wrong_answer, 0),
    ]
    return len(committed), missing + short + wrong + unsynced + wrong_answer, mem
