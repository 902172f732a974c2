"""One client, closed loop: the next query leaves when the last answer is back.

The store is a clone of the configuration's pristine store.  Each warm-up
pass answers ``warm_blocks`` blocks of the mix's queries, drawn from the
seed's warm-up stream, on a throwaway clone that carries the geometries the
passes before it tuned; the window's clone carries them too.  After the window every answer it gave is checked against the plain
reference (:mod:`bench.oracle`).
"""

from __future__ import annotations

import gc
import json
import os
import sys
import time
import traceback

import numpy as np

from .. import oracle, store
from ..context import add_span_seconds, memory_peak, note
from ..mix import QueryStream


def _open_resident(root: str):
    """Open a store with every table resident, as a server that has been
    answering queries holds it: tables load lazily on first touch, and the
    planner prices a hop whose table is not loaded yet as index-less."""
    from repro.core.catalog import DSLog

    log = DSLog.load(root)
    for e in log.lineage.values():
        e.backward
        e.forward
    return log


def run(r) -> tuple:
    import jax

    ctx = r.ctx
    path, meta = store.pristine(r.cfg, out=note)
    ctx.raw_bytes = meta["raw_bytes"]
    ctx.stored_bytes = store.dir_bytes(path)
    stream = QueryStream(r.cfg, r.mix, r.args.seed)
    warm = QueryStream(r.cfg, r.mix, r.args.seed, part="warm")
    tuned = {}

    def warm_once():  # on a fresh clone, with what earlier passes tuned
        store.clone(path, r.scratch + "-warm")
        if tuned:
            with open(os.path.join(r.scratch + "-warm", "autotune.json"), "w") as f:
                json.dump(tuned, f)
        log = _open_resident(r.scratch + "-warm")
        for i in range(int(r.mix["warm_blocks"]) * len(warm)):
            log.prov_query(*warm[i].args())
        tuned.update(log.autotune.to_manifest())
        del log
        gc.collect()
        store.remove(r.scratch + "-warm")

    r.warm_up(warm_once)
    geoms = sorted((e["bucket"], "x".join(map(str, e["geometry"])))
                   for e in tuned["entries"].values())
    note(f"autotuned geometry: {json.dumps(geoms)}")

    # the window's store: a fresh clone that carries the tuned geometries
    store.clone(path, r.scratch)
    with open(os.path.join(r.scratch, "autotune.json"), "w") as f:
        json.dump(tuned, f)
    log = _open_resident(r.scratch)
    base = dict(log.io_stats)
    answers, errors = [], []
    t_start = r.open_window()
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() - t_start < r.args.seconds:
            q = stream[len(answers)]
            with jax.profiler.TraceAnnotation("bench.query"):
                t = time.perf_counter()
                try:
                    out = log.prov_query(*q.args(), trace=bool(r.args.trace))
                except Exception:  # a query that fails counts, the run goes on
                    out = None
                    errors.append(traceback.format_exc(limit=3))
                ctx.latencies_s.append(time.perf_counter() - t)
            res, tr = out if r.args.trace and out is not None else (out, None)
            answers.append(None if res is None else (res.lo, res.hi, res.shape))
            if tr is not None:
                ctx.spans.append(add_span_seconds({}, tr))
    r.close_window()
    ctx.counters = {k: log.io_stats[k] - base.get(k, 0) for k in log.io_stats}
    mem = memory_peak()
    del log
    gc.collect()
    store.remove(r.scratch)
    r.reduce_trace()

    t_check = time.perf_counter()
    want = oracle.answer_queries(r.cfg, [stream[j] for j in range(len(answers))])
    wrong = sum(
        got is None or not np.array_equal(oracle.boxes_to_flat(*got), w)
        for got, w in zip(answers, want)
    )
    note(f"reference check: {time.perf_counter() - t_check:.3f} s")
    for e in errors[:3]:
        print(e, file=sys.stderr)
    by: dict = {}
    for i, dt in enumerate(ctx.latencies_s):
        by.setdefault(stream[i].label, []).append(dt)
    note(f"window: {len(answers)} queries in {ctx.window_s:.3f} s; launches "
         f"{ctx.counters.get('kernel_launches', 0)}; programs built in the window "
         f"{ctx.window_programs}")
    note("median ms by class [count, ms]: " + json.dumps(
        {k: [len(v), round(float(np.median(v)) * 1e3, 3)] for k, v in sorted(by.items())}))
    r.checks += [
        ("wrong_answers", wrong, 0),
        ("failed_queries", len(errors), 0),
        ("twin_launches", ctx.counters.get("twin_launches", 0), 0),
    ]
    return len(answers), wrong, mem

