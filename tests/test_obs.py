"""Telemetry subsystem: registry, histograms, tracing, export, EXPLAIN ANALYZE.

Covers the observability invariants end to end: instrument math
(log-bucketed percentiles, snapshot merge), the live ``io_stats`` facade,
the per-query span tree (plan / hop / kernel / exchange / cache / view and
the stages inside a dispatch), the ingest spans under ``trace_scope``, the
dispatch byte and pair counters, profiler annotations only while tracing,
``describe(analyze=True)``, the ``telemetry.json`` sidecar + Prometheus
exposition + ``dstat`` CLI, health red-flags, the sharded aggregation
union fix, tracing on/off bit-identity under the race detector, and a
bound on the tracing-off instrument cost.
"""

import json
import os
import tempfile
import time

import numpy as np
import pytest

from repro.core.capture import (
    conv2d_lineage,
    flip_lineage,
    identity_lineage,
    roll_lineage,
    sort_lineage,
    transpose_lineage,
)
from repro.core.catalog import DSLog
from repro.core.provrc import compress
from repro.core.query import INDEX_MIN_ROWS, BatchedJoinExecutor, JoinRequest, QueryBox
from repro.core.shard import ShardedDSLog
from repro.obs.export import (
    TELEMETRY_SCHEMA,
    parse_prometheus,
    render_prometheus,
    telemetry_snapshot,
    validate_telemetry,
)
from repro.obs.metrics import Histogram, MetricsRegistry, bucket_index
from repro.obs.trace import QueryTrace, maybe_span
from repro.tools import dstat

SIDE = 8
SHAPE = (SIDE, SIDE)

_OPS = [
    lambda rng: identity_lineage(SHAPE),
    lambda rng: flip_lineage(SHAPE, int(rng.integers(0, 2))),
    lambda rng: roll_lineage(SHAPE, int(rng.integers(1, 4)), 0),
    lambda rng: transpose_lineage(SHAPE, (1, 0)),
]


def _build_random_dag(logs, n_ops: int, seed: int):
    """Drive identical op streams into several stores (see test_shard)."""
    rng = np.random.default_rng(seed)
    names = ["a0"]
    for log in logs:
        log.define_array("a0", SHAPE)
    for k in range(n_ops):
        new = f"a{k + 1}"
        prev = names[-1]
        fan_in = k % 3 == 2 and len(names) > 2
        if fan_in:
            other = names[int(rng.integers(0, len(names) - 1))]
            state = rng.bit_generator.state
            for log in logs:
                rng.bit_generator.state = state
                rel_a = _OPS[int(rng.integers(0, len(_OPS)))](rng)
                rel_b = _OPS[int(rng.integers(0, len(_OPS)))](rng)
                log.define_array(new, SHAPE)
                log.register_operation(
                    f"op{k}", [prev, other], [new],
                    capture=lambda ra=rel_a, rb=rel_b: {(0, 0): ra, (0, 1): rb},
                    reuse=False,
                )
        else:
            state = rng.bit_generator.state
            for log in logs:
                rng.bit_generator.state = state
                rel = _OPS[int(rng.integers(0, len(_OPS)))](rng)
                log.define_array(new, SHAPE)
                log.register_operation(
                    f"op{k}", [prev], [new],
                    capture=lambda r=rel: {(0, 0): r},
                    reuse=False,
                )
        names.append(new)
    return names


def _one_hop(log):
    log.add_lineage("A", "B", identity_lineage(SHAPE))
    return log


# --------------------------------------------------------------------------- #
# histogram + registry units
# --------------------------------------------------------------------------- #
def test_histogram_percentiles_bracket_samples():
    h = Histogram()
    values = [0.001 * (i + 1) for i in range(100)]
    for v in values:
        h.observe(v)
    d = h.to_dict()
    assert d["count"] == 100
    assert d["min"] == pytest.approx(0.001)
    assert d["max"] == pytest.approx(0.1)
    # geometric buckets: each percentile within a 2x factor of the exact
    # order statistic, and ordered
    assert 0.04 <= d["p50"] <= 0.11
    assert d["p50"] <= d["p90"] <= d["p99"] <= d["max"]
    assert d["sum"] == pytest.approx(sum(values))


def test_histogram_merge_equals_combined_stream():
    rng = np.random.default_rng(3)
    a, b, both = Histogram(), Histogram(), Histogram()
    for v in rng.uniform(1e-6, 1e-2, 500):
        a.observe(float(v)); both.observe(float(v))
    for v in rng.uniform(1e-4, 1.0, 500):
        b.observe(float(v)); both.observe(float(v))
    a.merge(b)
    da, dboth = a.to_dict(), both.to_dict()
    assert da["count"] == dboth["count"] == 1000
    assert da["buckets"] == dboth["buckets"]
    assert da["p99"] == pytest.approx(dboth["p99"])
    assert da["min"] == dboth["min"] and da["max"] == dboth["max"]


def test_bucket_index_is_monotone():
    idxs = [bucket_index(10.0 ** e) for e in range(-9, 3)]
    assert idxs == sorted(idxs)
    assert bucket_index(1e-9) <= bucket_index(2e-9) <= bucket_index(4e-9)


def test_registry_labeled_counters_fold_into_flat_view():
    reg = MetricsRegistry("t")
    reg.inc("queries", 2, path="cache")
    reg.inc("queries", 3, path="planned")
    reg.inc("queries")  # unlabeled base series
    assert reg.counters_flat()["queries"] == 6
    assert reg.counter_value("queries", path="cache") == 2


def test_merge_snapshots_unions_novel_keys():
    a, b = MetricsRegistry("a"), MetricsRegistry("b")
    a.inc("shared", 1)
    b.inc("shared", 2)
    b.inc("only_in_b", 7)
    b.observe("lat", 0.5)
    merged = MetricsRegistry.merge_snapshots([a.snapshot(), b.snapshot()])
    counters = {(r["name"], tuple(sorted(r["labels"].items()))): r["value"]
                for r in merged["counters"]}
    assert counters[("shared", ())] == 3
    assert counters[("only_in_b", ())] == 7
    assert [h["name"] for h in merged["histograms"]] == ["lat"]


# --------------------------------------------------------------------------- #
# trace span trees
# --------------------------------------------------------------------------- #
def test_trace_covers_plan_hop_kernel_cache_view(race_detector):
    log = _one_hop(DSLog())
    res, tr = log.prov_query("B", "A", np.array([[2, 2]]), trace=True)
    assert res.cell_set() == {(2, 2)}
    kinds = tr.kinds()
    for kind in ("query", "cache", "plan", "view", "execute", "kernel", "hop",
                 "boxes_in", "wave", "prepare", "finalize", "merge"):
        assert kind in kinds, f"missing span kind {kind!r} in {sorted(kinds)}"
    hop = tr.spans(kind="hop")[0]
    assert hop.attrs["u"] == "B" and hop.attrs["v"] == "A"
    assert hop.attrs["qrows"] >= 1 and hop.attrs["pairs"] >= 1
    # the root spans the whole query; the stages carry their own counts
    root = tr.root
    assert root.duration > 0
    assert tr.spans(kind="boxes_in")[0].attrs == {"queries": 1, "rows": 1}
    wave = tr.spans(kind="wave")[0]
    assert wave.attrs == {"requests": 1, "rows": 1}
    assert {m.attrs["at"] for m in tr.spans(kind="merge")} == {
        "init", "hop", "canonical"}
    rendered = tr.render()
    assert "plan" in rendered and "hop" in rendered


def test_trace_exchange_events_on_sharded_store(race_detector):
    sl = ShardedDSLog(n_shards=4)
    _build_random_dag([sl], n_ops=6, seed=11)
    res, tr = sl.prov_query("a6", "a0", np.array([[3, 3]]), trace=True)
    assert "exchange" in tr.kinds()
    ex = tr.spans(kind="exchange")[0]
    assert ex.attrs["from_shard"] != ex.attrs["to_shard"]
    assert ex.attrs["boxes"] >= 1
    # the per-shard-pair labeled counter moved with it
    pair_total = sum(
        row["value"]
        for row in sl.metrics_snapshot()["counters"]
        if row["name"] == "exchange_boxes" and row["labels"]
    )
    assert pair_total >= ex.attrs["boxes"]


def test_trace_cache_hit_path_labels(race_detector):
    log = _one_hop(DSLog())
    cells = np.array([[1, 1]])
    log.prov_query("B", "A", cells)
    _, tr = log.prov_query("B", "A", cells, trace=True)
    probe = tr.spans(kind="cache")[0]
    assert probe.attrs["hit"] is True
    assert log.metrics.counter_value("queries", path="cache") == 1
    assert log.metrics.counter_value("queries", path="planned") == 1


def test_trace_off_installs_nothing():
    log = _one_hop(DSLog())
    res = log.prov_query("B", "A", np.array([[2, 2]]))
    assert res.cell_set() == {(2, 2)}
    assert log._active_trace is None


def test_maybe_span_null_path_and_real_path():
    with maybe_span(None, "x", kind="plan") as sp:
        sp.attrs["anything"] = 1  # writes on the null span are swallowed
    tr = QueryTrace()
    with maybe_span(tr, "x", kind="plan") as sp:
        sp.attrs["est"] = 4
    tr.finish()
    assert tr.spans(kind="plan")[0].attrs["est"] == 4


def _dense_engine(log, engine: str):
    """Pin the store's dense joins to one engine: ``"kernel"`` runs the
    Pallas kernels (interpreted on the CPU), as a chip runs them."""
    log.planner._executor = BatchedJoinExecutor(
        stats=log._bump,
        tuner=log.autotune,
        metrics=log.metrics,
        trace_source=log.planner._trace,
        engine=engine,
    )
    return log


@pytest.mark.parametrize("engine,stages", [
    ("kernel", ["pack", "dispatch", "readback", "pair_extract", "finalize"]),
    ("twin", ["finalize"]),
])
def test_trace_dense_join_stages_nest_in_the_launch(engine, stages, race_detector):
    log = _dense_engine(_one_hop(DSLog()), engine)
    res, tr = log.prov_query("B", "A", np.array([[2, 2], [5, 6]]), trace=True)
    assert res.cell_set() == {(2, 2), (5, 6)}
    (launch,) = tr.spans(kind="kernel")
    assert launch.name == ("kernel_launch" if engine == "kernel" else "twin")
    # the launch still runs from the first stage (pack) to the last finalize
    assert [c.kind for c in launch.children] == stages
    end = launch.start + launch.duration
    for child in launch.children:
        assert launch.start <= child.start
        assert child.start + child.duration <= end
    assert sum(c.duration for c in launch.children) <= launch.duration
    assert launch.attrs["pairs"] == 2
    if engine == "kernel":
        pack, dispatch, readback, extract, _ = launch.children
        assert dispatch.attrs["h2d_bytes"] == launch.attrs["h2d_bytes"] > 0
        assert readback.attrs["d2h_bytes"] == launch.attrs["d2h_bytes"] > 0
        assert extract.attrs["pairs"] == 2


@pytest.mark.parametrize("form", ["graph", "path"], ids=["wave", "path"])
def test_trace_hop_record_per_engine(form, race_detector):
    """A wave joins all its hops at once, so each query form records the
    hop as an instant inside a ``wave`` span."""
    log = _one_hop(DSLog())
    args = ("B", "A") if form == "graph" else (["B", "A"],)
    _, tr = log.prov_query(*args, np.array([[2, 2]]), trace=True)
    (hop,) = tr.spans(kind="hop")
    assert hop.attrs["u"] == "B" and hop.attrs["pairs"] >= 1
    assert hop.duration is None
    assert "wave" in tr.kinds()


@pytest.mark.parametrize("hop,route", [("sort", "sorted"), ("maxpool", "lattice")])
def test_trace_index_route_records_the_probe(hop, route, race_detector):
    """A sort hop scatters the frontier, and a few cells of a 2x2 stride-2
    max-pool's 48x48 output are a sparse one: the planner routes either
    through the table's interval index, which the ``index_probe`` span
    times.  The max-pool's backward table holds one row per output cell, a
    full lattice, so the index answers by grid arithmetic and counts the
    probe; the sort table's rows merge where neighbouring rows sort alike,
    so it probes sorted windows."""
    side = 64 if hop == "sort" else 96
    log = DSLog()
    log.define_array("A", (side, side))
    if hop == "sort":
        rel = sort_lineage(np.random.default_rng(0).random((side, side)))
    else:
        rel = conv2d_lineage(side, side, 2, 2, stride=2)
    log.define_array("B", rel.out_shape)
    log.add_lineage("A", "B", rel)
    assert log.lineage[0].backward.n_rows >= INDEX_MIN_ROWS
    n = rel.out_shape[0]
    cells = np.stack([np.arange(0, n, 2), np.arange(0, n, 2)[::-1]], 1)
    res, tr = log.prov_query("B", "A", cells, trace=True)
    picked = (rel.out_idx[:, None, :] == cells[None]).all(axis=2).any(axis=1)
    assert res.cell_set() == {tuple(c) for c in rel.in_idx[picked].tolist()}
    (wave,) = tr.spans(kind="wave")
    assert [c.kind for c in wave.children] == ["prepare", "index_probe", "finalize"]
    probe = wave.children[1]
    assert probe.attrs["pairs"] >= len(cells)
    assert probe.attrs["route"] == route
    assert log.io_stats["index_lattice_probes"] == (route == "lattice")
    assert "kernel" not in tr.kinds()


def test_kernel_dispatch_counters_on_a_two_segment_join(monkeypatch):
    """``h2d_bytes``, ``d2h_bytes`` and ``join_pairs`` against a hand count:
    two small segments share one masked launch, each side packed as
    ``[rows, 128]`` int32, the query side padded to a ladder step (8-row
    units), and the mask read back as int32 per (q, r)."""
    from repro.kernels import ops
    from repro.kernels.autotune import ladder_step
    from repro.kernels.range_join import SUBLANES

    monkeypatch.setattr(ops, "PROGRAMS", ops.LaunchPrograms())  # no shape built yet
    reg = MetricsRegistry("t")
    ex = BatchedJoinExecutor(stats=lambda k, n=1: reg.inc(k, n), engine="kernel")
    tables = [compress(roll_lineage(SHAPE, 3, 0), "backward"),
              compress(transpose_lineage(SHAPE, (1, 0)), "backward")]
    queries = [QueryBox.from_cells(SHAPE, np.array([[0, 0], [6, 6], [3, 1]])),
               QueryBox.from_cells(SHAPE, np.array([[1, 2], [7, 7]]))]
    ex.run([JoinRequest([q], t, merge=False, path="batched")
            for q, t in zip(queries, tables)])
    nq = sum(q.n_rows for q in queries)
    nr = sum(t.n_rows for t in tables)
    pairs = sum(
        int(np.all((q.lo[:, None] <= t.key_hi[None])
                   & (t.key_lo[None] <= q.hi[:, None]), axis=2).sum())
        for q, t in zip(queries, tables)
    )
    # the query side runs on a ladder step, widened by no more padding than
    # costs a launch nothing (ops.PAD_BUDGET) past its own step
    q_launch = nq + reg.counter_value("ladder_pad_rows")
    own = ladder_step(nq, SUBLANES)
    assert q_launch == ladder_step(q_launch, SUBLANES) >= own
    assert 0 < (q_launch - own) * (128 + nr) * 4 <= ops.PAD_BUDGET
    assert reg.counter_value("kernel_launches") == 1
    assert reg.counter_value("h2d_bytes") == (q_launch + nr) * 128 * 4
    assert reg.counter_value("d2h_bytes") == q_launch * nr * 4
    assert reg.counter_value("join_pairs") == pairs == 5


def test_tracing_off_enters_no_annotation(monkeypatch, tmp_path):
    """Untraced queries and ingest enter no profiler annotation; traced
    ones annotate every span as ``dslog.<name>``."""
    import jax.profiler

    entered = []

    class Counting:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    with DSLog.open(str(tmp_path / "store"), durability="manual") as log:
        _dense_engine(log, "kernel")
        for name in ("A", "B", "C"):
            log.define_array(name, SHAPE)
        log.add_lineage("A", "B", identity_lineage(SHAPE))
        log.commit()
        log.prov_query("B", "A", np.array([[2, 2]]))
        assert entered == []
        with log.trace_scope("ingest"):
            log.add_lineage("B", "C", flip_lineage(SHAPE, 0))
            log.commit()
        log.prov_query("C", "A", np.array([[2, 2]]), trace=True)
    for name in ("ingest", "canonical", "fsync", "query", "pack", "readback"):
        assert f"dslog.{name}" in entered


def test_trace_scope_records_the_ingest_stages(tmp_path):
    with DSLog.open(str(tmp_path / "store"), durability="manual") as log:
        log.define_array("A", SHAPE)
        log.define_array("B", SHAPE)
        with log.trace_scope("ingest") as tr:
            assert log._active_trace is tr
            log.add_lineage("A", "B", roll_lineage(SHAPE, 3, 0))
            log.commit()
        assert log._active_trace is None
    assert tr.root.kind == "ingest" and tr.root.duration > 0
    assert {"canonical", "provrc.step1", "provrc.step2", "wal.record",
            "wal.append", "commit", "fsync"} <= tr.kinds()
    for kind in ("canonical", "provrc.step1", "provrc.step2"):
        spans = tr.spans(kind)
        assert sorted(s.attrs["direction"] for s in spans) == ["backward", "forward"]
        assert all(s.attrs["rows_in"] >= s.attrs["rows_out"] > 0 for s in spans)
    assert tr.spans("canonical")[0].attrs["rows_in"] == SIDE * SIDE
    (record,) = tr.spans("wal.record")
    assert record.attrs["bytes"] > 0
    (commit,) = tr.spans("commit")
    assert [c.kind for c in commit.children] == ["fsync"]


# --------------------------------------------------------------------------- #
# EXPLAIN ANALYZE
# --------------------------------------------------------------------------- #
def test_describe_analyze_reports_est_vs_measured():
    log = _one_hop(DSLog())
    plan = log.planner.plan("B", "A")
    assert "not executed" in plan.describe(analyze=True)
    boxes = log._as_boxes("B", [np.array([[2, 2]])])
    log.planner.execute(plan, boxes)
    txt = plan.describe(analyze=True)
    assert "est_pairs=" in txt and "measured pairs=" in txt
    assert "not executed" not in txt
    assert "measured exec=" in txt  # packed-dispatch wall time in header
    # plain describe is unchanged (no measured sublines)
    assert "measured" not in plan.describe()


def test_sharded_describe_analyze_includes_exchanges(race_detector):
    sl = ShardedDSLog(n_shards=4)
    _build_random_dag([sl], n_ops=6, seed=11)
    sl.prov_query("a6", "a0", np.array([[3, 3]]))
    plan = sl.views.plan_get("a6", ("a0",)) or sl.planner.plan("a6", "a0")
    txt = plan.describe(analyze=True)
    assert "est_pairs=" in txt


# --------------------------------------------------------------------------- #
# io_stats facade + sharded union (satellite regression)
# --------------------------------------------------------------------------- #
def test_io_stats_view_is_live_and_read_only():
    log = _one_hop(DSLog())
    before = log.io_stats["kernel_launches"]
    log.prov_query("B", "A", np.array([[2, 2]]))
    assert log.io_stats["kernel_launches"] > before
    with pytest.raises(TypeError):
        log.io_stats["kernel_launches"] = 0
    assert set(dict(log.io_stats)) == set(log.io_stats)


def test_sharded_io_stats_unions_shard_minted_counters(race_detector):
    sl = ShardedDSLog(n_shards=2)
    _build_random_dag([sl], n_ops=4, seed=5)
    # a counter no registry seeds: minted only inside one shard (the bug
    # was aggregating over a hardcoded key list, dropping these)
    sl.shard(0).metrics.inc("wal_replayed", 3)
    sl.shard(1).metrics.inc("exchange_boxes", 2, from_shard="1", to_shard="0")
    stats = sl.io_stats
    assert stats["wal_replayed"] == 3
    assert stats["exchange_boxes"] >= 2  # labeled series fold into the base
    # facade-minted counters still present
    assert "shards_loaded" in stats


def test_sharded_metrics_snapshot_merges_all_registries(race_detector):
    sl = ShardedDSLog(n_shards=2)
    _build_random_dag([sl], n_ops=4, seed=5)
    sl.prov_query("a4", "a0", np.array([[1, 1]]))
    snap = sl.metrics_snapshot()
    assert snap["registry"] == "dslog-root"
    names = {r["name"] for r in snap["counters"]}
    assert "kernel_launches" in names  # shard-side work
    assert "queries" in names  # facade-side work


# --------------------------------------------------------------------------- #
# sidecar, exporters, CLI, health
# --------------------------------------------------------------------------- #
def _store_with_traffic(d):
    log = DSLog.open(os.path.join(d, "s"))
    _one_hop(log)
    log.prov_query("B", "A", np.array([[2, 2]]))
    log.prov_query("B", "A", np.array([[2, 2]]))  # cache hit
    log.save()
    return log


def test_telemetry_sidecar_schema_and_percentiles():
    with tempfile.TemporaryDirectory() as d:
        log = _store_with_traffic(d)
        try:
            path = os.path.join(d, "s", "telemetry.json")
            snap = json.loads(open(path).read())
            counts = validate_telemetry(snap)
            assert counts["counters"] > 0 and counts["histograms"] > 0
            assert snap["schema"] == TELEMETRY_SCHEMA
            hists = {h["name"] for h in snap["histograms"]}
            assert "wal_fsync_seconds" in hists
            assert "query_seconds" in hists
            qs = [h for h in snap["histograms"] if h["name"] == "query_seconds"]
            assert all(h["labels"].get("path") for h in qs)
            assert all(h["p50"] <= h["p99"] <= h["max"] * 2 for h in qs)
        finally:
            log.close()


def test_telemetry_sidecar_not_restored_on_load():
    with tempfile.TemporaryDirectory() as d:
        _store_with_traffic(d).close()
        re = DSLog.load(os.path.join(d, "s"))
        assert re.io_stats["tables_loaded"] == 0
        assert re.io_stats["cache_hits"] == 0


def test_prometheus_render_and_parse_roundtrip():
    log = _one_hop(DSLog())
    log.prov_query("B", "A", np.array([[2, 2]]))
    log.metrics.observe("query_seconds", 0.01, path="planned", engine="batched")
    text = render_prometheus(telemetry_snapshot(log))
    assert parse_prometheus(text) > 10
    assert "dslog_kernel_launches_total" in text
    assert 'le="+Inf"' in text


def test_validate_telemetry_rejects_malformed():
    with pytest.raises(ValueError):
        validate_telemetry({"schema": "nope"})
    with pytest.raises(ValueError):
        validate_telemetry(
            {"schema": TELEMETRY_SCHEMA, "store": "X", "registry": "r",
             "counters": [{"name": 3, "labels": {}, "value": 1}],
             "gauges": [], "histograms": []}
        )
    with pytest.raises(ValueError):
        parse_prometheus("bad{unterminated 3\n")


def test_dstat_cli_dump_diff(capsys):
    with tempfile.TemporaryDirectory() as d:
        log = _store_with_traffic(d)
        root = os.path.join(d, "s")
        try:
            assert dstat.main(["dump", root, "--json"]) == 0
            snap = json.loads(capsys.readouterr().out)
            validate_telemetry(snap)

            assert dstat.main(["dump", root, "--prometheus"]) == 0
            assert parse_prometheus(capsys.readouterr().out) > 0

            assert dstat.main(["dump", root]) == 0
            assert "counters:" in capsys.readouterr().out

            old = os.path.join(d, "old.json")
            with open(old, "w") as fh:
                json.dump(snap, fh)
            log.prov_query("B", "A", np.array([[5, 5]]))
            log.save()
            assert dstat.main(["diff", old, root, "--json"]) == 0
            delta = json.loads(capsys.readouterr().out)
            assert delta["counters"].get("queries{path=planned}", 0) >= 1
        finally:
            log.close()
        assert dstat.main(["dump", os.path.join(d, "missing")]) == 2


def test_health_reports_flags_and_fsck():
    with tempfile.TemporaryDirectory() as d:
        log = _store_with_traffic(d)
        try:
            rep = log.health()
            assert rep["ok"] is True and rep["flags"] == []
            assert rep["fsck"] is not None
            log.metrics.inc("wal_replayed", 5)
            rep = log.health(run_fsck=False)
            assert [f["flag"] for f in rep["flags"]] == ["wal-replayed"]
            assert rep["ok"] is True  # warnings don't fail health
        finally:
            log.close()


# --------------------------------------------------------------------------- #
# bit-identity tracing on/off (DSLog + ShardedDSLog N in {1, 4})
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("make", [
    lambda: DSLog(),
    lambda: ShardedDSLog(n_shards=1),
    lambda: ShardedDSLog(n_shards=4),
], ids=["dslog", "sharded1", "sharded4"])
def test_bit_identical_results_tracing_on_off(make, race_detector):
    plain, traced = make(), make()
    names = _build_random_dag([plain, traced], n_ops=8, seed=23)
    cells = np.array([[2, 3], [7, 0], [4, 4]])
    for src, dst in [(names[-1], names[0]), (names[0], names[-1])]:
        a = plain.prov_query(src, dst, cells)
        b, tr = traced.prov_query(src, dst, cells, trace=True)
        assert tr.root.duration > 0 and "hop" in tr.kinds()
        assert a.shape == b.shape
        assert a.lo.tobytes() == b.lo.tobytes()
        assert a.hi.tobytes() == b.hi.tobytes()


# --------------------------------------------------------------------------- #
# tracing-off instrument cost
# --------------------------------------------------------------------------- #
def test_tracing_off_instrument_cost_bounded():
    """The per-query telemetry tax when tracing is off stays sub-10us/op.

    The off-path adds: one ``_active_trace is None`` check per site, a few
    null-context allocations, and one ``observe`` + ``inc`` pair per query.
    Bound each primitive at 50us/op average over 20k calls — two orders of
    magnitude above their real cost, so the test only fails on a genuine
    regression (e.g. a span allocated while tracing is off).
    """
    n = 20_000
    reg = MetricsRegistry("bench")
    t0 = time.perf_counter()
    for _ in range(n):
        reg.inc("queries", path="planned")
    inc_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        reg.observe("query_seconds", 1e-4, path="planned", engine="batched")
    obs_us = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        with maybe_span(None, "plan", kind="plan") as sp:
            sp.attrs["x"] = 1
    null_us = (time.perf_counter() - t0) / n * 1e6
    assert inc_us < 50, f"registry.inc too slow: {inc_us:.2f}us/op"
    assert obs_us < 50, f"registry.observe too slow: {obs_us:.2f}us/op"
    assert null_us < 50, f"null span too slow: {null_us:.2f}us/op"
