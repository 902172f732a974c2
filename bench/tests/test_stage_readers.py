"""Readers of the program's stage spans, and idle time charged to them."""

from types import SimpleNamespace as NS

import pytest

from bench import devtrace, run
from bench.context import RunContext
from bench.loops import closed, ingest

STAGES = ("prepare", "index_probe", "pack", "readback", "pair_extract",
          "finalize", "merge")


@pytest.mark.parametrize("workload", ["numpy.fig89_forward", "conv.fig89_forward"])
def test_traced_query_cell_reports_every_stage(small_bench, capsys, monkeypatch, workload):
    from repro.core import planner, query

    # tables this small stay under the index route's row threshold
    for mod, name in ((query, "_INDEX_MIN_ROWS"), (planner, "INDEX_MIN_ROWS")):
        monkeypatch.setattr(mod, name, 100)
    for mod, name in ((query, "_DENSE_FRACTION"), (planner, "DENSE_FRACTION")):
        monkeypatch.setattr(mod, name, float("inf"))
    seen = []  # (trace, span seconds by kind) of every window query
    real = closed.add_span_seconds

    def recorded(into, tr):
        seen.append((tr, real(into, tr)))
        return seen[-1][1]

    monkeypatch.setattr(closed, "add_span_seconds", recorded)
    rc, res, _ = small_bench(workload, 2**31 + 17, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]
    assert {f"{k}_ms" for k in STAGES} <= set(res["metrics"])
    for tr, by_kind in seen:
        assert sum(by_kind.get(k, 0.0) for k in STAGES) <= by_kind["query"]
        assert by_kind["plan"] == sum(s.duration for s in tr.spans("plan"))
        assert by_kind.get("kernel", 0.0) == sum(s.duration for s in tr.spans("kernel"))
    n = len(seen)
    for metric, kind in [("plan_ms", "plan"), ("join_wall_ms", "kernel"),
                         *((f"{k}_ms", k) for k in STAGES)]:
        want = 1e3 * sum(q.get(kind, 0.0) for _, q in seen) / n
        assert res["metrics"][metric]["value"] == pytest.approx(want, rel=1e-12)


def test_traced_ingest_reports_its_program_shares(small_bench, capsys, monkeypatch):
    counted = []
    real = ingest._add_spans

    def recorded(spans, routes, tr):
        counted[:] = [routes]
        real(spans, routes, tr)

    monkeypatch.setattr(ingest, "_add_spans", recorded)
    rc, res, _ = small_bench("numpy.ingest", 2**31 + 19, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]
    for name in ("canonical_share_pct", "provrc_encode_share_pct", "wal_record_share_pct"):
        assert 0 < res["metrics"][name]["value"] <= 100, name
    (routes,) = counted
    # one canonical span per direction of every committed hop
    assert sum(routes.values()) == 2 * res["attempted"]
    assert set(routes) <= {"presorted", "packed", "lexsort", "reused"}


def test_an_untraced_ingest_collects_no_spans(small_bench, capsys, monkeypatch):
    monkeypatch.setattr(ingest, "_add_spans", None)  # any call would fail
    rc, res, _ = small_bench("numpy.ingest", 2**31 + 23, trace=0, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]


def _ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def _planes(program_spans, other_thread=()):
    host = NS(name="/host:CPU", lines=[
        NS(name="main", events=[
            _ev("bench.window", 1000, 9000),
            _ev("bench.query", 1000, 4000),
            _ev("bench.query", 5000, 5000),
        ]),
        NS(name="program", events=program_spans),
        NS(name="flusher", events=list(other_thread)),
    ])
    dev = NS(name="/device:TPU:0", lines=[NS(name="XLA Modules", events=[
        _ev("jit_range_join_mask(1)", 2000, 1500),
        _ev("jit_range_join_mask(2)", 6000, 500),
    ])])
    return [host, dev]


NESTED = [
    _ev("dslog.query", 1000, 3900),
    _ev("dslog.execute", 1200, 3600),
    _ev("dslog.wave", 1200, 2300),  # starts with execute, ends first: inner
    _ev("dslog.pack", 1400, 500),
    _ev("dslog.readback", 3500, 600),
    _ev("dslog.query", 5100, 4800),
    _ev("dslog.merge", 6600, 3000),
]


def test_idle_is_charged_to_the_innermost_program_span():
    """Idle stretches [1000, 2000], [3500, 6000] and [6500, 10000] split by
    the innermost open span; the stretch between the two queries and the
    tail after the second go to none; a span on another thread that starts
    later is the innermost while it runs."""
    fsync = [_ev("dslog.fsync", 8000, 200)]
    red = devtrace.reduce_planes(_planes(NESTED, fsync), by_span=True)
    got = {k: round(v * 1e9) for k, v in red.idle_by_span.items()}
    assert got == {"query": 1600, "wave": 300, "pack": 500, "readback": 600,
                   "execute": 700, "merge": 2800, "fsync": 200, None: 300}
    assert sum(got.values()) == round(sum(s for _, s in red.gaps) * 1e9)
    ctx = RunContext(device=red)
    assert run._reader("idle_unattributed_pct")(ctx) == pytest.approx(
        100.0 * 1900 / 7000)


def test_idle_by_span_leaves_the_gaps_and_their_labels_alone():
    """The split changes no other field of the reduction; without it the
    program's spans change nothing at all."""
    plain = devtrace.reduce_planes(_planes([]))
    traced = devtrace.reduce_planes(_planes(NESTED), by_span=True)
    for f in ("window_s", "busy_s", "n_devices", "module_s", "op_s", "gaps"):
        assert getattr(traced, f) == getattr(plain, f), f
    assert [label for label, _ in traced.gaps] == ["query"] * 3
    assert plain.idle_by_span == {}
    assert devtrace.reduce_planes(_planes(NESTED)) == plain
    split = devtrace.reduce_planes(_planes([]), by_span=True).idle_by_span
    assert split == {None: pytest.approx(7e-6)}


@pytest.mark.parametrize("ctx", [
    RunContext(),  # untraced
    RunContext(device=devtrace.Reduced(1.0, 0.0, 1, idle_by_span={None: 1.0})),
    RunContext(device=devtrace.Reduced(1.0, 1.0, 1)),  # never idle
], ids=["no_trace", "no_program_spans", "no_idle"])
def test_idle_unattributed_pct_reports_nothing_to_read(ctx):
    assert run._reader("idle_unattributed_pct.ingest")(ctx) is None


@pytest.mark.parametrize("name", [f"{k}_ms" for k in STAGES])
def test_a_stage_no_query_reached_reports_nothing(name):
    ctx = RunContext(spans=[{"query": 0.003, "plan": 0.001}, {"query": 0.004, "plan": 0.002}])
    assert run._reader(name)(ctx) is None


@pytest.mark.parametrize("name,kinds", [
    ("canonical_share_pct", ("canonical",)),
    ("provrc_encode_share_pct", ("provrc.step1", "provrc.step2")),
    ("wal_record_share_pct", ("wal.record",)),
])
def test_ingest_shares_of_the_window(name, kinds):
    read = run._reader(name)
    spans = {"ingest": 9.0, "canonical": 0.5, "provrc.step1": 3.0,
             "provrc.step2": 2.0, "wal.record": 1.5, "commit": 0.1}
    ctx = RunContext(window_s=10.0, ingest={"rows": 1, "add_s": 8.0,
                                            "commit_s": 0.2, "spans": spans})
    assert read(ctx) == pytest.approx(10.0 * sum(spans[k] for k in kinds))
    # an untraced run, or a program without the spans, reports nothing
    ctx.ingest = {"rows": 1, "add_s": 8.0, "commit_s": 0.2}
    assert read(ctx) is None
    ctx.ingest["spans"] = {"ingest": 9.0}
    assert read(ctx) is None
