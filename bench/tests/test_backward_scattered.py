"""The U-Net tile and backward debugging over scattered cells, on the CPU.

The configuration's crop and up-conv hops equal the program's own capture;
graph-form queries over U-Net's five routes, and backward queries over the
Figs 8/9 workflows, equal the plain reference; fresh scattered frontiers
after a warm-up build no program on the kernel route; and the new readers
read what the program's spans and counters hold.
"""

import numpy as np
import pytest

from bench import layouts, oracle, run, store, workflows
from bench.context import RunContext
from bench.loops import closed
from bench.mix import Query, QueryStream


def _config(name: str, side: int | None = None) -> dict:
    cfg = workflows.load_config(name)
    cfg["side"] = side or cfg["test_side"]
    return cfg


UNET = _config("unet_tile")
UNET_HOPS = [(k, spec) for k, spec, _, _ in workflows._steps(UNET, UNET["pipelines"][0])[0]
             if spec["op"] in ("crop", "upsample")]


@pytest.mark.parametrize("k,spec", UNET_HOPS, ids=[f"{s['op']}{k}" for k, s in UNET_HOPS])
def test_crop_and_upconv_hops_equal_the_programs_capture(k, spec):
    from repro.core.capture import slice_lineage, upsample2d_lineage

    hop = list(workflows.pipeline_hops(UNET, UNET["pipelines"][0]))[k]
    h, w = hop.in_shape
    if spec["op"] == "crop":
        m = spec["margin"]
        want = slice_lineage(hop.in_shape, (m, m), (h - m, w - m))
    else:
        want = upsample2d_lineage(h, w, spec["stride"])
    assert store.to_relation(hop) == want


def _kernel_route(monkeypatch):
    """Every dense join through the Pallas kernels in interpret mode, on
    launch programs built from scratch."""
    from repro.core import query
    from repro.kernels import ops

    init = query.BatchedJoinExecutor.__init__
    monkeypatch.setattr(query.BatchedJoinExecutor, "__init__",
                        lambda self, *a, **kw: init(self, *a, **{**kw, "engine": "kernel"}))
    monkeypatch.setattr(ops, "PROGRAMS", ops.LaunchPrograms())


def _open(cfg: dict, tmp_path):
    from repro.core.catalog import DSLog

    root = str(tmp_path / cfg["name"])
    store.build(cfg, root)
    return DSLog.load(root)


def _query(cfg, pipe, src, dst, k, rng) -> Query:
    shapes = workflows.array_shapes(cfg, pipe)
    forward = workflows.routes(cfg, pipe, src, dst)[1]
    cells = layouts.get("scattered").draw(rng, shapes[src], k, None)
    return Query(pipe["name"], src, dst, (), forward, k, cells, shapes[src])


def _assert_equal_to_the_reference(log, cfg, queries):
    want = oracle.answer_queries(cfg, queries)
    for q, w in zip(queries, want):
        res = log.prov_query(*q.args())
        np.testing.assert_array_equal(oracle.boxes_to_flat(res.lo, res.hi, res.shape), w,
                                      err_msg=q.label)


def test_unet_graph_queries_equal_the_reference(tmp_path, monkeypatch):
    """Backward from scattered output cells and forward from scattered input
    cells, in graph form: the planner routes over all five paths and merges
    where they converge."""
    _kernel_route(monkeypatch)
    log = _open(UNET, tmp_path)
    pipe = UNET["pipelines"][0]
    names = list(workflows.array_shapes(UNET, pipe))
    rng = np.random.default_rng(2**31 + 171)
    queries = [_query(UNET, pipe, names[-1], names[0], k, rng) for k in (1, 3, 7, 16)]
    queries += [_query(UNET, pipe, names[0], names[-1], k, rng) for k in (2, 40, 400)]
    queries += [_query(UNET, pipe, names[19], names[0], 60, rng)]  # from a skip source
    _assert_equal_to_the_reference(log, UNET, queries)
    assert log.io_stats["kernel_launches"] > 0 and log.io_stats["twin_launches"] == 0


def test_fig89_conv_backward_scattered_queries_equal_the_reference(tmp_path, monkeypatch):
    _kernel_route(monkeypatch)
    cfg = _config("fig89_conv")
    log = _open(cfg, tmp_path)
    rng = np.random.default_rng(2**31 + 172)
    queries = []
    for pipe, src in zip(cfg["pipelines"], ("a4", "a6")):
        names = list(workflows.array_shapes(cfg, pipe))
        for k in (1, 9, 90, 300):
            queries.append(_query(cfg, pipe, f"{pipe['name']}_{src}", names[0], k, rng))
    assert not any(q.forward for q in queries)
    _assert_equal_to_the_reference(log, cfg, queries)
    assert log.io_stats["twin_launches"] == 0


def test_fresh_scattered_frontiers_after_warm_up_build_no_program(tmp_path, monkeypatch):
    """After the warm-up the harness runs (four blocks of the mix), 36 fresh
    scattered frontiers of changing sizes run on the kernel route and build
    no program; the numpy twin gives the same answers."""
    import jax

    _kernel_route(monkeypatch)
    built = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _d, **_kw: built.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    log = _open(UNET, tmp_path)
    mix = {"loop": "closed", "warm_blocks": 4, "classes": [
        {"pipelines": "all", "src": "first", "dst": "last", "form": "graph",
         "cells": "scattered", "k": [5, 50, 500]}]}
    warm = QueryStream(UNET, mix, 2**31 + 173, part="warm")
    for i in range(mix["warm_blocks"] * len(warm)):
        log.prov_query(*warm[i].args())
    n_warm = len(built)
    fresh = QueryStream(UNET, mix, 2**31 + 173)
    answers = [log.prov_query(*fresh[i].args()) for i in range(36)]
    assert n_warm > 0 and len(built) == n_warm
    assert log.io_stats["twin_launches"] == 0

    twin_log = _open(UNET, tmp_path / "twin")
    twin_log.planner.executor._engine = "twin"
    for i, got in enumerate(answers):
        want = twin_log.prov_query(*fresh[i].args())
        np.testing.assert_array_equal(got.lo, want.lo)
        np.testing.assert_array_equal(got.hi, want.hi)
    assert twin_log.io_stats["twin_launches"] > 0


def test_traced_unet_cell_reads_its_fanin_merges_and_padding(small_bench, capsys, monkeypatch):
    kinds = set()
    real = closed.add_span_seconds

    def recorded(into, tr):
        out = real(into, tr)
        kinds.update(out)
        return out

    monkeypatch.setattr(closed, "add_span_seconds", recorded)
    rc, res, _ = small_bench("unet.backward_scattered", 2**31 + 174, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]
    assert "fanin_merge" in kinds and "merge" in kinds
    assert res["metrics"]["fanin_merge_ms"]["value"] > 0
    assert res["metrics"]["ladder_pad_pct"]["value"] >= 0
    assert "range_join_roofline" not in res["metrics"]  # no device plane on the CPU


def test_linear_cells_hold_no_fanin_merge_and_merge_ms_reads_every_merge(
        small_bench, capsys, monkeypatch):
    seen = []
    real = closed.add_span_seconds

    def recorded(into, tr):
        seen.append((tr, real(into, tr)))
        return seen[-1][1]

    monkeypatch.setattr(closed, "add_span_seconds", recorded)
    rc, res, _ = small_bench("conv.fig89_forward", 2**31 + 175, trace=1, capsys=capsys)
    assert rc == 0 and res["correct"], res["checks"]
    assert seen and not any(tr.spans("fanin_merge") for tr, _ in seen)
    assert "fanin_merge_ms" not in res["metrics"]
    want = 1e3 * sum(s.duration for tr, _ in seen for s in tr.spans("merge")) / len(seen)
    assert res["metrics"]["merge_ms"]["value"] == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", ["fanin_merge_ms", "ladder_pad_pct", "range_join_roofline"])
def test_new_readers_read_nothing_from_a_program_without_their_counters(name):
    """A parent program has neither the counters nor the span: no value."""
    ctx = RunContext(latencies_s=[0.1], counters={"batch_rows": 100, "h2d_bytes": 10},
                     spans=[{"query": 0.1, "merge": 0.01}])
    assert run._reader(name)(ctx) is None


def test_roofline_share_from_counters_and_module_time(monkeypatch):
    import jax

    from bench.devtrace import Reduced

    ctx = RunContext(counters={"h2d_bytes": 8_190_000, "mask_cells_written": 1_000_000})
    ctx.device = Reduced(window_s=1.0, busy_s=1e-3, n_devices=1,
                         module_s={"jit_range_join_mask": 1e-4, "jit_other": 1.0})
    kind = type("Device", (), {"device_kind": "TPU v5 lite"})
    monkeypatch.setattr(jax, "devices", lambda: [kind()])
    got = run._reader("range_join_roofline")(ctx)
    assert got == pytest.approx(100 * (8_190_000 + 4_000_000) / (1e-4 * 819e9))
    assert 0 < got <= 100
    kind.device_kind = "TPU v9"
    with pytest.raises(ValueError, match="TPU v9"):
        run._reader("range_join_roofline")(ctx)


def test_ladder_pad_pct_is_padded_rows_over_packed_rows():
    ctx = RunContext(counters={"batch_rows": 400, "ladder_pad_rows": 100})
    assert run._reader("ladder_pad_pct")(ctx) == 25.0
    assert run._reader("ladder_pad_pct")(RunContext(counters={"ladder_pad_rows": 0})) is None


# at test_side 188 every backward U-Net answer is a whole rectangle of the
# input (its receptive field), which ``approximate`` (the bounding box)
# leaves as it is; at side 572 a 15-cell answer is 284,672 of 327,184 cells
@pytest.mark.parametrize("workload,fault", [
    ("conv.backward_scattered", None),
    ("conv.backward_scattered", "approximate"),
    ("conv.backward_scattered", "drop_box"),
    ("unet.backward_scattered", None),
    ("unet.backward_scattered", "drop_box"),
])
def test_correct_catches_a_planted_fault_in_the_new_cells(small_bench, capsys, monkeypatch,
                                                          workload, fault):
    from bench.tests import faults

    if fault:
        faults.plant(fault, monkeypatch.setattr)
    rc, res, _ = small_bench(workload, 2**31 + 176, trace=0, capsys=capsys)
    assert rc == 0 and res["attempted"] > 0
    assert res["correct"] is (fault is None), res["checks"]
    assert res["checks"]["window_programs"] == {"value": 0, "limit": 0}
