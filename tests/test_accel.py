"""Batched accelerator execution of plan steps (ISSUE 5).

The contract under test: the :class:`BatchedJoinExecutor` — packing a plan
frontier's dense joins into one blocked evaluation — returns the answers of
independent oracles (a brute-force overlap join per request; the recorded
uncompressed relations composed along the DAG per query), for DSLog and
ShardedDSLog, serial and ``parallel=N``; and the Pallas dense path's
padding, int32-overflow, and lane-capacity limits are enforced instead of
silently wrong.
"""

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.catalog import DSLog
from repro.core.query import (
    BatchedJoinExecutor,
    JoinRequest,
    QueryBox,
    dense_backend,
    merge_boxes,
    theta_join,
    theta_join_inverse_batch,
)
from repro.core.shard import ShardedDSLog
from repro.core.table import CompressedTable

from test_shard import (
    SHAPE,
    SIDE,
    _assert_oracle_answer,
    _build_random_dag,
    _oracle_cells,
    _oracle_path_cells,
)

rng = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _race_detect(race_detector):
    """Covers the parallel∈{2,4} plan-execution cases below."""
    yield


def _random_table(nr, l=2, m=2, span=500, seed=None):
    r = np.random.default_rng(seed if seed is not None else rng.integers(1 << 30))
    key_lo = r.integers(0, span, (nr, l))
    key_hi = key_lo + r.integers(0, 4, (nr, l))
    val_lo = r.integers(-3, 0, (nr, m))
    val_hi = val_lo + r.integers(0, 6, (nr, m))
    return CompressedTable(
        key_shape=(span + 10,) * l,
        val_shape=(span + 10,) * m,
        key_lo=key_lo,
        key_hi=key_hi,
        val_lo=val_lo,
        val_hi=val_hi,
        val_ref=r.integers(0, l, (nr, m)),
    )


def _boxes(shape, n, span=400, width=30, seed=0):
    r = np.random.default_rng(seed)
    lo = r.integers(0, span, (n, len(shape)))
    return QueryBox(shape, lo, lo + r.integers(0, width, (n, len(shape))))


def _assert_boxes_equal(a, b):
    assert a.shape == b.shape
    assert a.lo.tobytes() == b.lo.tobytes()
    assert a.hi.tobytes() == b.hi.tobytes()


def _brute_join(q: QueryBox, t: CompressedTable, inverse: bool) -> QueryBox:
    """Reference θ-join, one query row at a time against every table row.

    Natural join: rows whose key box meets the query row, value intervals
    de-relativized onto the key intersection.  Inverse join: each relative
    value attribute ``val = key_ref + δ`` bounds its key attribute by
    ``[q_lo − δ_hi, q_hi − δ_lo]``, an absolute one must meet the query;
    rows whose bounded key box stays non-empty contribute it.
    """
    out_shape = t.key_shape if inverse else t.val_shape
    n_out = t.n_key if inverse else t.n_val
    key_lo, key_hi = t.key_lo.astype(np.int64), t.key_hi.astype(np.int64)
    val_lo, val_hi = t.val_lo.astype(np.int64), t.val_hi.astype(np.int64)
    los, his = [np.zeros((0, n_out), np.int64)], [np.zeros((0, n_out), np.int64)]
    for i in range(q.n_rows):
        if inverse:
            lo, hi = key_lo.copy(), key_hi.copy()
            ok = np.ones(t.n_rows, bool)
            for a in range(t.n_val):
                ref = t.val_ref[:, a]
                ok &= (ref >= 0) | (
                    (val_lo[:, a] <= q.hi[i, a]) & (q.lo[i, a] <= val_hi[:, a])
                )
                for j in range(t.n_key):
                    on = ref == j
                    lo[on, j] = np.maximum(lo[on, j], q.lo[i, a] - val_hi[on, a])
                    hi[on, j] = np.minimum(hi[on, j], q.hi[i, a] - val_lo[on, a])
            ok &= np.all(lo <= hi, axis=1)
        else:
            ilo = np.maximum(q.lo[i], key_lo)
            ihi = np.minimum(q.hi[i], key_hi)
            ok = np.all(ilo <= ihi, axis=1)
            lo, hi = val_lo.copy(), val_hi.copy()
            for a in range(t.n_val):
                for j in range(t.n_key):
                    on = t.val_ref[:, a] == j
                    lo[on, a] += ilo[on, j]
                    hi[on, a] += ihi[on, j]
        los.append(lo[ok])
        his.append(hi[ok])
    return QueryBox(out_shape, np.concatenate(los), np.concatenate(his))


def _assert_equals_brute(got: QueryBox, want: QueryBox, merge: bool) -> None:
    """Unmerged: the same multiset of boxes.  Merged: the same boxes as
    merging the reference's, which depends on that multiset alone."""
    if merge:
        _assert_boxes_equal(got, merge_boxes(want))
        return
    assert got.shape == want.shape

    def rows(b):
        return sorted(map(tuple, np.concatenate([b.lo, b.hi], axis=1).tolist()))

    assert rows(got) == rows(want)


# --------------------------------------------------------------------------- #
# Executor vs a brute-force join per request
# --------------------------------------------------------------------------- #
def test_executor_matches_per_request_joins_exactly():
    """Every route/direction/merge combination, one packed run."""
    reqs, oracle = [], []
    for trial in range(10):
        t = _random_table(int(rng.integers(1, 2000)))
        inverse = trial % 2 == 1
        shape = t.val_shape if inverse else t.key_shape
        qs = [
            _boxes(shape, int(rng.integers(0, 25)), seed=trial * 7 + j)
            for j in range(int(rng.integers(0, 3)))
        ]
        want = [_brute_join(q, t, inverse) for q in qs]
        for path in ("auto", "dense", "index", "batched"):
            merge = (trial + len(reqs)) % 3 == 0
            reqs.append(
                JoinRequest(qs, t, inverse=inverse, merge=merge, path=path)
            )
            oracle.append((want, merge))
    got = BatchedJoinExecutor().run(reqs)
    assert len(got) == len(oracle)
    for g_list, (w_list, merge) in zip(got, oracle):
        assert len(g_list) == len(w_list)
        for g, w in zip(g_list, w_list):
            _assert_equals_brute(g, w, merge)


def test_executor_worker_count_is_bit_identical():
    reqs = [
        JoinRequest(
            [_boxes((510,) * 2, 40, seed=k)],
            _random_table(600, seed=k),
            merge=False,
            path="dense",
        )
        for k in range(9)
    ]
    want = BatchedJoinExecutor().run(reqs)
    for workers in (2, 4, 9):
        got = BatchedJoinExecutor().run(reqs, workers=workers)
        for g_list, w_list in zip(got, want):
            for g, w in zip(g_list, w_list):
                _assert_boxes_equal(g, w)


# --------------------------------------------------------------------------- #
# Property test: prov_query == the uncompressed oracle on random DAGs
# --------------------------------------------------------------------------- #
@settings(max_examples=10, deadline=None)
@given(
    n_ops=st.integers(4, 9),
    seed=st.integers(0, 10_000),
    n_shards=st.sampled_from([1, 4]),
)
def test_batched_execution_equals_perhop_oracle(n_ops, seed, n_shards):
    """Graph and path forms, serial and ``parallel=N``, single and sharded
    stores: every answer is the recorded relations' composed cell set."""
    log = DSLog()
    sharded = ShardedDSLog(n_shards=n_shards)
    record: list = []
    names = _build_random_dag([log, sharded], n_ops, seed, record)
    r = np.random.default_rng(seed + 1)
    cells = np.stack([r.integers(0, SIDE, 3), r.integers(0, SIDE, 3)], axis=1)
    src, dst = names[0], names[-1]
    path = [src, names[1], names[2]]
    want_path = _oracle_path_cells(record, path, cells)
    for store in (log, sharded):
        for s, t, q in [(src, dst, cells), (dst, src, cells[:1])]:
            want = _oracle_cells(record, s, t, q)
            for kw in (dict(), dict(parallel=2), dict(parallel=4)):
                _assert_oracle_answer(store.prov_query(s, t, q, **kw), want)
        # path form through the same engine
        _assert_oracle_answer(store.prov_query(path, cells), want_path)


def _force_kernel_engine(store):
    """Pin the store's batched executor to the segmented Pallas kernel path
    (interpreted here — no TPU), replacing the planner's lazy default."""
    store.planner._executor = BatchedJoinExecutor(
        stats=store._bump,
        tuner=getattr(store, "autotune", None),
        engine="kernel",
    )


@settings(max_examples=6, deadline=None)
@given(
    n_ops=st.integers(4, 8),
    seed=st.integers(0, 10_000),
    n_shards=st.sampled_from([1, 4]),
)
def test_kernel_engine_blockdiag_equals_perhop_oracle(n_ops, seed, n_shards):
    """ISSUE 8 tentpole, end to end: ``engine="kernel"`` forces every dense
    segment through ``segmented_range_join_pairs`` (block-diagonal schedule
    when the frontier warrants it) — equal to the uncompressed oracle on
    random DAGs, DSLog and ShardedDSLog, serial and parallel, under the
    autouse race detector."""
    log = DSLog()
    sharded = ShardedDSLog(n_shards=n_shards)
    record: list = []
    names = _build_random_dag([log, sharded], n_ops, seed, record)
    r = np.random.default_rng(seed + 1)
    cells = np.stack([r.integers(0, SIDE, 3), r.integers(0, SIDE, 3)], axis=1)
    src, dst = names[0], names[-1]
    want = _oracle_cells(record, src, dst, cells)
    for store in (log, sharded):
        store.views.enabled = False  # answer cache would serve the repeats
        _force_kernel_engine(store)
        for kw in (dict(), dict(parallel=2), dict(parallel=4)):
            _assert_oracle_answer(store.prov_query(src, dst, cells, **kw), want)
        assert store.io_stats["batch_tiles_visited"] > 0


def test_batch_and_multi_target_forms_parity():
    log = DSLog()
    record: list = []
    names = _build_random_dag([log], 7, seed=42, record=record)
    r = np.random.default_rng(5)
    cells = np.stack([r.integers(0, SIDE, 4), r.integers(0, SIDE, 4)], axis=1)
    src, dst = names[0], names[-1]
    batch = [cells, cells[:2]]
    got = log.prov_query_batch(src, dst, batch)
    assert len(got) == len(batch)
    for g, q in zip(got, batch):
        _assert_oracle_answer(g, _oracle_cells(record, src, dst, q))
    mids = [names[2], dst]
    got_m = log.prov_query(src, mids, cells, parallel=2)
    assert set(got_m) == set(mids)
    for k in mids:
        _assert_oracle_answer(got_m[k], _oracle_cells(record, src, k, cells))


# --------------------------------------------------------------------------- #
# io_stats batching meters
# --------------------------------------------------------------------------- #
def test_io_stats_meter_batched_dispatches():
    log = DSLog()
    names = _build_random_dag([log], 6, seed=9)
    cells = np.array([[1, 2], [5, 6]])
    base = dict(log.io_stats)
    log.prov_query(names[0], names[-1], cells)
    assert log.io_stats["kernel_launches"] > base["kernel_launches"]
    assert log.io_stats["joins_packed"] > base["joins_packed"]
    assert (
        log.io_stats["batch_rows_padded"] >= log.io_stats["batch_rows"] > 0
    )
    # tile meters (ISSUE 8): every dense dispatch charges its schedule
    assert log.io_stats["batch_tiles_visited"] > 0
    assert log.io_stats["batch_tiles_skipped"] >= 0
    # workers split a wave's segments, not its launch: the same query
    # meters the same dispatches serial and with parallel=2
    log.views.enabled = False  # the answer cache would serve the repeats
    launches = []
    for kw in (dict(), dict(parallel=2)):
        base = dict(log.io_stats)
        log.prov_query(names[0], names[-1], cells, **kw)
        launches.append(log.io_stats["kernel_launches"] - base["kernel_launches"])
    assert launches[0] == launches[1] > 0


def test_sharded_io_stats_aggregate_batching_counters():
    sharded = ShardedDSLog(n_shards=2)
    names = _build_random_dag([sharded], 6, seed=9)
    sharded.prov_query(names[0], names[-1], np.array([[1, 2]]))
    assert sharded.io_stats["kernel_launches"] > 0
    # the facade aggregates the tile meters across root + shards
    assert sharded.io_stats["batch_tiles_visited"] > 0
    assert "batch_tiles_skipped" in sharded.io_stats


# --------------------------------------------------------------------------- #
# Bugfix: int32 overflow routes to the numpy dense path
# --------------------------------------------------------------------------- #
def _huge_coord_table(n=40):
    """Value bounds beyond 2**31: an int32 pack would silently wrap."""
    big = np.int64(2) ** 33
    r = np.random.default_rng(0)
    key_lo = r.integers(0, 50, (n, 1))
    key_hi = key_lo + r.integers(0, 3, (n, 1))
    val_lo = key_lo * (big // 50)
    val_hi = val_lo + 5
    return CompressedTable(
        key_shape=(100,),
        val_shape=(int(big * 2),),
        key_lo=key_lo,
        key_hi=key_hi,
        val_lo=val_lo,
        val_hi=val_hi,
        val_ref=np.full((n, 1), -1),
    )


def test_int64_coordinates_join_correctly_via_numpy_dense():
    t = _huge_coord_table()
    q = QueryBox((100,), np.array([[0]]), np.array([[60]]))
    res = theta_join(q, t, merge=False, path="dense")
    # oracle: every overlapping key row contributes its value interval
    hits = (t.key_lo[:, 0] <= 60) & (t.key_hi[:, 0] >= 0)
    assert res.n_rows == int(hits.sum())
    assert res.lo.min() >= 0 and res.hi.max() >= 2**31  # no wraparound
    # inverse direction probes the huge value bounds
    qv = QueryBox(t.val_shape, t.val_lo[:1], t.val_hi[:1])
    res_inv = theta_join_inverse_batch([qv], t, merge=False, path="dense")[0]
    assert res_inv.n_rows >= 1


def _claim_device_without_kernel(monkeypatch):
    """Make the engine believe an accelerator is attached, and fail any
    launch of the segmented kernel: a join must reach the twin."""
    from repro.kernels import ops

    def no_kernel(*a, **kw):
        raise AssertionError("kernel launched for an unkernelable segment")

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(ops, "segmented_range_join_pairs", no_kernel)


def test_kernel_path_refuses_int64_and_twin_handles_it(monkeypatch):
    from repro.kernels import ops

    big = np.full((4, 2), 2**31 + 7, np.int64)
    # the packer raises loudly instead of wrapping
    with pytest.raises(ValueError, match="int32"):
        ops.range_join_pairs(big, big, big, big)
    # with a device claimed, the functional join's int64 hop is planned and
    # answered by the twin, never packed for the kernel
    _claim_device_without_kernel(monkeypatch)
    assert dense_backend(1, int32_ok=False) == "np:i64"
    t = _huge_coord_table()
    qv = QueryBox(t.val_shape, t.val_lo[:3] - 1, t.val_hi[:3] + 1)
    got = theta_join_inverse_batch([qv], t, merge=False, path="dense")[0]
    _assert_equals_brute(got, _brute_join(qv, t, inverse=True), merge=False)
    assert got.n_rows >= 3


def test_executor_skips_kernel_pack_for_overflowing_segment():
    """With a forced non-interpret executor, int64 segments take the twin."""
    t_small = _random_table(80, seed=1)
    t_big = _huge_coord_table()
    qv = QueryBox(t_big.val_shape, t_big.val_lo[:2] - 1, t_big.val_hi[:2] + 1)
    reqs = [
        JoinRequest([_boxes(t_small.key_shape, 10)], t_small, path="dense"),
        JoinRequest([qv], t_big, inverse=True, path="dense"),
    ]
    want = [
        _brute_join(reqs[0].queries[0], t_small, inverse=False),
        _brute_join(qv, t_big, inverse=True),
    ]
    # interpret=True: everything through the twin (the kernel-eligibility
    # partition itself is covered by the unkernelable-segment test below)
    got = BatchedJoinExecutor(interpret=True).run(reqs)
    for (g,), w in zip(got, want):
        _assert_equals_brute(g, w, merge=True)


# --------------------------------------------------------------------------- #
# Bugfix: lane capacity is an explicit limit, visible in plan.describe()
# --------------------------------------------------------------------------- #
def test_high_dimensional_table_joins_via_numpy(monkeypatch):
    """65 key attributes: 2*65 > 128 lanes — kernel refuses, numpy serves."""
    from repro.kernels import ops

    l = 65
    n = 30
    r = np.random.default_rng(3)
    key_lo = r.integers(0, 4, (n, l))
    t = CompressedTable(
        key_shape=(8,) * l,
        val_shape=(8,),
        key_lo=key_lo,
        key_hi=key_lo + 1,
        val_lo=r.integers(0, 4, (n, 1)),
        val_hi=r.integers(4, 8, (n, 1)),
        val_ref=np.full((n, 1), -1),
    )
    q = QueryBox((8,) * l, np.zeros((2, l)), np.full((2, l), 7))
    res = theta_join(q, t, merge=False, path="dense")
    assert res.n_rows == 2 * n  # full overlap: every (row, box) pair
    with pytest.raises(ValueError, match="lane capacity"):
        ops.range_join_pairs(key_lo, key_lo + 1, key_lo, key_lo + 1)
    # even with a device claimed, the dense route must fall back, not raise
    _claim_device_without_kernel(monkeypatch)
    assert dense_backend(l) == "np:wide"
    got = theta_join(q, t, merge=False, path="dense")
    _assert_equals_brute(got, _brute_join(q, t, inverse=False), merge=False)


def _wide_request():
    """65 key attributes: past the kernel's 128-lane pack."""
    l, n = 65, 30
    r = np.random.default_rng(4)
    key_lo = r.integers(0, 4, (n, l))
    t = CompressedTable(
        key_shape=(8,) * l,
        val_shape=(8,),
        key_lo=key_lo,
        key_hi=key_lo + 1,
        val_lo=r.integers(0, 4, (n, 1)),
        val_hi=r.integers(4, 8, (n, 1)),
        val_ref=np.full((n, 1), -1),
    )
    lo, hi = np.zeros((2, l), np.int64), np.full((2, l), 7)
    hi[1, 0] = 1  # the second box meets only rows keyed low on axis 0
    return JoinRequest([QueryBox(t.key_shape, lo, hi)], t, merge=False,
                       path="dense")


def _int64_request():
    t = _huge_coord_table()
    qv = QueryBox(t.val_shape, t.val_lo[:4] - 1, t.val_hi[:4] + 1)
    return JoinRequest([qv], t, inverse=True, merge=False, path="dense")


@pytest.mark.parametrize("make", [_wide_request, _int64_request],
                         ids=["wide", "int64"])
def test_executor_gives_unkernelable_segments_to_twin(make, monkeypatch):
    """With a device claimed, a segment the kernel cannot express (lane
    capacity, int32 overflow) is answered by the twin, never launched."""
    _claim_device_without_kernel(monkeypatch)
    req = make()
    counts: dict = {}

    def bump(key, n=1):
        counts[key] = counts.get(key, 0) + n

    (got,) = BatchedJoinExecutor(stats=bump).run([req])[0]
    assert counts.get("twin_launches") == 1
    want = _brute_join(req.queries[0], req.table, req.inverse)
    assert want.n_rows > 0
    _assert_equals_brute(got, want, merge=False)


def test_describe_shows_route_backend_notes():
    log = DSLog(store_forward=True)
    log.define_array("a", SHAPE)
    log.define_array("b", SHAPE)
    from repro.core.capture import identity_lineage

    log.add_lineage("a", "b", identity_lineage(SHAPE))
    plan = log.planner.plan("a", ["b"])
    text = plan.describe()
    assert "batched(" in text  # routing decision + backend note are visible
    assert "np:" in text  # this container has no TPU
    # every dense hop is planned for the packed engine
    assert "/dense" not in text


def test_sharded_describe_shows_notes():
    sharded = ShardedDSLog(n_shards=2)
    names = _build_random_dag([sharded], 5, seed=2)
    text = sharded.planner.plan(names[0], [names[-1]]).describe()
    assert "(" in text and "np:" in text


# --------------------------------------------------------------------------- #
# ISSUE 8: autotuned launch geometry — persistence, invalidation, notes
# --------------------------------------------------------------------------- #
def test_autotune_table_persists_across_save_load(tmp_path):
    """A tuned (backend, bucket) winner survives the catalog round-trip via
    the ``autotune.json`` sidecar, on both the single store and the sharded
    facade."""
    d1, d2 = str(tmp_path / "single"), str(tmp_path / "sharded")
    log = DSLog(root=d1)
    log.define_array("a", SHAPE)
    geom, _ = log.autotune.pick(
        "interpret", "k3q5r5w2",
        runner=lambda g: g, candidates=((128, 128), (256, 256)), warmup=False,
    )
    assert log.autotune.dirty
    log.save()
    assert not log.autotune.dirty
    log2 = DSLog.load(d1)
    assert log2.autotune.lookup("interpret", "k3q5r5w2") == geom

    sharded = ShardedDSLog(n_shards=2, root=d2)
    record: list = []
    names = _build_random_dag([sharded], 4, seed=1, record=record)
    # a twin ("np") entry as older stores persisted it still loads
    sharded.autotune.pick(
        "np", "k2q4r4w2",
        runner=lambda g: g, candidates=((1 << 20,), (1 << 22,)), warmup=False,
    )
    sharded.save()
    re = ShardedDSLog.load(d2)
    assert re.autotune.lookup("np", "k2q4r4w2") is not None
    # queries answer the oracle on the reopened store
    cells = np.array([[1, 1], [2, 3]])
    _assert_oracle_answer(
        re.prov_query(names[0], names[-1], cells),
        _oracle_cells(record, names[0], names[-1], cells),
    )


def test_autotune_cache_invalidated_by_backend_change():
    """Entries are backend-keyed: a table tuned under one backend never
    answers another (the store-moved-machines case), and a manifest whose
    entries disagree with their keys loads cold."""
    from repro.kernels.autotune import GeometryTuner

    t = GeometryTuner()
    t.pick("interpret", "k1q2r2w1",
           runner=lambda g: g, candidates=((64, 128),), warmup=False)
    assert t.lookup("interpret", "k1q2r2w1") == (64, 128)
    assert t.lookup("tpu", "k1q2r2w1") is None  # backend changed -> re-tune
    manifest = t.to_manifest()
    # simulate a table written on another backend: key says tpu, rec says
    # interpret — the loader must drop it rather than mislead a lookup
    manifest["entries"] = {
        "tpu|k1q2r2w1": dict(manifest["entries"]["interpret|k1q2r2w1"])
    }
    t2 = GeometryTuner()
    t2.load_manifest(manifest)
    assert t2.lookup("tpu", "k1q2r2w1") is None
    assert len(t2) == 0
    t2.load_manifest({"version": 1, "entries": "garbage"})  # torn -> cold
    assert len(t2) == 0


def test_describe_note_renders_launch_geometry():
    """ISSUE 8 satellite: the hop note names the engine's launch geometry —
    ``batched(np:cpu:4m)`` on this box (twin, its fixed 4M-cell mask
    blocks); the kernel's label follows its last launch."""
    log = DSLog(store_forward=True)
    log.define_array("a", SHAPE)
    log.define_array("b", SHAPE)
    from repro.core.capture import identity_lineage

    log.add_lineage("a", "b", identity_lineage(SHAPE))
    text = log.planner.plan("a", ["b"]).describe()
    assert "batched(np:cpu:4m)" in text
    log.prov_query("a", "b", np.array([[1, 1]]))
    assert "batched(np:cpu:4m)" in log.planner.plan("a", ["b"]).describe()
    ex = log.planner.executor
    assert ex.geometry_label("tpu") == "256x256"  # default before a launch
    ex._last_geometry = (64, 128)
    assert ex.geometry_label("tpu") == "64x128"


def test_planner_discount_tracks_measured_occupancy():
    """The batched-route discount widens back toward 1 as the executor
    observes tile waste — cold executors keep the flat prior."""
    from repro.core.planner import _BATCHED_PAIR_DISCOUNT

    log = DSLog()
    log.define_array("a", SHAPE)
    p = log.planner
    assert p._batched_discount() == pytest.approx(_BATCHED_PAIR_DISCOUNT)
    p.executor._observe_occupancy(tile_cells=100_000, useful_cells=100)
    assert p.executor.measured_waste > 1.0
    assert p._batched_discount() > _BATCHED_PAIR_DISCOUNT
    assert p._batched_discount() <= 1.0
