"""Sharded store: N=1 equivalence, cross-shard correctness, persistence.

The contract under test (ISSUE 3): ``ShardedDSLog`` with ``N=1`` is the
single store — byte-identical query results — and for ``N > 1`` every
``prov_query`` form returns the single-store answer while entries live on
different shards, frontiers cross boundaries as merged boxes, and each
shard saves independently.
"""

import os
import tempfile

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from test_query import oracle_backward, oracle_forward

from repro.core.capture import (
    flip_lineage,
    identity_lineage,
    reduce_lineage,
    roll_lineage,
    transpose_lineage,
)
from repro.core.catalog import DSLog
from repro.core.graph import CycleError
from repro.core.query import QueryBox, canonical_boxes
from repro.core.shard import (
    AffinityShardPolicy,
    HashShardPolicy,
    ShardedDSLog,
    ShardedQueryPlan,
)

SIDE = 8
SHAPE = (SIDE, SIDE)


@pytest.fixture(autouse=True)
def _race_detect(race_detector):
    """Whole module runs under the dynamic lock-order / race detector."""
    yield

# shape-preserving single-input ops for the random-DAG property test
_OPS = [
    lambda rng: identity_lineage(SHAPE),
    lambda rng: flip_lineage(SHAPE, int(rng.integers(0, 2))),
    lambda rng: roll_lineage(SHAPE, int(rng.integers(1, 4)), 0),
    lambda rng: transpose_lineage(SHAPE, (1, 0)),
]


def _build_random_dag(logs, n_ops: int, seed: int, record=None):
    """Drive identical op streams into several stores.

    A chain backbone (a0 → a1 → …) guarantees a route end to end; every
    third op is a two-input fan-in whose second parent is a random earlier
    array — under hashing those parents regularly land on distinct shards.
    ``record``, when given, receives every op's raw relations in ingest
    order as ``(input array, output array, LineageRelation)`` — what
    :func:`_oracle_cells` composes.
    """
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
                rng.bit_generator.state = state  # same draws per store
                rel_a = _OPS[int(rng.integers(0, len(_OPS)))](rng)
                rel_b = _OPS[int(rng.integers(0, len(_OPS)))](rng)
                log.define_array(new, SHAPE)
                log.register_operation(
                    f"op{k}", [prev, other], [new],
                    capture=lambda ra=rel_a, rb=rel_b: {(0, 0): ra, (0, 1): rb},
                    reuse=False,
                )
            if record is not None:
                record += [(prev, new, rel_a), (other, new, rel_b)]
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
            if record is not None:
                record.append((prev, new, rel))
        names.append(new)
    return names


def _oracle_cells(record, src, dst, cells) -> set:
    """The uncompressed answer of ``prov_query(src, dst, cells)``: the cell
    sets of :func:`_build_random_dag`'s recorded relations composed along
    the DAG — forward when ``dst`` is downstream of ``src``, else backward —
    with a union at every fan-in.  Ingest order is a topological order."""
    start = {tuple(int(v) for v in c) for c in cells}
    front = {src: start}
    for a, b, rel in record:
        if a in front:
            front[b] = front.get(b, set()) | oracle_forward(rel, front[a])
    if dst in front:
        return front[dst]
    front = {src: start}
    for a, b, rel in reversed(record):
        if b in front:
            front[a] = front.get(a, set()) | oracle_backward(rel, front[b])
    return front[dst]


def _oracle_path_cells(record, path, cells) -> set:
    """The uncompressed answer of ``prov_query(path, cells)``: each hop
    unions every recorded relation between its two arrays, in whichever
    dataflow direction it was registered."""
    cur = {tuple(int(v) for v in c) for c in cells}
    for a, b in zip(path[:-1], path[1:]):
        nxt = set()
        for x, y, rel in record:
            if (x, y) == (a, b):
                nxt |= oracle_forward(rel, cur)
            elif (x, y) == (b, a):
                nxt |= oracle_backward(rel, cur)
        cur = nxt
    return cur


def _assert_oracle_answer(got: QueryBox, want: set, merge: bool = True) -> None:
    """``got`` covers exactly the cells ``want``; a merged answer is also
    byte-identical to the canonical decomposition of those cells."""
    assert got.cell_set() == want
    if merge:
        cells = np.array(sorted(want), np.int64).reshape(-1, len(got.shape))
        exp = canonical_boxes(QueryBox.from_cells(got.shape, cells))
        assert got.lo.tobytes() == exp.lo.tobytes()
        assert got.hi.tobytes() == exp.hi.tobytes()


def _diamond(log, pins=None):
    """x fans out to a and b, which fan back into z (explicit affinity)."""
    log.define_array("x", SHAPE)
    log.define_array("a", SHAPE)
    log.define_array("b", SHAPE)
    log.define_array("z", SHAPE)
    log.register_operation(
        "split", ["x"], ["a", "b"],
        capture=lambda: {
            (0, 0): flip_lineage(SHAPE, 0),
            (1, 0): roll_lineage(SHAPE, 2, 1),
        },
        reuse=False,
    )
    log.register_operation(
        "combine", ["a", "b"], ["z"],
        capture=lambda: {
            (0, 0): identity_lineage(SHAPE),
            (0, 1): identity_lineage(SHAPE),
        },
        reuse=False,
    )
    return log


# --------------------------------------------------------------------------- #
# N=1: the single-store special case
# --------------------------------------------------------------------------- #
def test_n1_query_results_byte_identical():
    single = _diamond(DSLog())
    sharded = _diamond(ShardedDSLog(n_shards=1))
    cells = np.array([[2, 3], [7, 0]])
    for src, dst, q in [
        ("x", "z", cells),
        ("z", "x", np.array([[4, 4]])),
        ("x", "a", cells),
    ]:
        a = single.prov_query(src, dst, q)
        b = sharded.prov_query(src, dst, q)
        assert a.shape == b.shape
        assert a.lo.tobytes() == b.lo.tobytes()
        assert a.hi.tobytes() == b.hi.tobytes()
    # path form too
    a = single.prov_query(["z", "a", "x"], np.array([[1, 1]]))
    b = sharded.prov_query(["z", "a", "x"], np.array([[1, 1]]))
    assert a.lo.tobytes() == b.lo.tobytes() and a.hi.tobytes() == b.hi.tobytes()
    # the sharded plan is the single-store plan: no exchanges, one shard
    plan = sharded.planner.plan("x", ["z"])
    assert isinstance(plan, ShardedQueryPlan)
    assert plan.exchanges == [] and plan.shards_touched() == [0]


def test_n1_manifest_layout_and_reload():
    with tempfile.TemporaryDirectory() as d:
        _diamond(ShardedDSLog(n_shards=1, root=d)).save()
        assert os.path.exists(os.path.join(d, "catalog.json"))
        assert os.path.exists(os.path.join(d, "shard_00", "catalog.json"))
        re = ShardedDSLog.load(d)
        got = re.prov_query("z", "x", np.array([[4, 4]]))
        want = _diamond(DSLog()).prov_query("z", "x", np.array([[4, 4]]))
        assert got.lo.tobytes() == want.lo.tobytes()
        with pytest.raises(ValueError):
            DSLog.load(d)  # sharded roots refuse the single-store loader
        with pytest.raises(ValueError):
            ShardedDSLog.load(os.path.join(d, "shard_00"))  # and vice versa


# --------------------------------------------------------------------------- #
# Cross-shard correctness vs the single-store oracle
# --------------------------------------------------------------------------- #
@settings(max_examples=10, deadline=None)
@given(
    n_ops=st.integers(4, 9),
    seed=st.integers(0, 10_000),
    n_shards=st.sampled_from([1, 2, 4]),
)
def test_sharded_query_equals_single_store(n_ops, seed, n_shards):
    oracle = DSLog()
    sharded = ShardedDSLog(n_shards=n_shards)
    names = _build_random_dag([oracle, sharded], n_ops, seed)
    rng = np.random.default_rng(seed + 1)
    cells = np.stack(
        [rng.integers(0, SIDE, 3), rng.integers(0, SIDE, 3)], axis=1
    )
    src, dst = names[0], names[-1]
    for s, t, q in [(src, dst, cells), (dst, src, cells[:1])]:
        for merge in (True, False):
            want = oracle.prov_query(s, t, q, merge=merge).cell_set()
            got = sharded.prov_query(s, t, q, merge=merge).cell_set()
            assert got == want
    # batch + multi-target forms
    want_b = oracle.prov_query_batch(src, dst, [cells, cells[:1]])
    got_b = sharded.prov_query_batch(src, dst, [cells, cells[:1]])
    assert [r.cell_set() for r in got_b] == [r.cell_set() for r in want_b]
    mids = names[1 : len(names) - 1 : 2]
    if mids:
        want_m = oracle.prov_query(src, mids + [dst], cells)
        got_m = sharded.prov_query(src, mids + [dst], cells)
        assert {k: v.cell_set() for k, v in got_m.items()} == {
            k: v.cell_set() for k, v in want_m.items()
        }


def test_fanin_parents_on_different_shards():
    """The acceptance case: a fan-in array whose parents live on different
    shards — results match the single store, frontiers cross as exchanges."""
    pol = AffinityShardPolicy(2, {"x": 0, "a": 0, "b": 1, "z": 1})
    sharded = _diamond(ShardedDSLog(n_shards=2, policy=pol))
    oracle = _diamond(DSLog())
    assert sharded.shard_of_array("a") != sharded.shard_of_array("b")
    assert len(sharded.sgraph.boundary) > 0
    cells = np.array([[2, 3], [5, 5]])
    fwd = sharded.planner.plan("x", ["z"], frontier=None)
    assert fwd.exchanges, "fan-in across shards must ship a frontier"
    for s, t, q in [("x", "z", cells), ("z", "x", np.array([[4, 4]]))]:
        assert (
            sharded.prov_query(s, t, q).cell_set()
            == oracle.prov_query(s, t, q).cell_set()
        )
    assert sharded.io_stats["boxes_exchanged"] > 0
    # per-shard sub-plans partition the steps of the stitched plan
    subs = fwd.sub_plans()
    n_steps = sum(len(sl) for sl in fwd.steps.values())
    assert sum(len(sl) for p in subs.values() for sl in p.steps.values()) == n_steps
    assert set(subs) == set(fwd.shards_touched())


def test_sharded_graph_partition_is_consistent():
    pol = AffinityShardPolicy(3, {"x": 0, "a": 1, "b": 2, "z": 0})
    log = _diamond(ShardedDSLog(n_shards=3, policy=pol))
    g = log.sgraph
    # per-shard edge counts sum to the global count
    assert sum(sg.n_edges() for sg in g.shard_graphs) == g.n_edges() == 4
    # boundary table lists exactly the cross-shard entries
    for lid, src, dst, s_sh, d_sh in g.boundary_edges():
        assert s_sh != d_sh
        assert log.owner_shard(lid) == d_sh
        entry = log.lineage[lid]
        assert (entry.src, entry.dst) == (src, dst)
    # every edge is in the dst-owner's shard graph
    for (src, dst), ids in log.by_pair.items():
        shard = log.shard_of_array(dst)
        assert set(g.shard_graph(shard).edge_ids(src, dst)) == set(ids)


def test_sharded_cycle_rejection_spans_shards():
    pol = AffinityShardPolicy(2, {"u": 0, "v": 1, "w": 0})
    log = ShardedDSLog(n_shards=2, policy=pol)
    log.add_lineage("u", "v", identity_lineage(SHAPE))
    log.add_lineage("v", "w", identity_lineage(SHAPE))
    with pytest.raises(CycleError):
        log.add_lineage("w", "u", identity_lineage(SHAPE))
    with pytest.raises(CycleError):
        log.add_lineage("u", "u", identity_lineage(SHAPE))
    # the rejected edges left nothing behind, queries still work
    assert len(log.lineage) == 2
    res = log.prov_query("w", "u", np.array([[3, 3]]))
    assert res.cell_set() == {(3, 3)}


# --------------------------------------------------------------------------- #
# Persistence: dirty shards only, lazy shard loading
# --------------------------------------------------------------------------- #
def test_incremental_save_writes_only_dirty_shards():
    with tempfile.TemporaryDirectory() as d:
        pol = AffinityShardPolicy(3, {"u": 0, "v": 0, "p": 1, "q": 1})
        log = ShardedDSLog(n_shards=3, root=d, policy=pol)
        log.add_lineage("u", "v", identity_lineage((6, 3)))
        log.add_lineage("p", "q", reduce_lineage((6, 3), 1))
        log.save()
        base = log.io_stats
        # shard 2 never hosted an entry: no directory, no manifest
        assert not os.path.exists(os.path.join(d, "shard_02", "catalog.json"))

        log.save()  # clean save: nothing at all is written
        assert log.io_stats["manifests_written"] == base["manifests_written"]
        assert log.io_stats["tables_written"] == base["tables_written"]

        mtime_s1 = os.path.getmtime(os.path.join(d, "shard_01", "catalog.json"))
        log.add_lineage("v", "w", identity_lineage((6, 3)), op_name="grow")
        new_lid = log.by_pair[("v", "w")][0]
        dirty_shard = log.owner_shard(new_lid)  # the new entry's owning shard
        log.save()
        after = log.io_stats
        # exactly the dirty shard's manifest + the root manifest rewrote
        assert after["manifests_written"] == base["manifests_written"] + 2
        assert after["tables_written"] == base["tables_written"] + 2
        if dirty_shard != 1:
            assert (
                os.path.getmtime(os.path.join(d, "shard_01", "catalog.json"))
                == mtime_s1
            )


def test_lazy_shard_loading_on_query():
    with tempfile.TemporaryDirectory() as d:
        pol = AffinityShardPolicy(2, {"u": 0, "v": 0, "p": 1, "q": 1})
        log = ShardedDSLog(n_shards=2, root=d, policy=pol)
        log.add_lineage("u", "v", identity_lineage((6, 3)))
        log.add_lineage("p", "q", reduce_lineage((6, 3), 1))
        log.save()

        re = ShardedDSLog.load(d)
        assert re.io_stats["shards_loaded"] == 0
        # the graph came from the root manifest — no shard I/O to route
        assert re.graph.has_path("u", "v") and not re.graph.has_path("u", "q")
        res = re.prov_query("v", "u", np.array([[4, 1]]))
        assert res.cell_set() == {(4, 1)}
        # only the plan-touched shard loaded, and only one blob inside it
        assert re.io_stats["shards_loaded"] == 1
        assert re.loaded_shards() == [0]
        assert re.io_stats["tables_loaded"] == 1


def test_sharded_round_trip_extends_incrementally():
    with tempfile.TemporaryDirectory() as d:
        log = ShardedDSLog(n_shards=4, root=d)
        names = _build_random_dag([log], 6, seed=3)
        log.save()
        re = ShardedDSLog.load(d)
        re.define_array("tail", SHAPE)
        re.add_lineage(names[-1], "tail", identity_lineage(SHAPE))
        re.save()
        re2 = ShardedDSLog.load(d)
        oracle = DSLog()
        _build_random_dag([oracle], 6, seed=3)
        oracle.add_lineage(names[-1], "tail", identity_lineage(SHAPE))
        cells = np.array([[1, 2], [6, 7]])
        assert (
            re2.prov_query(names[0], "tail", cells).cell_set()
            == oracle.prov_query(names[0], "tail", cells).cell_set()
        )


def test_sharded_version_and_compact():
    with tempfile.TemporaryDirectory() as d:
        log = ShardedDSLog(n_shards=2, root=d)
        log.define_array("acc", (5,))
        prev = log.latest_version("acc")
        for _ in range(3):
            cur = log.version("acc")
            log.add_lineage(prev, cur, identity_lineage((5,)))
            prev = cur
        assert prev == "acc@3"
        # version chains co-locate: no boundary edges, no exchanges
        assert log.sgraph.boundary == {}
        res = log.prov_query("acc@3", "acc", np.array([[2]]))
        assert res.cell_set() == {(2,)}
        log.save()
        dropped = log.by_pair[("acc@2", "acc@3")][0]
        owner = log.owner_shard(dropped)
        assert any(  # the query above recorded feedback for this hop
            k.startswith(f"{dropped}:") for k in log.shard(owner).hop_stats
        )
        log.drop_lineage(dropped)
        assert not any(
            k.startswith(f"{dropped}:") for k in log.shard(owner).hop_stats
        )
        stats = log.compact()
        assert stats["files_removed"] >= 2  # backward + forward blobs
        re = ShardedDSLog.load(d)
        assert re.latest_version("acc") == "acc@3"
        assert re.version("acc") == "acc@4"
        assert dropped not in re.lineage


# --------------------------------------------------------------------------- #
# Cost-model feedback on the sharded planner
# --------------------------------------------------------------------------- #
def test_hop_feedback_routes_to_owning_shard():
    with tempfile.TemporaryDirectory() as d:
        pol = AffinityShardPolicy(2, {"x": 0, "a": 0, "b": 1, "z": 1})
        log = _diamond(ShardedDSLog(n_shards=2, root=d, policy=pol))
        log.prov_query("z", "x", np.array([[4, 4]]))
        # measurements landed on the shard owning each entry
        measured = {
            lid: log.hop_measurement(lid, "backward", "key")
            for lid in log.lineage
        }
        assert any(v is not None for v in measured.values())
        for lid, val in measured.items():
            shard = log.shard(log.owner_shard(lid))
            if val is not None:
                assert shard.hop_measurement(lid, "backward", "key") == val
        log.save()
        re = ShardedDSLog.load(d)
        for lid, val in measured.items():
            if val is not None:
                assert re.hop_measurement(lid, "backward", "key") == val
