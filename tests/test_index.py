"""Interval-index query path: indexed == dense, batch == loop, invalidation,
and the lattice path == the sorted-window path."""

import copy
import itertools
import json
import os
import tempfile

import numpy as np
import pytest

from repro.core import capture as C
from repro.core.catalog import DSLog
from repro.core.index import IntervalIndex, Lattice, ragged_ranges
from repro.core.provrc import compress, compress_both
from repro.core.query import (
    QueryBox,
    theta_join,
    theta_join_batch,
    theta_join_inverse,
)
from repro.core.relation import LineageRelation


def _random_relation(rng, l, m, n):
    oshape = tuple(int(rng.integers(2, 7)) for _ in range(l))
    ishape = tuple(int(rng.integers(2, 7)) for _ in range(m))
    o = np.stack([rng.integers(0, s, n) for s in oshape], axis=1)
    i = np.stack([rng.integers(0, s, n) for s in ishape], axis=1)
    return LineageRelation(oshape, ishape, o, i).canonical()


# --------------------------------------------------------------------------- #
# IntervalIndex primitives
# --------------------------------------------------------------------------- #
def test_ragged_ranges():
    owner, pos = ragged_ranges(np.array([2, 5, 5, 0]), np.array([4, 5, 8, 1]))
    np.testing.assert_array_equal(owner, [0, 0, 2, 2, 2, 3])
    np.testing.assert_array_equal(pos, [2, 3, 5, 6, 7, 0])


def test_candidate_pairs_match_dense_oracle():
    rng = np.random.default_rng(7)
    for _ in range(40):
        nq = int(rng.integers(1, 25))
        nr = int(rng.integers(1, 300))
        l = int(rng.integers(1, 4))
        r_lo = rng.integers(0, 80, (nr, l)).astype(np.int64)
        r_hi = r_lo + rng.integers(0, 12, (nr, l))
        q_lo = rng.integers(0, 80, (nq, l)).astype(np.int64)
        q_hi = q_lo + rng.integers(0, 20, (nq, l))
        idx = IntervalIndex(r_lo, r_hi)
        qi, ri = idx.candidate_pairs(q_lo, q_hi)
        ov = np.ones((nq, nr), bool)
        for j in range(l):
            ov &= (q_lo[:, j : j + 1] <= r_hi[None, :, j]) & (
                r_lo[None, :, j] <= q_hi[:, j : j + 1]
            )
        wq, wr = np.nonzero(ov)
        np.testing.assert_array_equal(qi, wq)
        np.testing.assert_array_equal(ri, wr)
        assert idx.estimate_candidates(q_lo, q_hi) >= qi.size


def test_index_serialization_roundtrip():
    rng = np.random.default_rng(11)
    lo = rng.integers(0, 50, (200, 2)).astype(np.int64)
    hi = lo + rng.integers(0, 5, (200, 2))
    idx = IntervalIndex(lo, hi)
    idx2 = IntervalIndex.from_bytes(idx.to_bytes(), lo, hi)
    q_lo = rng.integers(0, 50, (7, 2)).astype(np.int64)
    q_hi = q_lo + 3
    for a, b in zip(idx.candidate_pairs(q_lo, q_hi), idx2.candidate_pairs(q_lo, q_hi)):
        np.testing.assert_array_equal(a, b)


def test_index_rejects_mismatched_table():
    lo = np.zeros((4, 1), np.int64)
    hi = np.ones((4, 1), np.int64)
    blob = IntervalIndex(lo, hi).to_bytes()
    with pytest.raises(ValueError):
        IntervalIndex.from_bytes(blob, np.zeros((5, 1), np.int64), np.ones((5, 1), np.int64))


def test_index_rejects_stale_or_corrupt_permutation():
    rng = np.random.default_rng(21)
    lo = np.sort(rng.integers(0, 1000, (64, 1)).astype(np.int64), axis=0)
    hi = lo + 2
    blob = IntervalIndex(lo, hi).to_bytes()
    # stale: same shape, different (reversed) bounds -> order no longer sorts
    with pytest.raises(ValueError):
        IntervalIndex.from_bytes(blob, lo[::-1].copy(), hi[::-1].copy())
    # corrupt: garbage order values must raise ValueError, not IndexError
    with pytest.raises(ValueError):
        IntervalIndex(lo, hi, order=np.full((1, 64), 9999))
    with pytest.raises(ValueError):
        IntervalIndex(lo, hi, order=np.zeros((1, 64), np.int64))  # not a perm


# --------------------------------------------------------------------------- #
# Full-lattice tables: grid arithmetic == sorted windows
# --------------------------------------------------------------------------- #
def _lattice_rows(counts, stride, width, origin, shuffle_seed=None):
    """One row per grid point, ``[origin + stride·k, … + width]`` per attr,
    in row-major order or shuffled."""
    ks = np.array(list(itertools.product(*[range(c) for c in counts])), np.int64)
    lo = np.asarray(origin, np.int64) + np.asarray(stride, np.int64) * ks
    if shuffle_seed is not None:
        lo = lo[np.random.default_rng(shuffle_seed).permutation(len(lo))]
    return lo, lo + np.asarray(width, np.int64)


def _probe_boxes(lo, hi, seed):
    """Query boxes inside the grid, straddling its edge, fully outside it,
    and inverted (``q_hi < q_lo`` on an attribute)."""
    rng = np.random.default_rng(seed)
    n_attrs = lo.shape[1]
    g_lo, g_hi = lo.min(axis=0), hi.max(axis=0)
    inside = rng.integers(g_lo, g_hi + 1, (12, n_attrs))
    edge = np.repeat(np.stack([g_lo - 2, g_hi - 1]), 3, axis=0)
    outside = np.stack([g_hi + 3, g_lo - 9])
    q_lo = np.concatenate([inside, edge, outside]).astype(np.int64)
    q_hi = q_lo + rng.integers(0, 5, q_lo.shape)
    q_hi[-1] = g_lo - 4  # the last box ends before the grid starts
    q_hi[0] = q_lo[0] - 1  # inverted: the conjunctive test decides, as ever
    return q_lo, q_hi


def _window_twin(idx):
    """The same index with its lattice dropped: the sorted-window path."""
    twin = copy.copy(idx)
    twin.lattice = None
    return twin


def _assert_same_pairs(idx, q_lo, q_hi):
    got = idx.candidate_pairs(q_lo, q_hi)
    want = _window_twin(idx).candidate_pairs(q_lo, q_hi)
    np.testing.assert_array_equal(got[0], want[0])  # same pairs, same order
    np.testing.assert_array_equal(got[1], want[1])
    return got


@pytest.mark.parametrize("shuffled", [False, True], ids=["row_major", "shuffled"])
@pytest.mark.parametrize(
    "counts,stride,width,origin",
    [
        ((37,), (1,), (0,), (0,)),
        ((29,), (3,), (2,), (-4,)),
        ((13, 11), (2, 2), (1, 1), (0, 0)),
        ((9, 14), (1, 3), (0, 2), (5, -1)),
        ((16, 16), (2, 1), (1, 0), (0, 3)),
        ((5, 4, 6), (1, 2, 3), (0, 1, 2), (0, 0, 0)),
        ((4, 7, 3), (3, 1, 2), (2, 0, 1), (-2, 1, 7)),
        ((1, 23), (1, 2), (0, 1), (4, 0)),
    ],
)
def test_lattice_pairs_equal_sorted_window_pairs(counts, stride, width, origin, shuffled):
    lo, hi = _lattice_rows(counts, stride, width, origin, 5 if shuffled else None)
    idx = IntervalIndex(lo, hi)
    lat = idx.lattice
    assert lat is not None
    assert (lat.rows is None) == (not shuffled)
    np.testing.assert_array_equal(lat.counts, counts)
    np.testing.assert_array_equal(lat.width, width)
    np.testing.assert_array_equal(lat.origin, origin)
    q_lo, q_hi = _probe_boxes(lo, hi, seed=len(lo))
    qi, ri = _assert_same_pairs(idx, q_lo, q_hi)
    assert qi.size > 0
    assert not (qi == q_lo.shape[0] - 1).any()  # the box outside meets nothing
    empty = np.zeros((0, lo.shape[1]), np.int64)
    got = idx.candidate_pairs(empty, empty)
    assert got[0].size == got[1].size == 0


def _break(kind):
    """Tables that are not full lattices, each next to a lattice it nearly is."""
    lo, hi = _lattice_rows((6, 5), (2, 1), (1, 0), (0, 0))
    if kind == "missing_cell":
        return lo[1:], hi[1:]
    if kind == "duplicated_row":
        return np.concatenate([lo[:-1], lo[:1]]), np.concatenate([hi[:-1], hi[:1]])
    if kind == "uneven_stride":
        lo = lo.copy()
        lo[lo[:, 0] == 10, 0] = 11
        return lo, lo + (1, 0)
    if kind == "mixed_widths":
        hi = hi.copy()
        hi[7, 1] += 1
        return lo, hi
    if kind == "one_row":
        return lo[:1], hi[:1]
    raise AssertionError(kind)


@pytest.mark.parametrize(
    "kind", ["missing_cell", "duplicated_row", "uneven_stride", "mixed_widths", "one_row"]
)
def test_near_lattices_keep_the_sorted_window_path(kind):
    lo, hi = _break(kind)
    idx = IntervalIndex(lo, hi)
    assert idx.lattice is None
    assert Lattice.detect(lo, hi, idx.sorted_lo) is None
    q_lo, q_hi = _probe_boxes(lo, hi, seed=3)
    qi, ri = idx.candidate_pairs(q_lo, q_hi)
    ov = np.ones((len(q_lo), len(lo)), bool)
    for j in range(lo.shape[1]):
        ov &= (q_lo[:, j : j + 1] <= hi[None, :, j]) & (lo[None, :, j] <= q_hi[:, j : j + 1])
    wq, wr = np.nonzero(ov)
    np.testing.assert_array_equal(qi, wq)
    np.testing.assert_array_equal(ri, wr)


@pytest.mark.parametrize("shuffled", [False, True], ids=["row_major", "shuffled"])
def test_lattice_rederived_on_attach_and_sidecar_unchanged(shuffled):
    lo, hi = _lattice_rows((12, 9), (2, 2), (1, 1), (0, 0), 8 if shuffled else None)
    idx = IntervalIndex(lo, hi)
    blob = idx.to_bytes()
    # the sidecar holds the header and the int32 sort permutations alone
    header = json.dumps({"n_rows": len(lo), "n_attrs": 2, "dtype": "<i4"}).encode()
    order = np.stack([np.argsort(lo[:, j], kind="stable") for j in range(2)])
    assert blob == (
        b"PRVCIDX1\n" + len(header).to_bytes(4, "little") + header
        + order.astype(np.int32).tobytes()
    )
    again = IntervalIndex.from_bytes(blob, lo, hi)
    assert again.to_bytes() == blob
    for name in ("origin", "stride", "width", "counts"):
        np.testing.assert_array_equal(getattr(again.lattice, name), getattr(idx.lattice, name))
    if shuffled:
        np.testing.assert_array_equal(again.lattice.rows, idx.lattice.rows)
    else:
        assert again.lattice.rows is None
    q_lo, q_hi = _probe_boxes(lo, hi, seed=9)
    for a, b in zip(again.candidate_pairs(q_lo, q_hi), _assert_same_pairs(idx, q_lo, q_hi)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------- #
# Indexed vs dense θ-join equivalence
# --------------------------------------------------------------------------- #
def test_indexed_theta_join_equals_dense_random_relations():
    rng = np.random.default_rng(0)
    for trial in range(25):
        l, m = int(rng.integers(1, 3)), int(rng.integers(1, 3))
        rel = _random_relation(rng, l, m, int(rng.integers(1, 80)))
        bwd, fwd = compress_both(rel)
        qo = np.unique(
            np.stack([rng.integers(0, s, 4) for s in rel.out_shape], axis=1), axis=0
        )
        qi = np.unique(
            np.stack([rng.integers(0, s, 4) for s in rel.in_shape], axis=1), axis=0
        )
        q_out = QueryBox.from_cells(rel.out_shape, qo)
        q_in = QueryBox.from_cells(rel.in_shape, qi)
        for fn, q, t in [
            (theta_join, q_out, bwd),
            (theta_join, q_in, fwd),
            (theta_join_inverse, q_in, bwd),
            (theta_join_inverse, q_out, fwd),
        ]:
            indexed = fn(q, t, path="index")
            dense = fn(q, t, path="dense")
            assert indexed.cell_set() == dense.cell_set(), (trial, fn.__name__)
            # merged outputs are canonical: cell-for-cell AND box-for-box
            both_i = np.concatenate([indexed.lo, indexed.hi], axis=1)
            both_d = np.concatenate([dense.lo, dense.hi], axis=1)
            np.testing.assert_array_equal(
                np.unique(both_i, axis=0), np.unique(both_d, axis=0)
            )


def test_auto_path_equals_dense_on_large_table():
    rng = np.random.default_rng(3)
    n = 3000
    o = np.stack([rng.integers(0, 200, n), rng.integers(0, 200, n)], axis=1)
    i = np.stack([rng.integers(0, 300, n)], axis=1)
    rel = LineageRelation((200, 200), (300,), o, i).canonical()
    t = compress(rel)
    assert t.n_rows >= 1024, "table must be large enough to engage the index"
    q = QueryBox.from_range((200, 200), (5, 5), (8, 8))
    assert theta_join(q, t).cell_set() == theta_join(q, t, path="dense").cell_set()


def test_unknown_path_raises():
    t = compress(C.identity_lineage((5,)))
    with pytest.raises(ValueError):
        theta_join(QueryBox.from_cells((5,), np.array([[0]])), t, path="turbo")


def test_symbolic_table_rejected_by_all_joins():
    t = compress(C.identity_lineage((5,)))
    t.key_sym = np.zeros((t.n_rows, 1), np.int8)  # mark axis-0 symbolic
    q_key = QueryBox.from_cells((5,), np.array([[0]]))
    q_val = QueryBox.from_cells((5,), np.array([[0]]))
    with pytest.raises(ValueError, match="symbolic"):
        theta_join(q_key, t)
    with pytest.raises(ValueError, match="symbolic"):
        theta_join_inverse(q_val, t)
    with pytest.raises(ValueError, match="symbolic"):
        theta_join_batch([q_key], t)


# --------------------------------------------------------------------------- #
# Batched API
# --------------------------------------------------------------------------- #
def test_batch_equals_loop_of_singles():
    rng = np.random.default_rng(5)
    rel = _random_relation(rng, 2, 2, 60)
    t = compress(rel)
    queries = []
    for _ in range(6):
        cells = np.stack(
            [rng.integers(0, s, 3) for s in rel.out_shape], axis=1
        )
        queries.append(QueryBox.from_cells(rel.out_shape, cells))
    queries.append(queries[0])  # duplicate query: exercises probe dedup
    queries.append(QueryBox(rel.out_shape, np.zeros((0, 2)), np.zeros((0, 2))))
    for path in ("index", "dense", "auto"):
        batch = theta_join_batch(queries, t, path=path)
        assert len(batch) == len(queries)
        for got, q in zip(batch, queries):
            want = theta_join(q, t)
            assert got.cell_set() == want.cell_set(), path


def test_batch_empty_inputs():
    t = compress(C.identity_lineage((5,)))
    assert theta_join_batch([], t) == []
    q = QueryBox((5,), np.zeros((0, 1)), np.zeros((0, 1)))
    assert theta_join_batch([q, q], t)[0].n_rows == 0


def test_batch_shape_mismatch_raises():
    t = compress(C.identity_lineage((5,)))
    with pytest.raises(ValueError):
        theta_join_batch([QueryBox.from_cells((4,), np.array([[0]]))], t)


# --------------------------------------------------------------------------- #
# Invalidation
# --------------------------------------------------------------------------- #
def test_index_invalidated_on_field_reassignment():
    rel = C.identity_lineage((10,))
    t = compress(rel)
    q = QueryBox.from_cells((10,), np.array([[3]]))
    assert theta_join(q, t, path="index").cell_set() == {(3,)}
    # shift every key interval by one: cell 3 now maps to value 2's row
    t.key_lo = t.key_lo + 1
    t.key_hi = t.key_hi + 1
    assert theta_join(q, t, path="index").cell_set() == \
        theta_join(q, t, path="dense").cell_set()


def test_index_invalidated_after_inplace_mutation():
    rel = C.identity_lineage((10,))
    t = compress(rel)
    q = QueryBox.from_cells((10,), np.array([[3]]))
    stale = theta_join(q, t, path="index").cell_set()
    assert stale == {(3,)}
    t.key_lo += 1
    t.key_hi += 1
    t.invalidate_index()  # in-place writes need the explicit call
    assert theta_join(q, t, path="index").cell_set() == \
        theta_join(q, t, path="dense").cell_set()


def test_select_returns_fresh_cache():
    rng = np.random.default_rng(9)
    rel = _random_relation(rng, 2, 1, 50)
    t = compress(rel)
    assert t.n_rows >= 2
    t.key_index()
    sub = t.select(np.array([0, 1]))
    assert sub.cached_key_index() is None
    assert sub.key_index().n_rows == sub.n_rows


# --------------------------------------------------------------------------- #
# Catalog persistence + batch queries
# --------------------------------------------------------------------------- #
def test_catalog_persists_and_reloads_index():
    with tempfile.TemporaryDirectory() as d:
        log = DSLog(root=d, store_forward=True)
        relXY = C.identity_lineage((6, 3))
        relYZ = C.reduce_lineage((6, 3), 1)
        log.add_lineage("X", "Y", relXY)
        log.add_lineage("Y", "Z", relYZ)
        for e in log.lineage.values():
            e.backward.key_index()  # build → save() must persist it
        log.save()
        assert any(f.endswith(".idx") for f in os.listdir(d))
        log2 = DSLog.load(d)
        e0 = log2.lineage[0]
        assert e0.backward.cached_key_index() is not None
        res = log2.prov_query(["Z", "Y", "X"], np.array([[4]]))
        assert res.cell_set() == {(4, j) for j in range(3)}


def test_prov_query_batch_matches_singles():
    log = DSLog(store_forward=True)
    relXY = C.identity_lineage((6, 3))
    relYZ = C.reduce_lineage((6, 3), 1)
    log.add_lineage("X", "Y", relXY)
    log.add_lineage("Y", "Z", relYZ)
    queries = [np.array([[4]]), np.array([[0]]), np.array([[4]])]
    batch = log.prov_query_batch(["Z", "Y", "X"], queries)
    for got, cells in zip(batch, queries):
        want = log.prov_query(["Z", "Y", "X"], cells)
        assert got.cell_set() == want.cell_set()
    assert log.prov_query_batch(["Z", "Y", "X"], []) == []
