"""Canonical dedup of a captured relation, and its one use per ingest.

``LineageRelation.canonical()`` must give, byte for byte, the rows
``np.unique(rows, axis=0)`` gives, whichever route it takes (``presorted``,
``packed`` or ``lexsort``).  ``add_lineage`` dedups each relation once,
hands the result to both directions (the second span reads ``reused``),
and keeps nothing across calls.
"""

import numpy as np
import pytest

import repro.core.relation as relation_mod
from repro.core.catalog import DSLog
from repro.core.provrc import compress, compress_both
from repro.core.relation import LineageRelation

RNG = np.random.default_rng(14)


def _rows(shape, n):
    cols = [RNG.integers(0, d, n) for d in shape]
    return np.array(cols, np.int64).reshape(len(shape), n).T


def _sorted_unique(out_shape, in_shape, n):
    rows = np.unique(
        np.concatenate([_rows(out_shape, n), _rows(in_shape, n)], axis=1), axis=0
    )
    return rows[:, : len(out_shape)], rows[:, len(out_shape):]


def _case(out_shape, in_shape, n, route, dup=False, shuffle=False, poke=None):
    o, i = _rows(out_shape, n), _rows(in_shape, n)
    if dup:
        o, i = np.concatenate([o, o[::2]]), np.concatenate([i, i[::2]])
    if shuffle:
        perm = RNG.permutation(o.shape[0])
        o, i = o[perm], i[perm]
    if poke is not None:  # (side, column, value): one index outside [0, dim)
        side, col, value = poke
        (o if side == "out" else i)[1, col] = value
    return out_shape, in_shape, o, i, route


CASES = {
    "empty": ((4,), (4,), np.zeros((0, 1)), np.zeros((0, 1)), "presorted"),
    "one_row": ((4, 4), (9,), np.array([[3, 1]]), np.array([[7]]), "presorted"),
    "0d_out": _case((), (5,), 12, "packed", dup=True),
    "0d_in_one_row": ((3,), (), np.array([[2]]), np.zeros((1, 0)), "presorted"),
    "0d_both": ((), (), np.zeros((3, 0)), np.zeros((3, 0)), "packed"),
    "1d_dups": _case((6,), (6,), 30, "packed", dup=True),
    "2d_1d_unsorted": _case((5, 7), (9,), 40, "packed", shuffle=True),
    "3d_2d_dups": _case((2, 3, 4), (3, 5), 50, "packed", dup=True, shuffle=True),
    "1d_3d_presorted": (
        (8,), (2, 3, 4), *_sorted_unique((8,), (2, 3, 4), 60), "presorted"
    ),
    "2d_2d_presorted": (
        (9, 9), (9, 9), *_sorted_unique((9, 9), (9, 9), 80), "presorted"
    ),
    "reshape_2d_to_1d": (
        (64,), (8, 8), np.arange(64)[:, None],
        np.stack(np.unravel_index(np.arange(64), (8, 8)), axis=1), "presorted",
    ),
    "sorted_with_dups": (
        (4,), (4,), np.array([[0], [1], [1], [3]]), np.array([[2], [0], [0], [1]]),
        "packed",
    ),
    "shape_product_2pow63": _case((2**32,), (2**30, 2), 20, "lexsort", dup=True),
    "shape_product_2pow64": _case((2**32,), (2**31, 2), 20, "lexsort", shuffle=True),
    "shape_product_2pow62": _case((2**62,), (1,), 20, "packed", dup=True),
    "numpy_dims_2pow64": _case(
        (np.int64(2**32),), (np.int64(2**32),), 20, "lexsort", dup=True
    ),
    "negative_index": _case((6, 6), (6,), 25, "lexsort", dup=True, poke=("out", 1, -3)),
    "index_at_dim": _case((6,), (6, 6), 25, "lexsort", shuffle=True, poke=("in", 0, 6)),
    "index_far_out": _case((6,), (6,), 25, "lexsort", poke=("in", 0, 2**40)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_canonical_matches_np_unique_rows(name):
    out_shape, in_shape, o, i, route = CASES[name]
    rel = LineageRelation(out_shape, in_shape, o, i)
    want = np.unique(rel.rows(), axis=0)
    l = len(out_shape)

    canon, took = rel.canonical_route()
    assert took == route
    for got, ref in ((canon.out_idx, want[:, :l]), (canon.in_idx, want[:, l:])):
        assert got.dtype == np.int64 and got.shape == ref.shape
        assert got.tobytes() == ref.tobytes()
    assert canon.rows().tobytes() == want.tobytes()
    assert (canon.out_shape, canon.in_shape) == (out_shape, in_shape)
    assert (canon.out_attrs, canon.in_attrs) == (rel.out_attrs, rel.in_attrs)
    # canonical() is the same rows, and a fresh copy: the input is untouched
    assert rel.canonical().rows().tobytes() == want.tobytes()
    assert canon.out_idx is not rel.out_idx and canon.in_idx is not rel.in_idx
    assert rel == canon and canon.canonical_route()[1] in ("presorted", "lexsort")


def test_relation_equality_is_set_equality():
    rel = LineageRelation((5,), (5,), [[3], [1], [1], [0]], [[2], [4], [4], [0]])
    assert rel == LineageRelation((5,), (5,), [[0], [1], [3]], [[0], [4], [2]])
    assert rel != LineageRelation((5,), (5,), [[0], [1], [3]], [[0], [4], [1]])
    assert rel != LineageRelation((5,), (6,), [[0], [1], [3]], [[0], [4], [2]])
    huge = LineageRelation((2**40,), (2**40,), [[2**39], [1]], [[5], [2**39]])
    assert huge == LineageRelation((2**40,), (2**40,), [[1], [2**39]], [[2**39], [5]])


# --------------------------------------------------------------------------- #
# Ingest: one dedup per add_lineage, identical tables
# --------------------------------------------------------------------------- #
SIDE = 64
N = SIDE * SIDE


def _hop(kind: str) -> LineageRelation:
    """Hops shaped like the benchmark's: rows in output order."""
    out_flat = np.arange(N)
    if kind == "roll":
        return LineageRelation.from_flat((N,), (N,), out_flat, (out_flat - 2) % N)
    if kind == "sort":
        perm = np.argsort(np.random.default_rng(3).random(N), kind="stable")
        return LineageRelation.from_flat((N,), (N,), out_flat, perm)
    assert kind == "reshape"
    return LineageRelation.from_flat((N,), (SIDE, SIDE), out_flat, out_flat)


def _shuffled(rel: LineageRelation) -> LineageRelation:
    perm = np.random.default_rng(5).permutation(rel.n_rows)
    return LineageRelation(
        rel.out_shape, rel.in_shape, rel.out_idx[perm], rel.in_idx[perm]
    )


def _assert_same_table(got, want):
    assert (got.key_shape, got.val_shape, got.direction) == (
        want.key_shape, want.val_shape, want.direction,
    )
    for name in ("key_lo", "key_hi", "val_lo", "val_hi", "val_ref"):
        a, b = getattr(got, name), getattr(want, name)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes(), name


@pytest.mark.parametrize("shuffle", [False, True], ids=["output_order", "shuffled"])
@pytest.mark.parametrize("kind", ["roll", "sort", "reshape"])
def test_add_lineage_dedups_once_into_identical_tables(tmp_path, kind, shuffle):
    rel = _hop(kind)
    if shuffle:
        rel = _shuffled(rel)
    rows = np.unique(rel.rows(), axis=0)
    l = rel.ndim_out
    ref = LineageRelation(rel.out_shape, rel.in_shape, rows[:, :l], rows[:, l:])
    with DSLog.open(str(tmp_path / "store"), durability="manual") as log:
        log.define_array("A", rel.in_shape)
        log.define_array("B", rel.out_shape)
        with log.trace_scope("ingest") as tr:
            entry = log.add_lineage("A", "B", rel)
            log.commit()
    _assert_same_table(entry.backward, compress(ref, "backward"))
    _assert_same_table(entry.forward, compress(ref, "forward"))

    bwd, fwd = tr.spans("canonical")
    assert (bwd.attrs["direction"], fwd.attrs["direction"]) == ("backward", "forward")
    assert bwd.attrs["route"] == ("packed" if shuffle else "presorted")
    assert fwd.attrs["route"] == "reused"
    for sp in (bwd, fwd):
        assert sp.attrs["rows_in"] == sp.attrs["rows_out"] == N


def test_each_add_lineage_dedups_its_own_input(tmp_path, monkeypatch):
    calls = []
    real = relation_mod._canonical_rows

    def counting(*args):
        calls.append(args[0].shape[0])
        return real(*args)

    monkeypatch.setattr(relation_mod, "_canonical_rows", counting)
    rel = _shuffled(_hop("roll"))
    with DSLog.open(str(tmp_path / "store"), durability="manual") as log:
        for name in ("A", "B", "C"):
            log.define_array(name, (N,))
        with log.trace_scope("ingest") as tr:
            first = log.add_lineage("A", "B", rel)
            second = log.add_lineage("B", "C", rel)  # the same object again
            log.commit()
    assert calls == [N, N]
    routes = [s.attrs["route"] for s in tr.spans("canonical")]
    assert routes == ["packed", "reused", "packed", "reused"]
    _assert_same_table(second.backward, first.backward)
    _assert_same_table(second.forward, first.forward)
    # the caller's relation is as it was handed in
    assert rel.out_idx.tobytes() == _shuffled(_hop("roll")).out_idx.tobytes()


def test_compress_both_spans_without_a_store():
    from repro.obs.trace import QueryTrace

    rel = _shuffled(_hop("reshape"))
    with QueryTrace("ingest") as tr:
        bwd, fwd = compress_both(rel, trace=tr)
    assert [s.attrs["route"] for s in tr.spans("canonical")] == ["packed", "reused"]
    _assert_same_table(bwd, compress(rel, "backward"))
    _assert_same_table(fwd, compress(rel, "forward"))
    untraced = compress_both(rel)
    _assert_same_table(untraced[0], bwd)
    _assert_same_table(untraced[1], fwd)
