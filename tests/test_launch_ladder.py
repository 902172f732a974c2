"""Shape-stable launches: the ladder, the launch-shape table, the tuner's
buckets, and pair lists bit-identical to exact-shape launches."""

import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.autotune import (
    GeometryTuner,
    LADDER_LINEAR,
    LADDER_RATIO,
    ladder_step,
    shape_bucket,
)
from repro.kernels.ops import LaunchPrograms, range_join_pairs, segmented_range_join_pairs


@pytest.mark.parametrize("unit", [1, 8, 64, 256])
def test_ladder_steps_hold_n_in_whole_units_with_under_a_quarter_of_padding(unit):
    last = 0
    for n in range(1, 40 * unit, max(1, unit // 7)):
        step = ladder_step(n, unit)
        assert step >= n and step % unit == 0 and step >= last
        if step > LADDER_LINEAR * unit:
            assert step < LADDER_RATIO * n + unit
        last = step
    assert ladder_step(0, unit) == 0


def test_ladder_moves_by_steps():
    assert [ladder_step(8, 1, k) for k in (-9, -2, -1, 0, 1, 2, 3)] == [1, 6, 7, 8, 10, 12, 15]
    assert ladder_step(9, 8) == 16 and ladder_step(65, 8) == 80
    assert ladder_step(104_857, 256) == 457 * 256


@pytest.mark.parametrize("layout", ["dense", "blockdiag"])
@pytest.mark.parametrize("n", [1, 7, 63, 65, 255, 257])
def test_ladder_launch_pairs_equal_the_exact_launch(monkeypatch, layout, n):
    """Boxes spanning [<=0, >=1] overlap both the per-block padding's empty
    boxes (lo = 1, hi = 0) and the ladder's zero rows; none of those pairs
    may reach a result, in either layout."""
    monkeypatch.setattr(ops, "PROGRAMS", LaunchPrograms())
    r = np.random.default_rng(n)
    segs = []
    for k, l in enumerate((2, 1, 2)):
        nq, nr = n + k, 2 * n + 1 - k
        q_lo = r.integers(-4, 2, (nq, l))
        r_lo = r.integers(-4, 2, (nr, l))
        segs.append((q_lo, q_lo + r.integers(0, 6, (nq, l)),
                     r_lo, r_lo + r.integers(0, 6, (nr, l))))
    for segments in (segs, segs[:1]):
        exact, _ = segmented_range_join_pairs(
            segments, 64, 128, interpret=True, layout=layout)
        ladder, info = segmented_range_join_pairs(
            segments, 64, 128, interpret=True, layout=layout, stable=True)
        for s, ((qi, ri), (lq, lr)) in enumerate(zip(exact, ladder)):
            np.testing.assert_array_equal(lq, qi)
            np.testing.assert_array_equal(lr, ri)
            wq, wr = range_join_pairs(*segments[s], block_q=64, block_r=128)
            np.testing.assert_array_equal(lq, wq)
            np.testing.assert_array_equal(lr, wr)
        assert info["ladder_pad_rows"] >= 0 and info["mask_cells"] > 0
    assert len(ops.PROGRAMS) > 0


def test_settled_launches_in_chunks_or_on_another_geometry_equal_the_exact_launch(
        monkeypatch):
    """Once the table is settled, a frontier past every built shape runs in
    two launches of the largest, a launch of an unbuilt geometry, of one
    segment or of fewer table rows on a built one, and one of more table
    rows as a launch per segment; the pair lists stay those of an
    exact-shape launch, boxes spanning [<=0, >=1] included."""
    monkeypatch.setattr(ops, "PROGRAMS", LaunchPrograms())
    r = np.random.default_rng(5)

    def seg(nq, nr=5):
        q_lo, r_lo = r.integers(-3, 4, (nq, 2)), r.integers(-3, 4, (nr, 2))
        return (q_lo, q_lo + r.integers(0, 3, (nq, 2)), r_lo, r_lo + 2)

    def launch(segs, geometry=(256, 256)):
        got, info = segmented_range_join_pairs(
            segs, *geometry, interpret=True, layout="dense", stable=True)
        want, _ = segmented_range_join_pairs(segs, *geometry, interpret=True)
        for (gq, gr), (wq, wr) in zip(got, want):
            np.testing.assert_array_equal(gq, wq)
            np.testing.assert_array_equal(gr, wr)
        return info

    built = launch([seg(20), seg(20)])["ladder_pad_rows"] + 40  # a segment lane
    monkeypatch.setattr(ops.PROGRAMS, "_since_build", ops.SETTLE_LAUNCHES)
    info = launch([seg(built + 1, 10)])
    assert info["launches"] == 2 and info["ladder_pad_rows"] == built - 1
    info = launch([seg(built // 2, 10)], geometry=(128, 128))
    assert info["launches"] == 1 and info["geometry"] == (256, 256)
    # fewer table rows than the program's: padded, and cut from the mask
    info = launch([seg(30, 3), seg(9, 4)])
    assert info["ladder_pad_rows"] == built - 39 + 3
    # more: each segment launches alone, on the same program
    info = launch([seg(30, 8), seg(9, 4)])
    assert info["layout"] == "each" and info["launches"] == 2
    assert len(ops.PROGRAMS) == 1


def test_a_ladder_launch_reuses_programs_across_nearby_frontiers(monkeypatch):
    """Frontiers a few rows apart land on shapes already built: one program
    serves them all, and the pair lists stay exact."""
    monkeypatch.setattr(ops, "PROGRAMS", LaunchPrograms())
    r = np.random.default_rng(3)
    table = r.integers(0, 50, (5, 2))
    for nq in range(40, 72, 3):
        q_lo = r.integers(0, 50, (nq, 2))
        seg = (q_lo, q_lo + 2, table, table + 3)
        (got,), info = segmented_range_join_pairs(
            [seg], interpret=True, layout="dense", stable=True)
        want = range_join_pairs(*seg)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])
    assert len(ops.PROGRAMS) == 1


def _plan(p, nq, kinds=("mask",), geometry=(256, 256), nr=(2000,)):
    """A launch of one segment per entry of ``nq`` against tables of ``nr``
    rows: a dense query row of a 2,000-row table moves 8,512 bytes, so
    ``PAD_BUDGET`` pays for 15 of them."""
    return p.plan(kinds, *geometry, True, list(nq), list(nr), 2)


def _build(p, key, *shapes):
    for shape in shapes:
        p.program(key, shape, lambda: type("L", (), {"compile": lambda self: object()})())


def test_a_launch_takes_a_built_shape_near_its_own_or_builds_its_step():
    p = LaunchPrograms()
    key = ("mask", 2, 256, 256, True, 2000)
    assert _plan(p, [65]) == (key, (80,), 1)  # nothing built: its own step
    _build(p, key, (80,))
    assert _plan(p, [50]) == (key, (80,), 1)  # within two steps above
    assert _plan(p, [30]) == (key, (40,), 1)  # too far below: its own, and 8 rows
    assert _plan(p, [81]) == (key, (96,), 1)  # past every built shape: its own
    # a cheap row (552 bytes): the highest step within PAD_BUDGET of its own
    small = ("mask", 2, 256, 256, True, 10)
    assert _plan(p, [90], nr=[10]) == (small, (328,), 1)
    assert _plan(p, [9], nr=[10]) == (small, (216,), 1)


def test_a_settled_table_reuses_programs_chunks_and_other_kernels():
    p = LaunchPrograms()
    key = ("mask", 2, 256, 256, True, 2000)
    tiles = ("tiles", 2, 64, 128, True, 2048)
    _build(p, key, (24,), (96,))
    _build(p, tiles, (128, 16))
    for _ in range(ops.SETTLE_LAUNCHES):
        assert _plan(p, [90]) == (key, (96,), 1)
    assert _plan(p, [56]) == (key, (96,), 1)  # a holder within twice the bytes
    assert _plan(p, [30]) == (key, (96,), 1)  # one launch before two chunks
    assert _plan(p, [100]) == (key, (96,), 2)  # two launches of the largest
    assert _plan(p, [300]) == (key, (328,), 1)  # more chunks than MAX_CHUNKS: builds
    # another geometry's program stands in for one never built
    assert _plan(p, [90], geometry=(128, 256)) == (key, (96,), 1)
    # two segments: a dense program holds them only with its segment lane
    two = ("mask", 3, 256, 256, True, 2000)
    assert _plan(p, [40, 40], kinds=("tiles", "mask"), geometry=(64, 128),
                 nr=(1000, 1000)) == (tiles, (128, 16), 1)
    # the block-diagonal program stands in for the dense one the caller
    # prefers, where the caller allows both
    assert _plan(p, [40, 40], kinds=("mask", "tiles"),
                 nr=(1000, 1000)) == (tiles, (128, 16), 1)
    # no program holds the two segments together: each launches alone
    assert _plan(p, [40, 40], nr=(1000, 1000)) is None
    _build(p, two, (80,))  # a build unsettles the table
    assert _plan(p, [30]) == (key, (40,), 1)


def test_tuner_buckets_on_ladder_steps_and_borrows_the_nearest():
    assert shape_bucket([(3000, 1, 2), (2900, 1, 2)]) == shape_bucket([(3050, 1, 2)] * 2)
    assert shape_bucket([(3000, 1, 2)]) != shape_bucket([(3800, 1, 2)])
    t = GeometryTuner()
    tuned = shape_bucket([(3000, 1, 2)])
    t.pick("tpu", tuned, runner=lambda g: g, candidates=((64, 128),), warmup=False)
    assert t.nearest("tpu", shape_bucket([(3800, 1, 2)])) == (64, 128)
    assert t.nearest("tpu", shape_bucket([(9000, 1, 2)])) is None  # too many steps
    assert t.nearest("tpu", shape_bucket([(3800, 1, 3)])) is None  # another width
    assert t.nearest("interpret", shape_bucket([(3800, 1, 2)])) is None
    assert t.nearest("tpu", "empty") is None


def test_an_unseen_bucket_near_a_tuned_one_counts_a_fallback(monkeypatch):
    """The executor measures candidates for a new frontier shape only where
    no tuned bucket lies within a few ladder steps; otherwise it borrows that
    geometry and counts ``tuner_fallbacks``."""
    from repro.core.query import BatchedJoinExecutor, JoinRequest, QueryBox
    from repro.core.capture import roll_lineage
    from repro.core.provrc import compress

    monkeypatch.setattr(ops, "PROGRAMS", LaunchPrograms())
    counts: dict = {}
    tuner = GeometryTuner()
    ex = BatchedJoinExecutor(
        stats=lambda k, n=1: counts.__setitem__(k, counts.get(k, 0) + n),
        engine="kernel", tuner=tuner)
    shape = (64, 64)
    table = compress(roll_lineage(shape, 3, 0), "backward")
    r = np.random.default_rng(0)
    measured = []
    real_pick = tuner.pick

    def pick(*a, **kw):
        measured.append(a[1])
        return real_pick(*a, **{**kw, "candidates": ((64, 128), (128, 128))})

    monkeypatch.setattr(tuner, "pick", pick)
    for n in (2100, 2400, 2700):
        cells = np.stack(np.unravel_index(r.choice(64 * 64, n, replace=False), shape), 1)
        (got,) = ex.run([JoinRequest([QueryBox.from_cells(shape, cells)], table,
                                     merge=False, path="batched")])
        assert got[0].n_rows == n
    assert len(measured) == 1
    assert counts["tuner_fallbacks"] == 2
    assert counts["mask_cells_written"] > 0 and counts["ladder_pad_rows"] >= 0


def test_upsample_capture_reads_the_cell_under_each_output():
    from repro.core.capture import upsample2d_lineage

    rel = upsample2d_lineage(3, 5, 2)
    assert rel.out_shape == (6, 10) and rel.in_shape == (3, 5)
    pairs = {(tuple(o), tuple(i)) for o, i in zip(rel.out_idx, rel.in_idx)}
    assert pairs == {((i, j), (i // 2, j // 2)) for i in range(6) for j in range(10)}


def test_a_node_where_steps_converge_merges_in_a_fanin_span():
    """A diamond's join node merges under ``fanin_merge``; nodes with one
    incoming step keep ``merge``."""
    from repro.core.capture import identity_lineage, roll_lineage
    from repro.core.catalog import DSLog

    shape = (16, 16)
    log = DSLog(store_forward=True)
    for name in "abcd":
        log.define_array(name, shape)
    log.add_lineage("a", "b", identity_lineage(shape))
    log.add_lineage("a", "c", roll_lineage(shape, 2, 1))
    log.add_lineage("b", "d", identity_lineage(shape))
    log.add_lineage("c", "d", identity_lineage(shape))
    cells = np.array([[1, 1], [5, 9]])
    res, tr = log.prov_query("a", "d", cells, trace=True)
    assert len(tr.spans("fanin_merge")) == 1
    assert [s.attrs.get("at") for s in tr.spans("merge")].count("hop") == 2
    want = {(1, 1), (5, 9), (1, 3), (5, 11)}
    assert {tuple(c) for c in res.cells()} == want
    _, chain = log.prov_query(["a", "b", "d"], cells, trace=True)
    assert not chain.spans("fanin_merge")
