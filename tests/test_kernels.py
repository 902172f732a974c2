"""Pallas kernels vs pure-jnp refs: shape/dtype sweeps, interpret=True."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import range_join_pairs, run_boundaries
from repro.kernels.range_join import range_join_mask
from repro.kernels.ref import range_join_mask_ref, run_boundaries_ref
from repro.kernels.run_boundary import run_boundaries_packed

rng = np.random.default_rng(0)


@pytest.mark.parametrize("n,nk,block", [
    (512, 1, 128), (1024, 2, 256), (2048, 4, 512), (4096, 8, 1024),
    (1024, 1, 1024), (3072, 6, 256),
])
def test_run_boundary_matches_ref(n, nk, block):
    packed = np.zeros((n, 128), np.int32)
    for c in range(nk):
        packed[:, c] = np.sort(rng.integers(0, 7, n))
    lo = np.sort(rng.integers(0, n // 2, n))
    packed[:, nk] = lo
    packed[:, nk + 1] = lo + rng.integers(0, 3, n)
    got = run_boundaries_packed(
        jnp.asarray(packed), n_keys=nk, block_rows=block, interpret=True
    )
    want = run_boundaries_ref(jnp.asarray(packed), nk)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_run_boundary_property(data):
    n = data.draw(st.sampled_from([256, 512, 1024]))
    nk = data.draw(st.integers(1, 5))
    seed = data.draw(st.integers(0, 2**31))
    r = np.random.default_rng(seed)
    packed = np.zeros((n, 128), np.int32)
    for c in range(nk):
        packed[:, c] = np.sort(r.integers(0, 5, n))
    lo = np.sort(r.integers(0, 40, n))
    packed[:, nk] = lo
    packed[:, nk + 1] = lo
    got = np.asarray(
        run_boundaries_packed(jnp.asarray(packed), n_keys=nk, block_rows=256, interpret=True)
    )
    want = np.asarray(run_boundaries_ref(jnp.asarray(packed), nk))
    np.testing.assert_array_equal(got, want)


def test_run_boundaries_wrapper_vs_numpy():
    """Wrapper output drives the same segmentation numpy produces."""
    n = 3000
    g = np.sort(rng.integers(0, 12, n)).astype(np.int64)
    lo = rng.integers(0, 50, n).astype(np.int64)
    order = np.lexsort((lo, g))
    g, lo = g[order], lo[order]
    flags = run_boundaries([g], lo, lo, block_rows=512)
    want = np.ones(n, bool)
    want[1:] = (g[1:] != g[:-1]) | (lo[1:] > lo[:-1] + 1)
    np.testing.assert_array_equal(flags, want)


@pytest.mark.parametrize("nq,nr,l,bq,br", [
    (100, 300, 1, 128, 128), (257, 511, 2, 128, 256),
    (64, 64, 3, 64, 64), (1000, 50, 4, 256, 128),
])
def test_range_join_matches_oracle(nq, nr, l, bq, br):
    q_lo = rng.integers(0, 60, (nq, l))
    q_hi = q_lo + rng.integers(0, 6, (nq, l))
    r_lo = rng.integers(0, 60, (nr, l))
    r_hi = r_lo + rng.integers(0, 6, (nr, l))
    qi, ri = range_join_pairs(q_lo, q_hi, r_lo, r_hi, block_q=bq, block_r=br)
    ov = np.ones((nq, nr), bool)
    for j in range(l):
        ov &= (q_lo[:, j : j + 1] <= r_hi[None, :, j]) & (
            r_lo[None, :, j] <= q_hi[:, j : j + 1]
        )
    wq, wr = np.nonzero(ov)
    np.testing.assert_array_equal(qi, wq)
    np.testing.assert_array_equal(ri, wr)


def test_range_join_kernel_vs_ref_padded():
    nq = nr = 256
    l = 2
    q = np.zeros((nq, 128), np.int32)
    r = np.zeros((nr, 128), np.int32)
    q[:, :l] = rng.integers(0, 30, (nq, l))
    q[:, l : 2 * l] = q[:, :l] + rng.integers(0, 4, (nq, l))
    r[:, :l] = rng.integers(0, 30, (nr, l))
    r[:, l : 2 * l] = r[:, :l] + rng.integers(0, 4, (nr, l))
    got = range_join_mask(
        jnp.asarray(q), jnp.asarray(r), n_attrs=l, block_q=128, block_r=128,
        interpret=True,
    )
    want = range_join_mask_ref(jnp.asarray(q), jnp.asarray(r), l)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_range_join_empty_inputs():
    qi, ri = range_join_pairs(
        np.zeros((0, 2)), np.zeros((0, 2)), np.zeros((5, 2)), np.ones((5, 2))
    )
    assert qi.size == 0 and ri.size == 0


@pytest.mark.parametrize("nq", [255, 256, 257])
@pytest.mark.parametrize("nr", [255, 256, 257])
def test_range_join_internal_padding_at_block_boundaries(nq, nr):
    """Regression (ISSUE 5): the kernel pads internally — row counts that
    are not block multiples must work and padded rows must never match."""
    l = 2
    q_lo = rng.integers(0, 40, (nq, l))
    q_hi = q_lo + rng.integers(0, 5, (nq, l))
    r_lo = rng.integers(0, 40, (nr, l))
    r_hi = r_lo + rng.integers(0, 5, (nr, l))
    qi, ri = range_join_pairs(q_lo, q_hi, r_lo, r_hi, block_q=256, block_r=256)
    ov = np.ones((nq, nr), bool)
    for j in range(l):
        ov &= (q_lo[:, j : j + 1] <= r_hi[None, :, j]) & (
            r_lo[None, :, j] <= q_hi[:, j : j + 1]
        )
    wq, wr = np.nonzero(ov)
    np.testing.assert_array_equal(qi, wq)
    np.testing.assert_array_equal(ri, wr)


def test_range_join_mask_unpadded_rows_direct():
    """range_join_mask itself accepts non-multiple row counts (the old
    ``nq % block_q == 0`` assert forced callers to pre-pad)."""
    q = np.zeros((255, 128), np.int32)
    r = np.zeros((130, 128), np.int32)
    q[:, :1] = rng.integers(0, 9, (255, 1))
    q[:, 1:2] = q[:, :1] + 1
    r[:, :1] = rng.integers(0, 9, (130, 1))
    r[:, 1:2] = r[:, :1] + 1
    mask = range_join_mask(
        jnp.asarray(q), jnp.asarray(r), n_attrs=1, block_q=128, block_r=128,
        interpret=True,
    )
    assert mask.shape == (255, 130)
    want = (q[:, :1] <= r[None, :, 1]) & (r[None, :, 0] <= q[:, 1:2])
    np.testing.assert_array_equal(np.asarray(mask).astype(bool), want)


def test_range_join_mask_lane_capacity_raises():
    q = np.zeros((8, 128), np.int32)
    with pytest.raises(ValueError, match="lane capacity"):
        range_join_mask(
            jnp.asarray(q), jnp.asarray(q), n_attrs=65, interpret=True
        )


def test_segmented_pack_matches_per_segment_joins():
    """One launch, many joins: segment-id lanes keep the masks separable,
    mixed attribute widths ride the same pack."""
    from repro.kernels.ops import segmented_range_join_pairs

    segs = []
    for l in (1, 3, 2, 1):
        nq, nr = int(rng.integers(1, 50)), int(rng.integers(1, 70))
        q_lo = rng.integers(0, 25, (nq, l))
        q_hi = q_lo + rng.integers(0, 5, (nq, l))
        r_lo = rng.integers(0, 25, (nr, l))
        r_hi = r_lo + rng.integers(0, 5, (nr, l))
        segs.append((q_lo, q_hi, r_lo, r_hi))
    got, info = segmented_range_join_pairs(
        segs, block_q=64, block_r=64, interpret=True
    )
    assert info["launches"] == 1 and info["rows_padded"] >= info["rows"] > 0
    for (q_lo, q_hi, r_lo, r_hi), (qi, ri) in zip(segs, got):
        wq, wr = range_join_pairs(q_lo, q_hi, r_lo, r_hi, block_q=64, block_r=64)
        np.testing.assert_array_equal(qi, wq)
        np.testing.assert_array_equal(ri, wr)


def _random_segments(r, k, widths=(1, 2, 3), max_rows=90, coords=(0, 25)):
    segs = []
    for i in range(k):
        l = int(widths[i % len(widths)])
        nq, nr = int(r.integers(1, max_rows)), int(r.integers(1, max_rows))
        q_lo = r.integers(*coords, (nq, l))
        q_hi = q_lo + r.integers(0, 5, (nq, l))
        r_lo = r.integers(*coords, (nr, l))
        r_hi = r_lo + r.integers(0, 5, (nr, l))
        segs.append((q_lo, q_hi, r_lo, r_hi))
    return segs


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_blockdiag_layout_property(data):
    """ISSUE 8 tentpole: the block-diagonal tile schedule is bit-identical
    to the masked cross-product launch and the per-segment oracle, across
    ragged segment counts/sizes/widths, and never visits more tiles than
    the cross product."""
    from repro.kernels.ops import segmented_range_join_pairs

    seed = data.draw(st.integers(0, 2**31))
    k = data.draw(st.integers(2, 7))
    bq = data.draw(st.sampled_from([32, 64, 128]))
    br = data.draw(st.sampled_from([32, 64, 128]))
    segs = _random_segments(np.random.default_rng(seed), k)
    dense, dinfo = segmented_range_join_pairs(
        segs, block_q=bq, block_r=br, interpret=True, layout="dense"
    )
    diag, ginfo = segmented_range_join_pairs(
        segs, block_q=bq, block_r=br, interpret=True, layout="blockdiag"
    )
    assert ginfo["layout"] == "blockdiag" and dinfo["layout"] == "dense"
    assert ginfo["tiles_visited"] + ginfo["tiles_skipped"] >= dinfo["tiles_visited"]
    for s, (q_lo, q_hi, r_lo, r_hi) in enumerate(segs):
        wq, wr = range_join_pairs(q_lo, q_hi, r_lo, r_hi, block_q=bq, block_r=br)
        for got in (dense[s], diag[s]):
            np.testing.assert_array_equal(got[0], wq)
            np.testing.assert_array_equal(got[1], wr)


def test_blockdiag_padding_rows_never_match():
    """Per-segment padding rows carry (lo=1, hi=0); boxes spanning [<=0, >=1]
    can graze them, so the extractor's bounds filter must drop any pair
    touching a padded row."""
    from repro.kernels.ops import segmented_range_join_pairs

    segs = []
    for _ in range(3):
        nq, nr = int(rng.integers(3, 40)), int(rng.integers(3, 40))
        q_lo = rng.integers(-4, 2, (nq, 2))  # spans the pad sentinel [1, 0]
        q_hi = q_lo + rng.integers(0, 6, (nq, 2))
        r_lo = rng.integers(-4, 2, (nr, 2))
        r_hi = r_lo + rng.integers(0, 6, (nr, 2))
        segs.append((q_lo, q_hi, r_lo, r_hi))
    diag, _ = segmented_range_join_pairs(
        segs, block_q=32, block_r=32, interpret=True, layout="blockdiag"
    )
    for (q_lo, q_hi, r_lo, r_hi), (qi, ri) in zip(segs, diag):
        wq, wr = range_join_pairs(q_lo, q_hi, r_lo, r_hi)
        np.testing.assert_array_equal(qi, wq)
        np.testing.assert_array_equal(ri, wr)


def test_segmented_auto_layout_routing():
    """layout="auto" charges both schedules in tiles: a many-segment
    frontier goes block-diagonal, one segment stays on the dense launch."""
    from repro.kernels.ops import segmented_range_join_pairs

    segs = _random_segments(np.random.default_rng(3), 6, max_rows=200)
    _, info = segmented_range_join_pairs(segs, block_q=64, block_r=64,
                                         interpret=True, layout="auto")
    assert info["layout"] == "blockdiag"
    assert info["tiles_skipped"] > 0
    _, info1 = segmented_range_join_pairs(segs[:1], block_q=64, block_r=64,
                                          interpret=True, layout="auto")
    assert info1["layout"] == "dense" and info1["tiles_skipped"] == 0
    with pytest.raises(ValueError, match="layout"):
        segmented_range_join_pairs(segs, layout="ragged")


def test_segmented_single_segment_skips_id_lane():
    """ISSUE 8 satellite: a one-segment frontier needs no segment-id lane,
    so the max packable width is LANES // 2 — one more than the segmented
    pack admits."""
    from repro.kernels.ops import segmented_range_join_pairs
    from repro.kernels.range_join import LANES

    l = LANES // 2  # 64: lo+hi fill all 128 lanes, no room for a seg id
    box = (np.zeros((4, l)), np.ones((4, l)), np.zeros((5, l)), np.ones((5, l)))
    got, info = segmented_range_join_pairs([box], interpret=True)
    assert info["layout"] == "dense"
    assert got[0][0].size == 4 * 5  # unit boxes all overlap
    with pytest.raises(ValueError, match="lane capacity"):
        segmented_range_join_pairs([box, box], interpret=True, layout="dense")


@pytest.mark.parametrize("n", [1, 255, 1024, 1025])
def test_run_boundary_pads_non_multiple_rows(n):
    """Regression (ISSUE 8): run_boundaries_packed padded internally
    instead of asserting ``n % block_rows == 0``."""
    r = np.random.default_rng(n)
    packed = np.zeros((n, 128), np.int32)
    packed[:, 0] = np.sort(r.integers(0, 6, n))
    lo = np.sort(r.integers(0, max(n // 3, 2), n))
    packed[:, 1] = lo
    packed[:, 2] = lo + r.integers(0, 3, n)
    got = run_boundaries_packed(
        jnp.asarray(packed), n_keys=1, block_rows=256, interpret=True
    )
    assert got.shape == (n,)
    want = run_boundaries_ref(jnp.asarray(packed), 1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _boxes(r, n, l, coords=(0, 40), span=5):
    lo = r.integers(*coords, (n, l))
    return lo, lo + r.integers(0, span, (n, l))


def _twin(q_lo, q_hi, r_lo, r_hi):
    """Pairs from the executor's numpy twin (R as ``[l, N]`` columns)."""
    from repro.core.query import _twin_pairs

    return _twin_pairs(
        q_lo, q_hi, np.ascontiguousarray(r_lo.T), np.ascontiguousarray(r_hi.T)
    )


@pytest.mark.parametrize("l", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [255, 256, 257])
def test_row_chunked_mask_matches_ref_and_twin(n, l):
    """The row-chunked body over a transposed R block, at and around one
    256-row tile: the mask equals the jnp reference and the pairs equal the
    numpy twin's, for every attribute count the chip compile covers."""
    from repro.kernels.ops import _pack_boxes

    r = np.random.default_rng(10 * n + l)
    q_lo, q_hi = _boxes(r, n, l)
    r_lo, r_hi = _boxes(r, 513 - n, l)
    q, rp = _pack_boxes(q_lo, q_hi, l), _pack_boxes(r_lo, r_hi, l)
    mask = range_join_mask(
        jnp.asarray(q), jnp.asarray(rp), n_attrs=l, block_q=256, block_r=256,
        interpret=True,
    )
    want = range_join_mask_ref(jnp.asarray(q), jnp.asarray(rp), l)
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(want))
    qi, ri = range_join_pairs(q_lo, q_hi, r_lo, r_hi, interpret=True)
    wq, wr = _twin(q_lo, q_hi, r_lo, r_hi)
    np.testing.assert_array_equal(qi, wq)
    np.testing.assert_array_equal(ri, wr)


@pytest.mark.parametrize("l", [1, 2, 4, 8])
def test_tile_masks_match_ref_per_tile(l):
    """Every scheduled tile of range_join_tile_masks equals the reference
    mask of its (q block, r block) — in schedule order, off-diagonal and
    repeated tiles included."""
    from repro.kernels.ops import _pack_boxes, _pad_packed_rows
    from repro.kernels.range_join import range_join_tile_masks

    r = np.random.default_rng(l)
    bq, br = 128, 256
    q = _pad_packed_rows(_pack_boxes(*_boxes(r, 257, l), l), bq, l)
    rp = _pad_packed_rows(_pack_boxes(*_boxes(r, 300, l), l), br, l)
    tq = np.array([0, 2, 1, 0, 2], np.int32)
    tr = np.array([1, 0, 1, 0, 1], np.int32)
    masks = np.asarray(range_join_tile_masks(
        jnp.asarray(q), jnp.asarray(rp), jnp.asarray(tq), jnp.asarray(tr),
        n_attrs=l, block_q=bq, block_r=br, interpret=True,
    ))
    want = np.asarray(range_join_mask_ref(jnp.asarray(q), jnp.asarray(rp), l))
    for t, (i, j) in enumerate(zip(tq, tr)):
        np.testing.assert_array_equal(
            masks[t], want[i * bq : (i + 1) * bq, j * br : (j + 1) * br]
        )


@pytest.mark.parametrize("layout", ["dense", "blockdiag"])
@pytest.mark.parametrize("n", [255, 256, 257])
def test_segmented_pad_graze_matches_twin(n, layout):
    """Boxes spanning the padding sentinel (lo=1, hi=0) at 256-row segment
    edges: both launch layouts at the default geometry return the twin's
    pair lists, so no padded row ever leaks into a result."""
    from repro.kernels.ops import segmented_range_join_pairs

    r = np.random.default_rng(n)
    segs = []
    for k, l in enumerate((2, 1, 3)):
        nq, nr = n + k - 1, 2 * n - k
        q_lo, q_hi = _boxes(r, nq, l, coords=(-4, 2), span=6)
        r_lo, r_hi = _boxes(r, nr, l, coords=(-4, 2), span=6)
        segs.append((q_lo, q_hi, r_lo, r_hi))
    got, info = segmented_range_join_pairs(
        segs, block_q=256, block_r=256, interpret=True, layout=layout
    )
    assert info["layout"] == layout
    for (q_lo, q_hi, r_lo, r_hi), (qi, ri) in zip(segs, got):
        wq, wr = _twin(q_lo, q_hi, r_lo, r_hi)
        np.testing.assert_array_equal(qi, wq)
        np.testing.assert_array_equal(ri, wr)


def test_range_join_rejects_block_q_off_row_chunk():
    """block_q must be a whole number of row chunks (sublane tiles)."""
    q = np.zeros((16, 128), np.int32)
    with pytest.raises(ValueError, match="block_q"):
        range_join_mask(
            jnp.asarray(q), jnp.asarray(q), n_attrs=1, block_q=12,
            block_r=128, interpret=True,
        )
