"""Jit'd public wrappers around the Pallas kernels.

These handle packing/padding from the natural numpy layouts used by
``repro.core`` into the 128-lane int32 tiles the kernels expect, and select
``interpret=True`` automatically when no TPU is attached, so the kernel
bodies are validated on CPU.

The packers are int32: coordinates outside the int32 range cannot ride the
kernel path (they would silently wrap — the bug this module now refuses).
``fits_int32`` is the gate callers use to route oversized joins to the
numpy dense path; handing out-of-range values to a packer raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import maybe_span

from .range_join import (
    LANES,
    check_lane_capacity,
    range_join_mask,
    range_join_tile_masks,
)
from .run_boundary import run_boundaries_packed

__all__ = [
    "run_boundaries",
    "range_join_pairs",
    "segmented_range_join_pairs",
    "default_interpret",
    "fits_int32",
]

_I32 = np.iinfo(np.int32)


def default_interpret() -> bool:
    """Interpret the kernels unless JAX's default device is a TPU.

    This is the CPU test path, and it is silent by design; a chip run
    proves the compiled path ran from ``io_stats`` (``twin_launches == 0``)
    and ``batched(tpu:…)`` plan notes, as ``chip_smoke.py`` does.
    """
    return jax.devices()[0].platform != "tpu"


def fits_int32(*arrays: np.ndarray) -> bool:
    """Whether every value survives an int32 pack without wrapping."""
    for a in arrays:
        if a.size and (a.min() < _I32.min or a.max() > _I32.max):
            return False
    return True


def _require_int32(*arrays: np.ndarray) -> None:
    if not fits_int32(*arrays):
        raise ValueError(
            "coordinates outside the int32 range cannot be packed for the "
            "kernel path (they would wrap); route this join to the numpy "
            "dense path (fits_int32 gates this)"
        )


def run_boundaries(
    group_cols: list[np.ndarray],
    lo: np.ndarray,
    hi: np.ndarray,
    block_rows: int = 1024,
    interpret: bool | None = None,
) -> np.ndarray:
    """Boundary flags for sorted rows; drop-in for the numpy hot pass.

    ``group_cols`` are the equality columns, ``lo``/``hi`` the merge-column
    interval.  Values must fit int32 (array indices always do).
    """
    if interpret is None:
        interpret = default_interpret()
    n = lo.shape[0]
    n_keys = len(group_cols)
    assert n_keys + 2 <= LANES, "too many group columns for one tile"
    _require_int32(*group_cols, lo, hi)
    packed = np.zeros((n, LANES), np.int32)
    for c, col in enumerate(group_cols):
        packed[:, c] = col.astype(np.int32)
    packed[:, n_keys] = lo.astype(np.int32)
    packed[:, n_keys + 1] = hi.astype(np.int32)
    # the kernel pads rows to the block grid internally (copies of the last
    # row never start a run) and slices the flags back to n
    flags = run_boundaries_packed(
        jnp.asarray(packed),
        n_keys=n_keys,
        block_rows=block_rows,
        interpret=interpret,
    )
    return np.asarray(flags).astype(bool)


def _pack_boxes(lo: np.ndarray, hi: np.ndarray, n_attrs: int) -> np.ndarray:
    """Pack ``[N, l]`` lo/hi into the kernel's ``[N, 128]`` int32 layout.

    Lanes ``[0, n_attrs)`` hold lo columns, ``[n_attrs, 2*n_attrs)`` hi
    columns; attributes beyond ``lo.shape[1]`` (width padding in segmented
    packs) are left ``lo = hi = 0`` on *both* operands, which always
    overlaps and so never filters a pair.
    """
    n, l = lo.shape
    _require_int32(lo, hi)  # last line of defense at the cast site
    p = np.zeros((n, LANES), np.int32)
    p[:, :l] = lo.astype(np.int32)
    p[:, n_attrs : n_attrs + l] = hi.astype(np.int32)
    return p


def range_join_pairs(
    q_lo: np.ndarray,
    q_hi: np.ndarray,
    r_lo: np.ndarray,
    r_hi: np.ndarray,
    block_q: int = 256,
    block_r: int = 256,
    interpret: bool | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """All (query row, table row) index pairs whose boxes overlap.

    Kernel-accelerated replacement for the broadcasting pass inside
    ``repro.core.query.theta_join``.  Raises for joins the kernel cannot
    express faithfully (lane capacity, int32 overflow) — the caller's
    routing (``repro.core.query._kernel_pairs``) checks the same gates and
    falls back to numpy before ever reaching this point.
    """
    if interpret is None:
        interpret = default_interpret()
    nq, l = q_lo.shape
    nr = r_lo.shape[0]
    if nq == 0 or nr == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    check_lane_capacity(l)
    _require_int32(q_lo, q_hi, r_lo, r_hi)
    mask = range_join_mask(
        jnp.asarray(_pack_boxes(q_lo, q_hi, l)),
        jnp.asarray(_pack_boxes(r_lo, r_hi, l)),
        n_attrs=l,
        block_q=block_q,
        block_r=block_r,
        interpret=interpret,
    )
    qi, ri = np.nonzero(np.asarray(mask))
    return qi.astype(np.int64), ri.astype(np.int64)


def _pad_packed_rows(p: np.ndarray, mult: int, n_attrs: int) -> np.ndarray:
    """Pad packed rows to a multiple of ``mult`` with empty boxes.

    The numpy twin of ``range_join._pad_empty``: padded rows carry
    ``lo = 1, hi = 0`` on every attribute lane, so they never overlap a
    padded row; real rows with coordinates spanning ``[≤0, ≥1]`` *can* still
    graze one, which is why tile extraction bounds-checks pairs against the
    segment's real row counts.
    """
    n = p.shape[0]
    pad = (-n) % mult
    if pad == 0:
        return p
    row = np.zeros(LANES, np.int32)
    row[:n_attrs] = 1
    return np.concatenate([p, np.tile(row, (pad, 1))], axis=0)


def _upload(*arrays: np.ndarray) -> tuple[list, int]:
    """``jnp.asarray`` each host array; returns the device arrays and the
    bytes they moved host to device."""
    return [jnp.asarray(a) for a in arrays], sum(a.nbytes for a in arrays)


def _blockdiag_pairs(
    segments: "list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]",
    n_attrs: int,
    block_q: int,
    block_r: int,
    interpret: bool,
    trace=None,
) -> tuple[list[tuple[np.ndarray, np.ndarray]], dict]:
    """Per-segment pairs via the tile-scheduled (block-diagonal) kernel.

    Each segment is packed and padded to block multiples *independently*
    (tiles never straddle segments, so no segment-id lane is spent), the
    diagonal tile schedule is enumerated on the host, and pair extraction
    runs on the ``[T, block_q, block_r]`` tile stack — host transfer scales
    with the diagonal, not the cross product.  Returns the per-segment
    pair lists plus ``rows_padded``, ``tiles_visited``, ``h2d_bytes``,
    ``d2h_bytes`` and ``pairs``.
    """
    n_segs = len(segments)
    with maybe_span(trace, "pack", kind="pack"):
        q_parts = [
            _pad_packed_rows(_pack_boxes(s[0], s[1], n_attrs), block_q, n_attrs)
            for s in segments
        ]
        r_parts = [
            _pad_packed_rows(_pack_boxes(s[2], s[3], n_attrs), block_r, n_attrs)
            for s in segments
        ]
        nqb = np.array([p.shape[0] // block_q for p in q_parts], np.int64)
        nrb = np.array([p.shape[0] // block_r for p in r_parts], np.int64)
        q_blk_off = np.concatenate([[0], np.cumsum(nqb)])
        r_blk_off = np.concatenate([[0], np.cumsum(nrb)])
        tile_start = np.concatenate([[0], np.cumsum(nqb * nrb)])
        n_tiles = int(tile_start[-1])
        rows_padded = int(
            sum(p.shape[0] for p in q_parts) + sum(p.shape[0] for p in r_parts)
        )
        empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
        if n_tiles == 0:
            moved = {"rows_padded": 0, "tiles_visited": 0,
                     "h2d_bytes": 0, "d2h_bytes": 0, "pairs": 0}
            return [empty for _ in segments], moved
        # the diagonal schedule: segment-major, q-block outer / r-block inner
        tile_q = np.concatenate(
            [q_blk_off[s] + np.repeat(np.arange(nqb[s]), nrb[s])
             for s in range(n_segs)]
        )
        tile_r = np.concatenate(
            [r_blk_off[s] + np.tile(np.arange(nrb[s]), int(nqb[s]))
             for s in range(n_segs)]
        )
        q_packed = np.concatenate(q_parts, axis=0)
        r_packed = np.concatenate(r_parts, axis=0)
    with maybe_span(trace, "dispatch", kind="dispatch") as sp:
        (qd, rd, tqd, trd), h2d = _upload(
            q_packed,
            r_packed,
            # dslint: ignore[int32-cast] block indices, bounded by row count/block
            tile_q.astype(np.int32),
            # dslint: ignore[int32-cast] block indices, bounded by row count/block
            tile_r.astype(np.int32),
        )
        masks = range_join_tile_masks(
            qd, rd, tqd, trd,
            n_attrs=n_attrs,
            block_q=block_q,
            block_r=block_r,
            interpret=interpret,
        )
        sp.attrs["h2d_bytes"] = h2d
    with maybe_span(trace, "readback", kind="readback") as sp:
        host_masks = np.asarray(masks)
        sp.attrs["d2h_bytes"] = host_masks.nbytes
    with maybe_span(trace, "pair_extract", kind="pair_extract") as sp:
        flat = np.flatnonzero(host_masks)
        t, rem = np.divmod(flat, block_q * block_r)
        lq, lr = np.divmod(rem, block_r)
        qi_pad = tile_q[t] * block_q + lq  # global padded-row coordinates
        ri_pad = tile_r[t] * block_r + lr
        # tiles are segment-grouped and flatnonzero is tile-major, so one cut
        # per segment recovers the per-join slices
        cuts = np.searchsorted(t, tile_start[1:-1])
        out = []
        for s, (qs, rs) in enumerate(
            zip(np.split(qi_pad, cuts), np.split(ri_pad, cuts))
        ):
            qi = qs - q_blk_off[s] * block_q
            ri = rs - r_blk_off[s] * block_r
            keep = (qi < segments[s][0].shape[0]) & (ri < segments[s][2].shape[0])
            if not keep.all():
                qi, ri = qi[keep], ri[keep]
            if nrb[s] > 1:
                # tiles run r-block inner, so segments spanning several r
                # blocks need a row-major resort to match the dense oracle's
                # pair order
                order = np.lexsort((ri, qi))
                qi, ri = qi[order], ri[order]
            out.append(
                (qi.astype(np.int64, copy=False), ri.astype(np.int64, copy=False))
            )
        n_pairs = sum(qi.size for qi, _ in out)
        sp.attrs["pairs"] = n_pairs
    moved = {"rows_padded": rows_padded, "tiles_visited": n_tiles,
             "h2d_bytes": h2d, "d2h_bytes": host_masks.nbytes, "pairs": n_pairs}
    return out, moved


def segmented_range_join_pairs(
    segments: "list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]",
    block_q: int = 256,
    block_r: int = 256,
    interpret: bool | None = None,
    layout: str = "auto",
    trace=None,
) -> tuple[list[tuple[np.ndarray, np.ndarray]], dict]:
    """Many independent range joins in **one** kernel launch.

    ``segments`` is a list of ``(q_lo, q_hi, r_lo, r_hi)`` joins; attribute
    widths are padded to the widest segment (spare attributes carry
    ``lo = hi = 0`` on both sides, never filtering).  Two launch layouts:

    * ``"dense"`` — one masked ``[NQ, 128] × [NR, 128]`` cross-product
      launch; with more than one segment, a spare-lane attribute holds the
      *segment id* with ``lo = hi = segment`` so rows only match within
      their own join (a single segment skips the lane).  The correctness
      oracle, and the cheaper plan for single-segment or tiny frontiers
      where per-segment padding would cost more than the cross product.
    * ``"blockdiag"`` — the tile-scheduled kernel
      (:func:`repro.kernels.range_join.range_join_tile_masks`): only the
      ~K diagonal tiles of a K-segment frontier are visited, and the host
      reads back the tile stack instead of the full cross-product mask.

    ``layout="auto"`` charges both schedules in tiles and picks the
    cheaper.  Returns the per-segment ``(qi, ri)`` pair lists (row-major
    order, bit-identical between layouts and to a per-segment dense
    evaluation) plus occupancy info for ``io_stats``: ``tiles_visited`` is
    the executed schedule, ``tiles_skipped`` the cross-product tiles the
    block-diagonal schedule avoided, ``h2d_bytes`` the packed operands and
    tile schedule uploaded, ``d2h_bytes`` the mask or tile stack read
    back, ``pairs`` the pairs returned.

    ``trace`` (a :class:`~repro.obs.trace.QueryTrace`, or None) records the
    host stages as ``pack``, ``dispatch`` (uploads plus the kernel call),
    ``readback`` (the device wait and the copy to the host) and
    ``pair_extract`` spans.
    """
    if interpret is None:
        interpret = default_interpret()
    geometry = (block_q, block_r)
    if not segments:
        return [], {
            "rows": 0, "rows_padded": 0, "launches": 0, "layout": "dense",
            "geometry": geometry, "tiles_visited": 0, "tiles_skipped": 0,
            "h2d_bytes": 0, "d2h_bytes": 0, "pairs": 0,
        }
    if layout not in ("auto", "dense", "blockdiag"):
        raise ValueError(f"unknown launch layout {layout!r}")
    l_max = max(s[0].shape[1] for s in segments)
    for q_lo, q_hi, r_lo, r_hi in segments:
        _require_int32(q_lo, q_hi, r_lo, r_hi)
    nq_tot = sum(s[0].shape[0] for s in segments)
    nr_tot = sum(s[2].shape[0] for s in segments)
    rows = int(nq_tot + nr_tot)
    # tile bills for both schedules over the same segments: the masked
    # cross product pays the full grid, the diagonal pays per-segment
    # ceil-padded blocks — auto takes the cheaper, and the difference is
    # what io_stats reports as skipped
    cross_tiles = -(-nq_tot // block_q) * -(-nr_tot // block_r)
    diag_tiles = sum(
        -(-s[0].shape[0] // block_q) * -(-s[2].shape[0] // block_r)
        for s in segments
    )
    if layout == "auto":
        layout = (
            "blockdiag"
            if len(segments) > 1 and diag_tiles < cross_tiles
            else "dense"
        )
    if layout == "blockdiag":
        check_lane_capacity(l_max)  # no segment lane: tiles never cross segments
        out, moved = _blockdiag_pairs(
            segments, l_max, block_q, block_r, interpret, trace
        )
        return out, {
            "rows": rows,
            "launches": 1,
            "layout": "blockdiag",
            "geometry": geometry,
            "tiles_skipped": max(0, int(cross_tiles - moved["tiles_visited"])),
            **moved,
        }

    # masked dense cross-product launch
    segmented = len(segments) > 1
    n_attrs = l_max + (1 if segmented else 0)  # + segment-id lane pair
    check_lane_capacity(l_max, segmented=segmented)

    def pack_side(arrs: list[tuple[np.ndarray, np.ndarray]]) -> np.ndarray:
        parts = []
        for seg, (lo, hi) in enumerate(arrs):
            p = _pack_boxes(lo, hi, n_attrs)
            if segmented:
                p[:, l_max] = seg  # segment id: lo = hi = seg
                p[:, n_attrs + l_max] = seg
            parts.append(p)
        return np.concatenate(parts, axis=0)

    with maybe_span(trace, "pack", kind="pack"):
        qp = pack_side([(s[0], s[1]) for s in segments])
        rp = pack_side([(s[2], s[3]) for s in segments])
        q_off = np.cumsum([0] + [s[0].shape[0] for s in segments])
        r_off = np.cumsum([0] + [s[2].shape[0] for s in segments])
    with maybe_span(trace, "dispatch", kind="dispatch") as sp:
        (qd, rd), h2d = _upload(qp, rp)
        mask = range_join_mask(
            qd,
            rd,
            n_attrs=n_attrs,
            block_q=block_q,
            block_r=block_r,
            interpret=interpret,
        )
        sp.attrs["h2d_bytes"] = h2d
    with maybe_span(trace, "readback", kind="readback") as sp:
        host_mask = np.asarray(mask)
        sp.attrs["d2h_bytes"] = host_mask.nbytes
    with maybe_span(trace, "pair_extract", kind="pair_extract") as sp:
        qi, ri = np.nonzero(host_mask)
        # pairs are qi-major and the segment lane confines ri to the
        # segment's own column range, so one cut per segment recovers the
        # per-join lists
        cuts = np.searchsorted(qi, q_off[1:-1])
        out = []
        for seg, (qs, rs) in enumerate(
            zip(np.split(qi, cuts), np.split(ri, cuts))
        ):
            out.append(
                (
                    (qs - q_off[seg]).astype(np.int64),
                    (rs - r_off[seg]).astype(np.int64),
                )
            )
        sp.attrs["pairs"] = qi.size
    rows_padded = int(
        -(-qp.shape[0] // block_q) * block_q + -(-rp.shape[0] // block_r) * block_r
    )
    return out, {
        "rows": rows,
        "rows_padded": rows_padded,
        "launches": 1,
        "layout": "dense",
        "geometry": geometry,
        "tiles_visited": int(cross_tiles),
        "tiles_skipped": 0,
        "h2d_bytes": h2d,
        "d2h_bytes": host_mask.nbytes,
        "pairs": int(qi.size),
    }
