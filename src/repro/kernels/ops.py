"""Jit'd public wrappers around the Pallas kernels.

These handle packing/padding from the natural numpy layouts used by
``repro.core`` into the 128-lane int32 tiles the kernels expect, and select
``interpret=True`` automatically when no TPU is attached, so the kernel
bodies are validated on CPU.

The packers are int32: coordinates outside the int32 range cannot ride the
kernel path (they would silently wrap — the bug this module now refuses).
``fits_int32`` is the gate callers use to route oversized joins to the
numpy dense path; handing out-of-range values to a packer raises.

Shape-stable launches (``segmented_range_join_pairs(stable=True)``, the
batched executor's mode): every operand dimension that follows the traffic —
the packed query rows and the tile count of a block-diagonal schedule — is
padded to a step of a fixed ladder (:func:`ladder_step`), and each launch
runs a program compiled once per shape and kept in :data:`PROGRAMS`, reusing
a built one wherever it holds the launch at bounded cost
(:meth:`LaunchPrograms.plan`), so the number of programs follows the store,
not the queries.  Padded rows are left zero (a real box that holds
coordinate 0 overlaps them) and the mask rows and tiles they give are cut
before pair extraction, so pair lists are bit-identical to an exact-shape
launch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.trace import maybe_span

from .autotune import ladder_step
from .range_join import (
    LANES,
    SUBLANES,
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
    "PROGRAMS",
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

    The non-segmented form of :func:`segmented_range_join_pairs`, which
    is what the query engine launches; this one is an oracle for tests and
    benchmarks.  Raises for joins the kernel cannot express faithfully (lane
    capacity, int32 overflow).
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


# --------------------------------------------------------------------------- #
# Launch shapes from a fixed ladder, and the programs built for them
# --------------------------------------------------------------------------- #
# bytes that cost a launch nothing next to its round trip: a new shape is
# built this much wider than its own, and every launch is charged this much
# on top of the bytes it moves when launches are compared
PAD_BUDGET = 128 << 10
# launches in a row that ran on built programs after which the table is
# settled, and a launch reuses any program that holds it at bounded cost
# rather than build one; a dense launch past every built shape then runs in
# at most MAX_CHUNKS launches of one
SETTLE_LAUNCHES = 64
MAX_CHUNKS = 2


def _demand(kind: str, block_q: int, block_r: int, nq: list, nr: list):
    """What a ``kind`` launch (``"mask"`` dense, ``"tiles"`` block-diagonal)
    of segments with ``nq`` query and ``nr`` table rows needs at a geometry:
    its packed table rows, the rows (and tiles) it must hold, and the units
    they step in.  A dense launch's query rows step in 8-row units (the
    kernel pads them on to its block inside the program)."""
    if kind == "mask":
        return sum(nr), (sum(nq),), (SUBLANES,)
    qb = [-(-n // block_q) for n in nq]
    rb = [-(-n // block_r) for n in nr]
    tiles = sum(a * b for a, b in zip(qb, rb))
    return sum(rb) * block_r, (sum(qb) * block_q, tiles), (block_q, 1)


def _moved(kind: str, block_q: int, block_r: int, r_rows: int, shape,
           chunks: int = 1) -> int:
    """Bytes a launch on ``shape`` moves between host and device: packed
    operands (and a tile schedule) up, the mask or tile stack back; a dense
    launch in ``chunks`` uploads its table once."""
    if kind == "mask":
        (q,) = shape
        return r_rows * LANES * 4 + chunks * q * (LANES + r_rows) * 4
    q, t = shape
    return (q + r_rows) * LANES * 4 + t * (8 + block_q * block_r * 4)


class LaunchPrograms:
    """The compiled programs of shape-stable launches, and the choice of
    which one runs a launch.

    A program is keyed by kernel (``"mask"`` or ``"tiles"``), attribute
    count, geometry, interpret mode and packed table rows, and built for
    one launch shape along the dimensions the traffic moves (query rows,
    tiles).  :meth:`plan` runs a launch on a built program of the caller's
    kernel and geometry that holds it within two ladder steps of its own
    shape, or within ``PAD_BUDGET`` bytes of it; else it builds one at the
    highest step whose padding stays within ``PAD_BUDGET``.

    Once ``SETTLE_LAUNCHES`` launches in a row have built nothing, the
    table is settled, and a launch runs on any built program that holds it
    — of either kernel, any geometry, as many table rows and attributes or
    more (padded rows are cut from the mask like padded query rows, padded
    attributes hold lo = hi = 0) — or on a dense one in up to
    ``MAX_CHUNKS`` chunks, wherever that moves at most twice the bytes of
    its own step, each launch charged ``PAD_BUDGET`` for its round trip:
    the caller's program first, then one launch before chunks, then the
    fewest bytes.  Where none does, a launch of several segments runs one
    launch per segment, and only a launch of one segment builds.  So a
    warm-up builds the shapes its queries need, and frontiers that later
    wander a little past them, onto another tuned geometry or onto another
    mix of tables build no program mid-traffic.

    Programs are compiled ahead of time and held here, so they live as
    long as the process whatever happens to JAX's own caches; the process
    holds one table (:data:`PROGRAMS`), as it holds one JAX cache, so a
    store opened after another runs the programs it built.
    """

    def __init__(self) -> None:
        from repro.core import _locks

        # guards the table alone (rank 76, a leaf): compiles run outside it,
        # and two threads that meet a new shape at once keep the first program
        self._lock = _locks.new_lock("ops._lock")
        self._built: dict[tuple, dict[tuple, object]] = {}
        self._since_build = 0  # launches since a program was last built

    def __len__(self) -> int:
        with self._lock:
            return sum(len(v) for v in self._built.values())

    def plan(
        self,
        kinds: tuple[str, ...],
        block_q: int,
        block_r: int,
        interpret: bool,
        nq: list,
        nr: list,
        n_attrs: int,
    ) -> "tuple[tuple, tuple[int, ...], int] | None":
        """``(key, shape, chunks)`` for a launch of segments with ``nq``
        query and ``nr`` table rows, ``n_attrs`` wide: ``kinds[0]`` at
        ``(block_q, block_r)`` is the caller's choice, any kind of
        ``kinds`` may stand in for it once the table is settled.  None where
        a settled table holds no program for several segments together:
        each then launches alone."""
        segmented = len(nq) > 1

        def attrs(kind: str) -> int:  # + a segment-id lane pair, dense only
            return n_attrs + (1 if kind == "mask" and segmented else 0)

        kind = kinds[0]
        r_rows, need, units = _demand(kind, block_q, block_r, nq, nr)
        pref = (kind, attrs(kind), block_q, block_r, interpret, r_rows)

        def step(up: int) -> tuple[int, ...]:
            return tuple(ladder_step(n, u, up) for n, u in zip(need, units))

        own = _moved(kind, block_q, block_r, r_rows, step(0))
        with self._lock:
            settled = self._since_build >= SETTLE_LAUNCHES
            self._since_build += 1
            built = [(k, tuple(v)) for k, v in self._built.items()
                     if k == pref or settled]
        best = None
        for key, shapes in built:
            k_kind, k_attrs, bq, br, k_interp, k_rows = key
            rows, k_need, _ = _demand(k_kind, bq, br, nq, nr)
            # a settled launch may pad its table rows too, and its
            # attributes with lo = hi = 0, which never filter
            fits = key == pref or (
                k_kind in kinds and k_interp == interpret and rows <= k_rows
                and k_attrs >= attrs(k_kind))
            if not fits:
                continue
            for shape in shapes:
                chunks = 1
                if any(x < n for x, n in zip(shape, k_need)):
                    if not settled or k_kind != "mask":
                        continue
                    chunks = -(-k_need[0] // shape[0])  # split by query rows
                    if chunks > MAX_CHUNKS:
                        continue
                moved = _moved(k_kind, bq, br, k_rows, shape, chunks)
                if settled:
                    ok = moved + chunks * PAD_BUDGET <= 2 * (own + PAD_BUDGET)
                else:
                    ok = moved - own <= PAD_BUDGET or all(
                        x <= c for x, c in zip(shape, step(2)))
                rank = (key != pref, chunks, moved)
                if ok and (best is None or rank < best[0]):
                    best = (rank, key, shape, chunks)
        if best is not None:
            return best[1:]
        if settled and segmented:
            return None
        up = 0
        while step(up + 1) != step(up) and (
            _moved(kind, block_q, block_r, r_rows, step(up + 1)) - own <= PAD_BUDGET
        ):
            up += 1
        return pref, step(up), 1

    def program(self, key: tuple, shape: tuple[int, ...], lower):
        """The program for ``shape`` under ``key``, compiling ``lower()``
        (a ``jax.stages.Lowered``) the first time."""
        with self._lock:
            exe = self._built.get(key, {}).get(shape)
        if exe is None:
            exe = lower().compile()
            with self._lock:
                exe = self._built.setdefault(key, {}).setdefault(shape, exe)
                self._since_build = 0
        return exe


PROGRAMS = LaunchPrograms()


def _i32(rows: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct((rows, LANES), jnp.int32)


def _blockdiag_pairs(
    segments: "list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]",
    n_attrs: int,
    block_q: int,
    block_r: int,
    interpret: bool,
    trace=None,
    launch: "tuple | None" = None,
) -> tuple[list[tuple[np.ndarray, np.ndarray]], dict]:
    """Per-segment pairs via the tile-scheduled (block-diagonal) kernel.

    Each segment is packed and padded to block multiples *independently*
    (tiles never straddle segments, so no segment-id lane is spent), the
    diagonal tile schedule is enumerated on the host, and pair extraction
    runs on the ``[T, block_q, block_r]`` tile stack — host transfer scales
    with the diagonal, not the cross product.  ``launch``, a planned
    ``(key, (rows, tiles))`` of :data:`PROGRAMS`, runs it on that program, the
    packed query rows and the tile count padded to its shape (zero rows;
    padded tiles point at block 0 and are cut before extraction).  Returns the
    per-segment pair lists plus ``rows_padded``, ``tiles_visited``,
    ``ladder_pad_rows``, ``mask_cells``, ``h2d_bytes``, ``d2h_bytes`` and
    ``pairs``.
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
        q_rows = int(sum(p.shape[0] for p in q_parts))
        r_rows = int(sum(p.shape[0] for p in r_parts))
        empty = (np.zeros(0, np.int64), np.zeros(0, np.int64))
        if n_tiles == 0:
            moved = {"rows_padded": 0, "tiles_visited": 0, "ladder_pad_rows": 0,
                     "mask_cells": 0, "h2d_bytes": 0, "d2h_bytes": 0, "pairs": 0}
            return [empty for _ in segments], moved
        key, (q_launch, t_launch) = launch or (None, (q_rows, n_tiles))
        r_launch = key[5] if key else r_rows
        # the diagonal schedule: segment-major, q-block outer / r-block inner
        tile_q = np.concatenate(
            [q_blk_off[s] + np.repeat(np.arange(nqb[s]), nrb[s])
             for s in range(n_segs)]
        )
        tile_r = np.concatenate(
            [r_blk_off[s] + np.tile(np.arange(nrb[s]), int(nqb[s]))
             for s in range(n_segs)]
        )
        q_packed = np.concatenate(
            q_parts + [np.zeros((q_launch - q_rows, LANES), np.int32)], axis=0
        )
        r_packed = np.concatenate(
            r_parts + [np.zeros((r_launch - r_rows, LANES), np.int32)], axis=0
        )
        sched = np.zeros((2, t_launch), np.int32)  # block indices: small
        sched[0, :n_tiles] = tile_q
        sched[1, :n_tiles] = tile_r
    with maybe_span(trace, "dispatch", kind="dispatch") as sp:
        (qd, rd, tqd, trd), h2d = _upload(q_packed, r_packed, sched[0], sched[1])
        kw = dict(n_attrs=n_attrs, block_q=block_q, block_r=block_r,
                  interpret=interpret)
        if key is not None:
            exe = PROGRAMS.program(key, (q_launch, t_launch), lambda: (
                range_join_tile_masks.lower(
                    _i32(q_launch), _i32(r_launch),
                    jax.ShapeDtypeStruct((t_launch,), jnp.int32),
                    jax.ShapeDtypeStruct((t_launch,), jnp.int32), **kw)))
            masks = exe(qd, rd, tqd, trd)
        else:
            masks = range_join_tile_masks(qd, rd, tqd, trd, **kw)
        sp.attrs["h2d_bytes"] = h2d
    with maybe_span(trace, "readback", kind="readback") as sp:
        host_masks = np.asarray(masks)
        sp.attrs["d2h_bytes"] = host_masks.nbytes
    with maybe_span(trace, "pair_extract", kind="pair_extract") as sp:
        flat = np.flatnonzero(host_masks[:n_tiles])
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
    moved = {"rows_padded": q_launch + r_launch, "tiles_visited": n_tiles,
             "ladder_pad_rows": q_launch - q_rows + r_launch - r_rows,
             "mask_cells": t_launch * block_q * block_r,
             "h2d_bytes": h2d, "d2h_bytes": host_masks.nbytes, "pairs": n_pairs}
    return out, moved


def _launch_each(segments, block_q, block_r, interpret, trace):
    """Each segment in a shape-stable launch of its own: the pair lists, and
    the launches' info summed (``layout`` ``"each"``, the caller's
    ``geometry``)."""
    out, info = [], {"layout": "each", "geometry": (block_q, block_r)}
    for seg in segments:
        pairs, one = segmented_range_join_pairs(
            [seg], block_q, block_r, interpret, trace=trace, stable=True)
        out += pairs
        for k, v in one.items():
            if k not in ("layout", "geometry"):
                info[k] = info.get(k, 0) + v
    return out, info


def segmented_range_join_pairs(
    segments: "list[tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]]",
    block_q: int = 256,
    block_r: int = 256,
    interpret: bool | None = None,
    layout: str = "auto",
    trace=None,
    stable: bool = False,
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

    ``stable=True`` launches on ladder shapes (the module docstring): the
    launch runs a program held in :data:`PROGRAMS`, which may be one built
    for the other layout or another geometry (``layout`` and ``geometry``
    report what ran; an explicit ``layout`` is kept), and a dense launch may
    run it in a few chunks of query rows (``launches``).  The packed query
    rows, and a block-diagonal schedule's tile count, are padded to the
    program's shape.  ``ladder_pad_rows`` counts the rows that padding added
    and ``mask_cells`` the mask cells the kernel wrote (its launched tiles
    times the tile size).

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
            "ladder_pad_rows": 0, "mask_cells": 0,
            "h2d_bytes": 0, "d2h_bytes": 0, "pairs": 0,
        }
    if layout not in ("auto", "dense", "blockdiag"):
        raise ValueError(f"unknown launch layout {layout!r}")
    l_max = max(s[0].shape[1] for s in segments)
    for q_lo, q_hi, r_lo, r_hi in segments:
        _require_int32(q_lo, q_hi, r_lo, r_hi)
    nq = [s[0].shape[0] for s in segments]
    nr = [s[2].shape[0] for s in segments]
    nq_tot, nr_tot = sum(nq), sum(nr)
    rows = int(nq_tot + nr_tot)

    def tile_bills(bq: int, br: int) -> tuple[int, int]:
        # tile bills for both schedules over the same segments: the masked
        # cross product pays the full grid, the diagonal pays per-segment
        # ceil-padded blocks — auto takes the cheaper, and the difference
        # is what io_stats reports as skipped
        cross = -(-nq_tot // bq) * -(-nr_tot // br)
        return cross, sum(-(-a // bq) * -(-b // br) for a, b in zip(nq, nr))

    cross_tiles, diag_tiles = tile_bills(block_q, block_r)
    kinds = {"dense": ("mask",), "blockdiag": ("tiles",)}.get(layout)
    if layout == "auto":
        blockdiag = len(segments) > 1 and diag_tiles < cross_tiles
        kinds = ("tiles", "mask") if blockdiag else ("mask", "tiles")
    launch, chunks = None, 1
    if stable:
        planned = PROGRAMS.plan(kinds, block_q, block_r, interpret, nq, nr, l_max)
        if planned is None:
            return _launch_each(segments, block_q, block_r, interpret, trace)
        key, shape, chunks = planned
        launch = (key, shape)
        if (key[2], key[3]) != geometry:  # another geometry's program stands in
            geometry = block_q, block_r = key[2], key[3]
            cross_tiles, _ = tile_bills(block_q, block_r)
    if (launch[0][0] if launch else kinds[0]) == "tiles":
        check_lane_capacity(l_max)  # no segment lane: tiles never cross segments
        out, moved = _blockdiag_pairs(
            segments, launch[0][1] if launch else l_max, block_q, block_r,
            interpret, trace, launch,
        )
        return out, {
            "rows": rows,
            "launches": 1,
            "layout": "blockdiag",
            "geometry": geometry,
            "tiles_skipped": max(0, int(cross_tiles - moved["tiles_visited"])),
            **moved,
        }

    # masked dense cross-product launch; a planned launch packs as many
    # attributes and table rows as its program takes
    segmented = len(segments) > 1
    check_lane_capacity(l_max, segmented=segmented)
    n_attrs = launch[0][1] if launch else l_max + segmented  # + segment-id lane
    r_launch = launch[0][5] if launch else nr_tot

    def pack_side(arrs: list[tuple[np.ndarray, np.ndarray]], rows: int) -> np.ndarray:
        """One ``[rows, 128]`` pack of every segment (the layout of
        :func:`_pack_boxes`), then zero rows up to ``rows``."""
        p = np.zeros((rows, LANES), np.int32)
        off = 0
        for seg, (lo, hi) in enumerate(arrs):
            n, l = lo.shape
            _require_int32(lo, hi)
            p[off : off + n, :l] = lo.astype(np.int32)
            p[off : off + n, n_attrs : n_attrs + l] = hi.astype(np.int32)
            if segmented:
                p[off : off + n, l_max] = seg  # segment id: lo = hi = seg
                p[off : off + n, n_attrs + l_max] = seg
            off += n
        return p

    # a planned launch runs its program once per chunk of q_launch query rows
    q_launch = launch[1][0] if launch else nq_tot
    with maybe_span(trace, "pack", kind="pack"):
        qp = pack_side([(s[0], s[1]) for s in segments], chunks * q_launch)
        rp = pack_side([(s[2], s[3]) for s in segments], r_launch)
        q_off = np.cumsum([0] + nq)
        r_off = np.cumsum([0] + nr)
    with maybe_span(trace, "dispatch", kind="dispatch") as sp:
        # chunks are cut on the host: a slice of a device array is a program
        (rd, *qds), h2d = _upload(rp, *np.split(qp, chunks))
        kw = dict(n_attrs=n_attrs, block_q=block_q, block_r=block_r,
                  interpret=interpret)
        if launch:
            exe = PROGRAMS.program(launch[0], launch[1], lambda: (
                range_join_mask.lower(_i32(q_launch), _i32(r_launch), **kw)))
            masks = [exe(qd, rd) for qd in qds]
        else:
            masks = [range_join_mask(qds[0], rd, **kw)]
        sp.attrs["h2d_bytes"] = h2d
    with maybe_span(trace, "readback", kind="readback") as sp:
        host_mask = np.concatenate([np.asarray(m) for m in masks], axis=0)
        sp.attrs["d2h_bytes"] = host_mask.nbytes
    with maybe_span(trace, "pair_extract", kind="pair_extract") as sp:
        # rows past the frontier or the table are zero padding: cut them
        qi, ri = np.nonzero(host_mask[:nq_tot, :nr_tot])
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
    q_blocks, r_blocks = -(-q_launch // block_q), -(-r_launch // block_r)
    return out, {
        "rows": rows,
        "rows_padded": int(chunks * q_blocks * block_q + r_blocks * block_r),
        "launches": chunks,
        "layout": "dense",
        "geometry": geometry,
        "tiles_visited": int(cross_tiles),
        "tiles_skipped": 0,
        "ladder_pad_rows": int(chunks * q_launch - nq_tot + r_launch - nr_tot),
        "mask_cells": int(chunks * q_blocks * r_blocks * block_q * block_r),
        "h2d_bytes": h2d,
        "d2h_bytes": host_mask.nbytes,
        "pairs": int(qi.size),
    }
