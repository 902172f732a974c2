"""Pallas TPU kernel: blocked multi-attribute interval-overlap join.

The range join of the paper's θ-join (§V.B.1): for query boxes ``Q`` and
compressed-table key boxes ``R``, emit the boolean matrix
``mask[q, r] = ∧_j  [q.lo_j, q.hi_j] ∩ [r.lo_j, r.hi_j] ≠ ∅``.

TPU adaptation: this is an all-pairs predicate with the same data-movement
shape as an attention-score block — we tile ``Q`` rows × ``R`` rows into
VMEM blocks and evaluate the conjunction over attributes in registers,
8 ``Q`` rows at a time, so each (q, r) tile pair is materialized once in
VMEM and never round-trips through HBM.  ``Q`` carries its attributes in
the lane dimension; ``R`` reaches the kernel transposed, attributes in the
sublane dimension, so attribute ``j`` is a ``Q`` column against an ``R``
row and both broadcast to the tile without a relayout.

Inputs are packed ``[N, 2*l]`` int32 (lo columns then hi columns), padded to
128 lanes; the wrappers transpose ``R``'s used lanes on the device.  The
mask output block is ``(block_q, block_r)`` int32.  Row
counts need **not** be multiples of the block sizes: the kernel pads both
operands internally with *empty* boxes (``lo = 1, hi = 0`` — they overlap
nothing) and slices the padding back off the mask, so callers hand in
natural row counts.

Batched (multi-join) invocations come in two launch layouts (see
``repro.kernels.ops.segmented_range_join_pairs``):

* **masked dense** — all segments packed into one ``[NQ, 128] × [NR, 128]``
  cross-product launch with a *segment id* in a spare lane as one more
  interval attribute (``lo = hi = segment``): two rows overlap on that
  attribute iff they belong to the same segment, so the masks stay
  separable.  Simple and the correctness oracle, but a K-segment frontier
  evaluates K² tile blocks for K blocks of useful work.
* **block-diagonal** (:func:`range_join_tile_masks`) — a scalar-prefetch
  grid over an explicit per-tile ``(q_block, r_block)`` schedule.  The host
  enumerates only the tiles on the segment diagonal; the kernel's
  ``BlockSpec`` index maps read the prefetched tile offsets, so off-diagonal
  tiles are never visited and the output (``[T, block_q, block_r]``) scales
  with the diagonal, not the cross product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8  # rows of one int32 vreg tile


def check_lane_capacity(n_attrs: int, segmented: bool = False) -> None:
    """Raise when ``n_attrs`` interval attributes cannot fit one tile.

    Each attribute needs a lo and a hi lane; a segmented (batched) launch
    additionally spends one attribute on the segment id.  Beyond this the
    dense route must run on the numpy path — callers that want the silent
    fallback check before packing, so reaching the kernel over-capacity is
    a hard error, not a degradation.
    """
    total = n_attrs + (1 if segmented else 0)
    if 2 * total > LANES:
        raise ValueError(
            f"range_join_mask lane capacity exceeded: {n_attrs} attributes"
            f"{' + 1 segment lane' if segmented else ''} need {2 * total} "
            f"lanes but one tile has {LANES}; route this join to the numpy "
            f"dense path instead"
        )


def _check_block_q(block_q: int) -> None:
    if block_q % SUBLANES:
        raise ValueError(
            f"block_q={block_q} must be a multiple of the {SUBLANES}-row chunk"
        )


def _overlap_rows(q_ref, rt_ref, r0, n_attrs: int) -> jax.Array:
    """The conjunction over attributes for ``SUBLANES`` Q rows × the R block.

    ``q_ref`` is a ``[TQ, LANES]`` row block and ``rt_ref`` a transposed
    ``[R_SUBLANES, TR]`` block, so attribute ``j`` is a width-1 column of Q
    and a height-1 row of R: both broadcast to ``[SUBLANES, TR]`` without
    a lane ↔ sublane relayout.  (A 1-D lane column reshaped to ``[:, None]``
    needs one, and Mosaic spills it to VMEM — tens of MB at 256×256.)  The
    chunk keeps every temporary a few vregs wide whatever the tile or the
    attribute count.
    """
    q = q_ref[pl.ds(r0, SUBLANES), :]  # [SUBLANES, LANES]
    ok = None
    for j in range(n_attrs):  # static unroll over attributes
        q_lo = q[:, j : j + 1]  # [SUBLANES, 1]
        q_hi = q[:, n_attrs + j : n_attrs + j + 1]
        r_lo = rt_ref[j : j + 1, :]  # [1, TR]
        r_hi = rt_ref[n_attrs + j : n_attrs + j + 1, :]
        hit = (q_lo <= r_hi) & (r_lo <= q_hi)
        ok = hit if ok is None else ok & hit
    return ok.astype(jnp.int32)  # dslint: ignore[int32-cast] bool mask


def _fill_mask(q_ref, rt_ref, out_ref, *, n_attrs: int) -> None:
    """Write the ``[TQ, TR]`` tile mask into ``out_ref``, chunk by chunk."""

    def body(c, carry):
        r0 = pl.multiple_of(c * SUBLANES, SUBLANES)
        rows = _overlap_rows(q_ref, rt_ref, r0, n_attrs)
        out_ref[pl.ds(r0, SUBLANES), :] = rows
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0] // SUBLANES, body, 0)


def _transpose_r(r_packed: jax.Array, n_attrs: int) -> jax.Array:
    """``[NR, LANES]`` → ``[R_SUBLANES, NR]``: R's lo/hi lanes as rows.

    Only the ``2 * n_attrs`` used lanes travel, rounded up to the 8-row
    sublane tile; the block then spans the whole first axis, which is
    always a legal block shape.
    """
    rows = -(-2 * n_attrs // SUBLANES) * SUBLANES
    return r_packed[:, :rows].T


def _pad_empty(packed: jax.Array, n: int, mult: int, n_attrs: int) -> jax.Array:
    """Pad rows to a multiple of ``mult`` with empty boxes (lo=1, hi=0)."""
    pad = (-n) % mult
    if pad == 0:
        return packed
    lane = jnp.arange(LANES)
    # dslint: ignore[int32-cast] constant 0/1 row, hi lanes stay 0
    row = jnp.where(lane < n_attrs, 1, 0).astype(jnp.int32)
    return jnp.concatenate([packed, jnp.tile(row, (pad, 1))], axis=0)


@functools.partial(
    jax.jit, static_argnames=("n_attrs", "block_q", "block_r", "interpret")
)
def range_join_mask(
    q_packed: jax.Array,
    r_packed: jax.Array,
    *,
    n_attrs: int,
    block_q: int = 256,
    block_r: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Overlap mask for padded ``[NQ, 128]`` × ``[NR, 128]`` int32 boxes.

    Arbitrary row counts: operands are padded internally to the block grid
    with empty boxes and the returned mask is sliced back to ``[NQ, NR]``.
    """
    check_lane_capacity(n_attrs)
    _check_block_q(block_q)
    nq, lanes = q_packed.shape
    nr, lanes_r = r_packed.shape
    if lanes != LANES or lanes_r != LANES:
        raise ValueError(f"operands must be packed to {LANES} lanes")
    qp = _pad_empty(q_packed, nq, block_q, n_attrs)
    rt = _transpose_r(_pad_empty(r_packed, nr, block_r, n_attrs), n_attrs)
    grid = (qp.shape[0] // block_q, rt.shape[1] // block_r)
    mask = pl.pallas_call(
        functools.partial(_fill_mask, n_attrs=n_attrs),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((rt.shape[0], block_r), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_q, block_r), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp.shape[0], rt.shape[1]), jnp.int32),
        interpret=interpret,
    )(qp, rt)
    return mask[:nq, :nr]


def _tile_kernel(tq_ref, tr_ref, q_ref, rt_ref, out_ref, *, n_attrs: int):
    """One scheduled tile: the overlap conjunction for its (q, r) blocks.

    ``tq_ref``/``tr_ref`` are the prefetched tile schedules — consumed by
    the BlockSpec index maps, not the body, which sees exactly the operand
    blocks the schedule selected.
    """
    _fill_mask(q_ref, rt_ref, out_ref.at[0], n_attrs=n_attrs)


@functools.partial(
    jax.jit, static_argnames=("n_attrs", "block_q", "block_r", "interpret")
)
def range_join_tile_masks(
    q_packed: jax.Array,
    r_packed: jax.Array,
    tile_q: jax.Array,
    tile_r: jax.Array,
    *,
    n_attrs: int,
    block_q: int = 256,
    block_r: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """Overlap masks for an explicit tile schedule (block-diagonal launch).

    ``tile_q``/``tile_r`` are int32 ``[T]`` *block indices* into the packed
    operands (rows must already be multiples of the block sizes — the
    segmented packer pads each segment independently, which is what keeps a
    tile from straddling two segments).  Tile ``t`` evaluates q rows
    ``[tile_q[t]*block_q, ...)`` against r rows ``[tile_r[t]*block_r, ...)``
    and lands in ``out[t]``; tiles not in the schedule are never computed,
    so a K-segment frontier costs its diagonal (~K tiles), not the K² cross
    product.  The schedule rides scalar prefetch: it is available to the
    ``BlockSpec`` index maps before the body runs, so this is one launch,
    not T.
    """
    check_lane_capacity(n_attrs)
    _check_block_q(block_q)
    nq, lanes = q_packed.shape
    nr, lanes_r = r_packed.shape
    if lanes != LANES or lanes_r != LANES:
        raise ValueError(f"operands must be packed to {LANES} lanes")
    if nq % block_q or nr % block_r:
        raise ValueError(
            "tile-scheduled operands must be pre-padded to block multiples "
            f"(got {nq} q rows / {nr} r rows for {block_q}x{block_r} tiles)"
        )
    n_tiles = tile_q.shape[0]
    rt = _transpose_r(r_packed, n_attrs)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((block_q, LANES), lambda t, tq, tr: (tq[t], 0)),
            pl.BlockSpec((rt.shape[0], block_r), lambda t, tq, tr: (0, tr[t])),
        ],
        out_specs=pl.BlockSpec((1, block_q, block_r), lambda t, tq, tr: (t, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_tile_kernel, n_attrs=n_attrs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, block_q, block_r), jnp.int32),
        interpret=interpret,
    )(tile_q, tile_r, q_packed, rt)
