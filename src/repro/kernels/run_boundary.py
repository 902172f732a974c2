"""Pallas TPU kernel: fused multi-column run-boundary detection.

This is the O(N) hot pass inside every ProvRC range-encoding step (paper
§IV.A): given rows *already sorted* by their group key, emit ``1`` where a
new run starts — i.e. where any group-key column changes, or the merge
column stops being contiguous (``lo[t] > hi[t-1] + 1``).

TPU adaptation (vs. the paper's scalar Python scan): the scan has no loop
dependence once the previous row is available, so we tile rows into VMEM
blocks of ``(block_rows, 128)`` int32 and compare each block against itself
shifted by one row.  The single cross-tile dependency (the last row of the
previous tile) is precomputed as a tiny side input — one 8-row block per
tile with the tail in its first row, an O(N / block_rows) strided gather
done once by XLA — so the kernel reads every element of the sorted table
exactly once from HBM.  The column axis is padded to the 128-lane width;
group-key columns and the two merge-interval columns travel in the same
tile so the whole boundary predicate fuses into one VMEM pass (numpy needs
C+2 separate comparison sweeps).

Layout:  ``packed[:, :n_keys]`` = group-key columns,
         ``packed[:, n_keys]`` = merge ``lo``, ``packed[:, n_keys+1]`` =
         merge ``hi``; remaining lanes are zero padding.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .range_join import LANES, SUBLANES


def _kernel(packed_ref, prev_ref, out_ref, *, n_keys: int):
    """One row-tile: boundary flags for rows [i*T, (i+1)*T)."""
    block = packed_ref[...]  # [T, LANES] int32
    prev_tail = prev_ref[0:1, :]  # [1, LANES]  last row of previous tile
    # previous-row view: shift block down by one, filling row 0 from the tail
    prev_rows = jnp.concatenate([prev_tail, block[:-1, :]], axis=0)

    key_mask = (jax.lax.iota(jnp.int32, LANES) < n_keys)[None, :]
    diff = (block != prev_rows) & key_mask
    key_change = jnp.any(diff, axis=1)

    lo = block[:, n_keys]
    prev_hi = prev_rows[:, n_keys + 1]
    not_adjacent = lo > prev_hi + 1

    # dslint: ignore[int32-cast] bool flags
    out_ref[...] = (key_change | not_adjacent).astype(jnp.int32)[:, None]


@functools.partial(jax.jit, static_argnames=("n_keys", "block_rows", "interpret"))
def run_boundaries_packed(
    packed: jax.Array,
    *,
    n_keys: int,
    block_rows: int = 1024,
    interpret: bool = True,
) -> jax.Array:
    """Boundary flags for a packed ``[N, 128]`` int32 sorted table.

    Any row count: rows are padded internally to the block grid with copies
    of the last row (identical rows never start a run, so padded flags are
    0) and the returned flags are sliced back to ``N``.  Row 0 is always a
    boundary — tile 0's previous-row sentinel differs from every real row.
    """
    n, lanes = packed.shape
    assert lanes == LANES, f"pack columns to {LANES} lanes"
    if n == 0:
        return jnp.zeros((0,), jnp.int32)
    pad = (-n) % block_rows
    if pad:
        packed = jnp.concatenate(
            [packed, jnp.tile(packed[-1:], (pad, 1))], axis=0
        )
    num_tiles = (n + pad) // block_rows

    # Last row of the previous tile for each tile; tile 0 gets a sentinel
    # row that can never equal a real row (forces a boundary at row 0).
    tails = packed[block_rows - 1 :: block_rows][:-1]
    sentinel = jnp.full((1, LANES), jnp.iinfo(jnp.int32).min, jnp.int32)
    prev = jnp.concatenate([sentinel, tails], axis=0)  # [num_tiles, LANES]
    # each tail heads its own 8-row block: a block's second-minor dim must
    # be a multiple of the 8-row sublane tile (or span the whole array)
    prev = jnp.pad(prev[:, None, :], ((0, 0), (0, SUBLANES - 1), (0, 0)))
    prev = prev.reshape(num_tiles * SUBLANES, LANES)

    flags = pl.pallas_call(
        functools.partial(_kernel, n_keys=n_keys),
        grid=(num_tiles,),
        in_specs=[
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, 1), jnp.int32),
        interpret=interpret,
    )(packed, prev)
    return flags[:n, 0]
