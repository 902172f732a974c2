"""The range-join kernels' share of their memory roofline.

Bytes that no schedule can avoid, over what HBM moves in the kernels' device
time: the packed operands and tile schedules uploaded (the ``h2d_bytes``
counter; the kernels read them) and the int32 mask cells they write (4 x
the ``mask_cells_written`` counter), over the window's
``jit_range_join_mask`` and ``jit_range_join_tile_masks`` module time times
the chip's HBM bandwidth.  The kernels move at least these bytes, so the
share is never above the true one.  A program without the counter, or a
trace without the modules, reports nothing; a chip missing from the table
is an error.
"""

# HBM bytes per second by device kind (Google Cloud documentation, "TPU v5e":
# 16 GB of HBM at 819 GB/s)
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9, "TPU v5e": 819e9}


def read(ctx):
    if ctx.device is None or "mask_cells_written" not in ctx.counters:
        return None
    s = ctx.device.kernel_s("jit_range_join_mask", "jit_range_join_tile_masks")
    if s <= 0:
        return None
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in HBM_BYTES_PER_S:
        raise ValueError(f"no HBM bandwidth on record for {kind!r}")
    moved = ctx.counters.get("h2d_bytes", 0) + 4 * ctx.counters["mask_cells_written"]
    return 100.0 * moved / (s * HBM_BYTES_PER_S[kind])
