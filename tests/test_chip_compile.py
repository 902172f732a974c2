"""Compile the Pallas kernels for a described TPU v5e chip, without a chip.

The TPU compiler refuses what interpret mode accepts: unaligned blocks, and
tiles whose temporaries overflow the scoped VMEM limit.  These tests lower
and compile every kernel of the query and compression paths for one chip of
a ``v5e:2x2`` topology, at every launch geometry the autotuner may pick, and
check that the compiled program holds the Mosaic kernel.  Nothing runs.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the one that runs this
file loads the TPU compiler.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.autotune import CANDIDATE_GEOMETRIES, DEFAULT_GEOMETRY
from repro.kernels.range_join import range_join_mask, range_join_tile_masks
from repro.kernels.run_boundary import run_boundaries_packed

GEOMETRIES = sorted(set(CANDIDATE_GEOMETRIES) | {DEFAULT_GEOMETRY})


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _i32(sharding, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _assert_kernel(lowered):
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("n_attrs", [1, 2, 4, 8])
@pytest.mark.parametrize("geometry", GEOMETRIES, ids=lambda g: f"{g[0]}x{g[1]}")
def test_range_join_mask_compiles_for_v5e(one_chip, geometry, n_attrs):
    bq, br = geometry
    # ragged row counts: the wrapper's own padding is compiled too
    _assert_kernel(range_join_mask.lower(
        _i32(one_chip, 4000, 128), _i32(one_chip, 8000, 128),
        n_attrs=n_attrs, block_q=bq, block_r=br, interpret=False,
    ))


@pytest.mark.parametrize("n_attrs", [1, 2, 4, 8])
@pytest.mark.parametrize("geometry", GEOMETRIES, ids=lambda g: f"{g[0]}x{g[1]}")
def test_range_join_tile_masks_compiles_for_v5e(one_chip, geometry, n_attrs):
    bq, br = geometry
    _assert_kernel(range_join_tile_masks.lower(
        _i32(one_chip, 4096, 128), _i32(one_chip, 8192, 128),
        _i32(one_chip, 96), _i32(one_chip, 96),
        n_attrs=n_attrs, block_q=bq, block_r=br, interpret=False,
    ))


def test_range_join_mask_compiles_at_lane_capacity(one_chip):
    """The widest segmented pack (63 attributes + the segment lane)."""
    _assert_kernel(range_join_mask.lower(
        _i32(one_chip, 1024, 128), _i32(one_chip, 1024, 128),
        n_attrs=64, block_q=512, block_r=256, interpret=False,
    ))


@pytest.mark.parametrize("n_rows", [1024, 4096, 5000])
def test_run_boundaries_packed_compiles_for_v5e(one_chip, n_rows):
    """One tile, several tiles, and a padded tail at 1024-row blocks."""
    _assert_kernel(run_boundaries_packed.lower(
        _i32(one_chip, n_rows, 128), n_keys=3, block_rows=1024,
        interpret=False,
    ))
