"""The harness's own lineage rows equal the program's capture adapters."""

import numpy as np
import pytest

from bench import workflows
from repro.core import capture as C

SIDE = 48


def _pairs(out_flat, in_flat, n_in):
    return np.unique(np.asarray(out_flat, np.int64) * n_in + np.asarray(in_flat, np.int64))


def _capture(spec, shape, values_seed):
    op = spec["op"]
    if op == "elementwise":
        return C.identity_lineage(shape)
    if op == "reshape":
        return C.reshape_lineage(shape, (int(np.prod(shape)),))
    if op == "transpose":
        return C.transpose_lineage(shape, tuple(spec["perm"]))
    if op == "roll":
        return C.roll_lineage(shape, spec["shift"], spec["axis"])
    if op == "flip":
        return C.flip_lineage(shape, spec["axis"])
    if op == "sort":
        values = np.random.default_rng(values_seed).random(shape)
        return C.sort_lineage(values, axis=spec["axis"])
    if op == "slice":
        return C.slice_lineage(shape, (0,) * len(shape), shape, tuple(spec["step"]))
    if op == "reduce":
        return C.reduce_lineage(shape, tuple(spec["axes"]))
    if op == "conv2d":
        return C.conv2d_lineage(shape[0], shape[1], *spec["kernel"])
    raise AssertionError(op)


@pytest.mark.parametrize("config", ["fig89_numpy", "fig89_conv"])
def test_rows_match_capture(config):
    cfg = workflows.load_config(config)
    cfg["side"] = SIDE
    for pipe in cfg["pipelines"]:
        shapes = workflows.array_shapes(cfg, pipe)
        for k, (spec, hop) in enumerate(zip(pipe["ops"], workflows.pipeline_hops(cfg, pipe))):
            rel = _capture(spec, hop.in_shape, cfg["data_seed"] * 1000 + k)
            assert (hop.in_shape, hop.out_shape) == (rel.in_shape, rel.out_shape)
            assert (shapes[hop.src], shapes[hop.dst]) == (hop.in_shape, hop.out_shape)
            assert (hop.src, hop.dst) == (f"{pipe['name']}_a{k}", f"{pipe['name']}_a{k + 1}")
            n_in = int(np.prod(hop.in_shape))
            got = _pairs(hop.out_flat, hop.in_flat, n_in)
            want = _pairs(np.ravel_multi_index(rel.out_idx.T, rel.out_shape),
                          np.ravel_multi_index(rel.in_idx.T, rel.in_shape), n_in)
            assert np.array_equal(got, want), (config, pipe["name"], k, spec)
