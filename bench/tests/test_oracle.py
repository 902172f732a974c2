"""The plain reference agrees with the program's answers at a small size."""

import json

import numpy as np
import pytest

from bench import mix, oracle, store, workflows


def test_boxes_to_flat():
    lo = np.array([[0, 1], [2, 2], [3, 0]])
    hi = np.array([[0, 2], [3, 3], [3, 0]])
    got = oracle.boxes_to_flat(lo, hi, (4, 4))
    assert got.tolist() == [1, 2, 10, 11, 12, 14, 15]


def _check(tmp_path, cfg, m, blocks=2):
    from repro.core.catalog import DSLog

    store.build(cfg, str(tmp_path / "s"))
    log = DSLog.load(str(tmp_path / "s"))
    stream = mix.QueryStream(cfg, m, 2**31 + 5)
    queries = [stream[i] for i in range(blocks * len(stream))]
    want = oracle.answer_queries(cfg, queries)
    for q, w in zip(queries, want):
        res = log.prov_query(*q.args())
        assert np.array_equal(oracle.boxes_to_flat(res.lo, res.hi, res.shape), w), q.label
        assert w.size > 0
    return queries


@pytest.mark.parametrize("config,traffic,side", [
    ("fig89_numpy", "fig89_forward", 32),
    ("fig89_conv", "fig89_forward_to_maps", 48),
])
def test_oracle_matches_prov_query(tmp_path, config, traffic, side):
    cfg = workflows.load_config(config)
    cfg["side"] = side
    queries = _check(tmp_path, cfg, mix.load_mix(traffic))
    assert all(q.forward and q.path for q in queries)


def test_oracle_follows_a_fan_in_graph(tmp_path):
    """A diamond (two branches that meet again, as a residual block's
    shortcut does), queried both ways in graph form."""
    cfg = json.loads(json.dumps(workflows.load_config("fig89_conv")))
    cfg["side"] = 16
    cfg["pipelines"] = [{"name": "block", "ops": [
        {"op": "conv2d", "kernel": [3, 3]},
        {"op": "roll", "shift": 1, "axis": 0},
        {"op": "conv2d", "kernel": [3, 3], "from": "a0", "to": "a2"},
        {"op": "flip", "axis": 1, "from": "a2", "to": "a1"},
    ]}]
    with pytest.raises(ValueError, match="written after"):
        workflows.array_shapes(cfg, cfg["pipelines"][0])
    cfg["pipelines"][0]["ops"].pop()
    shapes = workflows.array_shapes(cfg, cfg["pipelines"][0])
    assert list(shapes) == ["block_a0", "block_a1", "block_a2"]
    m = {"loop": "closed", "classes": [
        {"pipelines": "all", "src": "first", "dst": "a2", "cells": "prefix", "k": [3, 40]},
        {"pipelines": "all", "src": "a2", "dst": "a0", "cells": "prefix", "k": [5, 30]},
    ]}
    queries = _check(tmp_path, cfg, m)
    assert {q.forward for q in queries} == {True, False}
    assert not any(q.path for q in queries)
    with pytest.raises(ValueError, match="2 paths"):
        mix.QueryStream(cfg, {"classes": [dict(m["classes"][0], form="path")]}, 1)
