"""The configurations' workflows as raw lineage rows, in plain numpy.

A configuration file (``bench/configs/<name>.json``) lists pipelines of
array operations.  Array ``k + 1`` of a pipeline is the output of its
operation ``k``, read from the operation before it; an operation may instead
name its input (``"from": "a2"``) or write into an array that already exists
(``"to": "a3"``), so fan-out and fan-in graphs are data too.  Each
operation's rows come from its module under :mod:`bench.ops`, from its shape
and arguments alone (sort also from seeded values).  Nothing here shares
code with the program under test, so it can serve as the reference.
"""

from __future__ import annotations

import json
import os
from collections import deque
from dataclasses import dataclass

import numpy as np

from . import ops

CONFIG_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "configs")


@dataclass
class Hop:
    """Raw lineage rows of one operation: ``dst[out_flat[i]] <- src[in_flat[i]]``."""

    op: str
    src: str
    dst: str
    in_shape: tuple[int, ...]
    out_shape: tuple[int, ...]
    out_flat: np.ndarray
    in_flat: np.ndarray

    @property
    def n_rows(self) -> int:
        return int(self.out_flat.size)

    def raw_bytes(self) -> int:
        """Bytes of the rows as int64 coordinates, one column per dimension."""
        return self.n_rows * (len(self.out_shape) + len(self.in_shape)) * 8


def load_config(name: str) -> dict:
    with open(os.path.join(CONFIG_DIR, f"{name}.json")) as f:
        cfg = json.load(f)
    if cfg.get("name") != name:
        raise ValueError(f"config file {name}.json names itself {cfg.get('name')!r}")
    return cfg


def _steps(cfg: dict, pipe: dict):
    """``([(k, spec, src, dst)], {array: shape})`` without generating rows;
    the shapes are in the order the arrays appear."""
    name = pipe["name"]
    side = int(cfg["side"])
    first = f"{name}_a0"
    shapes = {first: tuple(side for _ in range(int(pipe.get("ndim", 2))))}
    steps, last, read = [], first, set()
    for k, spec in enumerate(pipe["ops"]):
        src = f"{name}_{spec['from']}" if "from" in spec else last
        dst = f"{name}_{spec['to']}" if "to" in spec else f"{name}_a{k + 1}"
        out = tuple(ops.get(spec["op"]).out_shape(spec, shapes[src]))
        if shapes.setdefault(dst, out) != out:
            raise ValueError(f"{dst} gets shapes {shapes[dst]} and {out}")
        if dst in read:  # the reference relies on writes before reads
            raise ValueError(f"{dst} is written after an operation read it")
        read.add(src)
        steps.append((k, spec, src, dst))
        last = dst
    return steps, shapes


def array_shapes(cfg: dict, pipe: dict) -> dict[str, tuple[int, ...]]:
    """Every array of the pipeline and its shape, in the order they appear."""
    return _steps(cfg, pipe)[1]


def pipeline_hops(cfg: dict, pipe: dict, data_seed: int | None = None):
    """Yield the pipeline's hops in order, one at a time (they are large)."""
    steps, shapes = _steps(cfg, pipe)
    base = int(cfg.get("data_seed", 0)) if data_seed is None else int(data_seed)
    for k, spec, src, dst in steps:
        out_flat, in_flat = ops.get(spec["op"]).rows(spec, shapes[src], base * 1000 + k)
        yield Hop(spec.get("name", spec["op"]), src, dst, shapes[src], shapes[dst],
                  out_flat, in_flat)


def routes(cfg: dict, pipe: dict, src: str, dst: str) -> tuple[list[list[str]], bool]:
    """Every array path from ``src`` to ``dst``, and whether they run
    forward (downstream); raises where there is none."""
    steps, _ = _steps(cfg, pipe)
    for forward in (True, False):
        nxt: dict[str, list[str]] = {}
        for _, _, a, b in steps:
            u, v = (a, b) if forward else (b, a)
            nxt.setdefault(u, []).append(v)
        paths, todo = [], deque([[src]])
        while todo:
            p = todo.popleft()
            if p[-1] == dst:
                paths.append(p)
                continue
            todo.extend(p + [v] for v in nxt.get(p[-1], []))
        if paths:
            return paths, forward
    raise ValueError(f"no path from {src} to {dst}")
