"""The one traffic generator: reads a mix file and yields queries from a seed.

A mix file (``bench/traffic/<name>.json``) is data only.  It names the loop
that drives it (``"loop"``: a module of :mod:`bench.loops`) and, for a query
loop, lists *classes*.  Each class names

* ``pipelines``: a list, or ``"all"`` of the configuration's;
* ``src`` and ``dst``: ``"first"``, ``"last"``, or an array of the pipeline
  (``"a4"``), each possibly per pipeline (``{"image": "a4", ...}``);
* ``form``: ``"path"``, the call ``prov_query([src, ..., dst], cells)`` over
  the one array path between them, or ``"graph"``, the call
  ``prov_query(src, dst, cells)`` that the planner routes;
* ``cells``: a layout of :mod:`bench.layouts`;
* ``k``, a list of cell counts, or ``selectivity``, a list of shares of the
  source array's cells (``max(1, int(n * s))`` cells each).

The stream is cut into blocks: every block holds each (class, pipeline, k)
combination once, the first in the listed order and the rest in orders drawn
from the seed, and each query's cells are drawn from the seed too.  The
warm-up draws from a stream of its own (``part="warm"``).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

import numpy as np

from . import layouts
from .workflows import array_shapes, routes

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")
PARTS = ("window", "warm")


def load_mix(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


@dataclass
class Query:
    pipe: str
    src_name: str
    dst_name: str
    path: tuple  # the array path of a path-form query, else ()
    forward: bool
    k: int
    cells_flat: np.ndarray  # sorted flat ids in the source array
    src_shape: tuple

    @property
    def label(self) -> str:
        return f"{self.pipe}:{'fwd' if self.forward else 'bwd'}:{self.k}"

    def cells(self) -> np.ndarray:
        """``[k, ndim]`` cell indices, the form ``prov_query`` takes."""
        return np.stack(np.unravel_index(self.cells_flat, self.src_shape), axis=1)

    def args(self) -> tuple:
        """Positional arguments of the ``prov_query`` call."""
        if self.path:
            return list(self.path), self.cells()
        return self.src_name, self.dst_name, self.cells()


def _array(spec, pipe: dict, names: list) -> str:
    if isinstance(spec, dict):
        spec = spec[pipe["name"]]
    if spec == "first":
        return names[0]
    if spec == "last":
        return names[-1]
    return f"{pipe['name']}_{spec}"


class QueryStream:
    """The seed's queries, generated block by block on demand."""

    def __init__(self, cfg: dict, mix: dict, seed: int, part: str = "window"):
        self.rng = np.random.default_rng([seed % 2**63, PARTS.index(part)])
        pipes = {p["name"]: p for p in cfg["pipelines"]}
        self.combos = []
        for cls in mix["classes"]:
            names = list(pipes) if cls["pipelines"] == "all" else cls["pipelines"]
            for pname in names:
                pipe = pipes[pname]
                shapes = array_shapes(cfg, pipe)
                src = _array(cls["src"], pipe, list(shapes))
                dst = _array(cls["dst"], pipe, list(shapes))
                paths, forward = routes(cfg, pipe, src, dst)
                form = cls.get("form", "graph")
                if form == "path" and len(paths) != 1:
                    raise ValueError(f"{len(paths)} paths from {src} to {dst}")
                n = int(np.prod(shapes[src]))
                ks = cls.get("k") or [max(1, int(n * s)) for s in cls["selectivity"]]
                for k in ks:
                    self.combos.append(dict(
                        pipe=pname, src=src, dst=dst, forward=forward, k=int(k),
                        path=tuple(paths[0]) if form == "path" else (),
                        layout=layouts.get(cls["cells"]), cls=cls,
                        src_shape=tuple(shapes[src]),
                    ))
        self._queries: list[Query] = []

    def __len__(self) -> int:
        """Queries per block."""
        return len(self.combos)

    def __getitem__(self, i: int) -> Query:
        while len(self._queries) <= i:
            # the first block runs in the listed order: the planner's routes
            # depend on what it has measured and indexed so far, so every
            # seed's store meets its hops in the same order
            order = (np.arange(len(self.combos)) if not self._queries
                     else self.rng.permutation(len(self.combos)))
            for j in order:
                c = self.combos[int(j)]
                cells = c["layout"].draw(self.rng, c["src_shape"], c["k"], c["cls"])
                self._queries.append(Query(
                    c["pipe"], c["src"], c["dst"], c["path"], c["forward"], c["k"],
                    cells, c["src_shape"],
                ))
        return self._queries[i]
