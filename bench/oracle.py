"""The plain reference: lineage queries answered by joins over raw rows.

Each hop's rows are sorted once by the side a frontier arrives on; a query
then carries its frontier from array to array, finding every row whose key
cell is in the frontier (two binary searches per frontier cell) and taking
the other side's cells.  A query in path form follows its path; one in graph
form takes every hop between its two arrays, merging frontiers where paths
meet.  Nothing here imports the program under test or reads anything it
made: the rows come from :mod:`bench.workflows`.
"""

from __future__ import annotations

import numpy as np

from .workflows import pipeline_hops


class HopIndex:
    """One hop's rows sorted by the key side, built on first use."""

    def __init__(self, hop):
        self.hop = hop
        self._by = {}

    def join(self, frontier: np.ndarray, forward: bool) -> np.ndarray:
        """Sorted unique cells on the far side of the hop from ``frontier``."""
        if forward not in self._by:
            key = self.hop.in_flat if forward else self.hop.out_flat
            val = self.hop.out_flat if forward else self.hop.in_flat
            if key.size > 1 and np.any(key[1:] < key[:-1]):
                order = np.argsort(key, kind="stable")
                key, val = key[order], val[order]
            self._by[forward] = (key, val)
        key, val = self._by[forward]
        lo = np.searchsorted(key, frontier, side="left")
        hi = np.searchsorted(key, frontier, side="right")
        counts = hi - lo
        total = int(counts.sum())
        if total == 0:
            return np.zeros(0, np.int64)
        starts = np.repeat(lo - (np.cumsum(counts) - counts), counts)
        return np.unique(val[starts + np.arange(total)])


def propagate(hops: list[HopIndex], q) -> np.ndarray:
    """The answer to ``q`` (a :class:`bench.mix.Query`) over ``hops``, which
    are listed so that every array is written before it is read."""
    cur = {q.src_name: np.unique(np.asarray(q.cells_flat, np.int64))}
    if q.path:  # path form: hop by hop along the path
        for a, b in zip(q.path, q.path[1:]):
            parts = [h.join(cur[a], forward=True) for h in hops
                     if (h.hop.src, h.hop.dst) == (a, b)]
            parts += [h.join(cur[a], forward=False) for h in hops
                      if (h.hop.dst, h.hop.src) == (a, b)]
            cur[b] = np.unique(np.concatenate(parts))
        return cur[q.dst_name]
    order = hops if q.forward else hops[::-1]
    for h in order:
        a, b = (h.hop.src, h.hop.dst) if q.forward else (h.hop.dst, h.hop.src)
        if a in cur:
            got = h.join(cur[a], forward=q.forward)
            cur[b] = np.union1d(cur[b], got) if b in cur else got
    return cur.get(q.dst_name, np.zeros(0, np.int64))


def answer_queries(cfg: dict, queries: list, data_seed: int | None = None) -> list:
    """Reference answers, one sorted flat-cell array per query.

    Each pipeline's hops are generated once, and dropped before the next
    pipeline's.  A query that repeats an earlier one shares its answer.
    """
    out: list = [None] * len(queries)
    by_pipe: dict[str, list[int]] = {}
    for i, q in enumerate(queries):
        by_pipe.setdefault(q.pipe, []).append(i)
    for pipe in cfg["pipelines"]:
        todo = by_pipe.get(pipe["name"])
        if not todo:
            continue
        hops = [HopIndex(h) for h in pipeline_hops(cfg, pipe, data_seed)]
        seen: dict = {}
        for i in todo:
            q = queries[i]
            key = (tuple(q.path) or (q.src_name, q.dst_name),
                   np.asarray(q.cells_flat, np.int64).tobytes())
            if key not in seen:
                seen[key] = propagate(hops, q)
            out[i] = seen[key]
        del hops
    return out


def boxes_to_flat(lo: np.ndarray, hi: np.ndarray, shape) -> np.ndarray:
    """Sorted unique flat cells covered by inclusive boxes ``[lo, hi]``."""
    lo = np.asarray(lo, np.int64).reshape(-1, len(shape))
    hi = np.asarray(hi, np.int64).reshape(-1, len(shape))
    ext = np.maximum(hi - lo + 1, 0)
    sizes = np.prod(ext, axis=1)
    total = int(sizes.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    box = np.repeat(np.arange(lo.shape[0]), sizes)
    local = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(sizes) - sizes, sizes)
    coords = [None] * len(shape)
    for ax in range(len(shape) - 1, -1, -1):
        e = ext[box, ax]
        coords[ax] = lo[box, ax] + local % e
        local //= e
    return np.unique(np.ravel_multi_index(tuple(coords), tuple(shape)))
