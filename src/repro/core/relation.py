"""Relational data model for fine-grained array lineage (paper §III.B).

A :class:`LineageRelation` is the uncompressed relation
``R(b_1..b_l, a_1..a_m)`` between an *output* array ``B`` and an *input*
array ``A``: one row per contribution ``B[b...] <- A[a...]``.  Rows are
unique (set semantics), which is what makes the UCP argument of the paper's
correctness proof go through.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import numpy as np

from .intervals import unique_rows

__all__ = ["LineageRelation", "axis_names"]


def axis_names(prefix: str, ndim: int) -> tuple[str, ...]:
    return tuple(f"{prefix}{i}" for i in range(ndim))


@dataclass
class LineageRelation:
    """Uncompressed lineage rows between one output and one input array."""

    out_shape: tuple[int, ...]
    in_shape: tuple[int, ...]
    # int64 [N, l] and [N, m]; row i means out_idx[i] <- in_idx[i].
    out_idx: np.ndarray = field(repr=False)
    in_idx: np.ndarray = field(repr=False)
    out_attrs: tuple[str, ...] = ()
    in_attrs: tuple[str, ...] = ()

    def __post_init__(self) -> None:
        self.out_idx = _as_rows(self.out_idx, len(self.out_shape))
        self.in_idx = _as_rows(self.in_idx, len(self.in_shape))
        if self.out_idx.shape[0] != self.in_idx.shape[0]:
            raise ValueError("out_idx and in_idx row counts differ")
        if not self.out_attrs:
            self.out_attrs = axis_names("b", len(self.out_shape))
        if not self.in_attrs:
            self.in_attrs = axis_names("a", len(self.in_shape))

    # ------------------------------------------------------------------ #
    @property
    def n_rows(self) -> int:
        return int(self.out_idx.shape[0])

    @property
    def ndim_out(self) -> int:
        return len(self.out_shape)

    @property
    def ndim_in(self) -> int:
        return len(self.in_shape)

    def rows(self) -> np.ndarray:
        """All columns side by side: ``[b_1..b_l, a_1..a_m]``."""
        return np.concatenate([self.out_idx, self.in_idx], axis=1)

    def nbytes_raw(self) -> int:
        """Size of the row-oriented int64 materialization (the Raw baseline)."""
        return self.rows().nbytes

    # ------------------------------------------------------------------ #
    def canonical(self) -> "LineageRelation":
        """Sorted + deduplicated copy (set semantics): the rows of
        ``np.unique(self.rows(), axis=0)``, byte for byte."""
        return self.canonical_route()[0]

    def canonical_route(self) -> tuple["LineageRelation", str]:
        """:meth:`canonical` and the route the dedup took.

        Each row is packed into one int64 key, the C-order ravel of
        ``(out..., in...)`` over ``out_shape + in_shape``, whose order is
        the rows' lexicographic order.  ``presorted``: the keys were already
        strictly increasing (capture emits rows in output order), so the
        rows are copied as they are.  ``packed``: the unique keys are
        unravelled back into rows.  ``lexsort``: an index outside its dim,
        or a shape of 2**63 cells or more, leaves no key; the rows are
        deduplicated by a lexsort of their columns instead.
        """
        out_idx, in_idx, route = _canonical_rows(
            self.out_idx, self.in_idx, self.out_shape + self.in_shape
        )
        rel = LineageRelation(
            self.out_shape,
            self.in_shape,
            out_idx,
            in_idx,
            self.out_attrs,
            self.in_attrs,
        )
        return rel, route

    def as_set(self) -> set[tuple[int, ...]]:
        return {tuple(int(v) for v in row) for row in self.rows()}

    def __eq__(self, other: object) -> bool:  # type: ignore[override]
        if not isinstance(other, LineageRelation):
            return NotImplemented
        if self.out_shape != other.out_shape or self.in_shape != other.in_shape:
            return False
        a, b = self.canonical(), other.canonical()
        return bool(
            np.array_equal(a.out_idx, b.out_idx)
            and np.array_equal(a.in_idx, b.in_idx)
        )

    # ------------------------------------------------------------------ #
    @staticmethod
    def from_pairs(
        out_shape: tuple[int, ...],
        in_shape: tuple[int, ...],
        pairs: "np.ndarray | list[tuple[tuple[int, ...], tuple[int, ...]]]",
    ) -> "LineageRelation":
        """Build from explicit ``(out_idx_tuple, in_idx_tuple)`` pairs."""
        if isinstance(pairs, np.ndarray):
            l = len(out_shape)
            return LineageRelation(out_shape, in_shape, pairs[:, :l], pairs[:, l:])
        out_rows = np.array([p[0] for p in pairs], dtype=np.int64).reshape(
            len(pairs), len(out_shape)
        )
        in_rows = np.array([p[1] for p in pairs], dtype=np.int64).reshape(
            len(pairs), len(in_shape)
        )
        return LineageRelation(out_shape, in_shape, out_rows, in_rows)

    @staticmethod
    def from_flat(
        out_shape: tuple[int, ...],
        in_shape: tuple[int, ...],
        out_flat: np.ndarray,
        in_flat: np.ndarray,
    ) -> "LineageRelation":
        """Build from flat (raveled) cell ids on each side."""
        out_idx = np.stack(
            np.unravel_index(np.asarray(out_flat, dtype=np.int64), out_shape), axis=1
        )
        in_idx = np.stack(
            np.unravel_index(np.asarray(in_flat, dtype=np.int64), in_shape), axis=1
        )
        return LineageRelation(out_shape, in_shape, out_idx, in_idx)


def _as_rows(idx, ndim: int) -> np.ndarray:
    """``idx`` as int64 ``[N, ndim]``; an ``[N, 0]`` array (a 0-d side)
    keeps its N, which no reshape can infer."""
    idx = np.asarray(idx, dtype=np.int64)
    if idx.ndim == 2 and idx.shape[1] == ndim:
        return idx
    return idx.reshape(-1, ndim)


def _canonical_rows(
    out_idx: np.ndarray, in_idx: np.ndarray, dims: tuple[int, ...]
) -> tuple[np.ndarray, np.ndarray, str]:
    """``(out, in, route)``: the rows of ``[out_idx | in_idx]`` sorted and
    deduplicated as ``np.unique(axis=0)`` gives them (see
    :meth:`LineageRelation.canonical_route`)."""
    n, l = out_idx.shape
    cols = [out_idx[:, j] for j in range(l)]
    cols += [in_idx[:, j] for j in range(in_idx.shape[1])]
    if n <= 1:
        return out_idx.copy(), in_idx.copy(), "presorted"
    if not cols:  # 0-d on both sides: every row is the one empty row
        return out_idx[:1].copy(), in_idx[:1].copy(), "packed"
    packable = math.prod(int(d) for d in dims) < 2**63 and all(
        c.min() >= 0 and c.max() < d for c, d in zip(cols, dims)
    )
    if not packable:
        rows = unique_rows(np.concatenate([out_idx, in_idx], axis=1))
        return rows[:, :l], rows[:, l:], "lexsort"
    # mixed-radix ravel: in bounds, so no step leaves [0, prod(dims))
    key = cols[0].copy()
    for c, d in zip(cols[1:], dims[1:]):
        key *= d
        key += c
    if bool(np.all(key[1:] > key[:-1])):
        return out_idx.copy(), in_idx.copy(), "presorted"
    rows = np.stack(np.unravel_index(np.unique(key), dims), axis=1)
    return rows[:, :l], rows[:, l:], "packed"
