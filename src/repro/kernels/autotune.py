"""Launch-geometry autotuner for the batched dense-join kernels.

The segmented kernels take a ``(block_q, block_r)`` launch geometry; the
best values depend on the backend (TPU Pallas vs interpret) and on the
*shape* of the frontier being joined (many tiny segments want small tiles,
few big segments want big ones).  :class:`GeometryTuner` measures candidate
geometries the first time a (backend, frontier-shape bucket) combination is
seen — Triton-style: each candidate runs the real workload once after a
warmup, the winner's result is kept so the measuring dispatch does the real
work — and caches the winner in a small table that the catalog persists as
an ``autotune.json`` sidecar next to the manifest.

Deliberately **jax-free**: backends are opaque strings, workloads run
through caller-supplied runners, so ``repro.core`` imports this without
touching the kernel stack.  Entries are keyed by backend, which is what
invalidates the cache when a store moves machines — a table tuned under
``interpret`` simply never answers a ``tpu`` lookup.
"""

from __future__ import annotations

import bisect
import math
import re
import threading
import time
from typing import Callable, Iterable, Sequence

__all__ = [
    "GeometryTuner",
    "shape_bucket",
    "ladder_step",
    "DEFAULT_GEOMETRY",
    "CANDIDATE_GEOMETRIES",
    "DEFAULT_TWIN_CELLS",
]

# kernel-launch geometry: (block_q, block_r) tile shapes.  Second-minor dim
# multiples of 8 and lane dim multiples of 128 keep every candidate legal
# for TPU tiling.
DEFAULT_GEOMETRY = (256, 256)
CANDIDATE_GEOMETRIES = (
    (64, 128),
    (128, 128),
    (128, 256),
    (256, 128),
    (256, 256),
    (512, 256),
)

# numpy-twin geometry: mask cells evaluated per row block (fixed; the twin
# is the CPU path and never runs where a launch geometry is measured)
DEFAULT_TWIN_CELLS = (4_194_304,)

_TABLE_VERSION = 2  # 2: query rows bucketed by ladder step, not by power of two

# the launch ladder: every multiple of a unit up to LADDER_LINEAR units, then
# each step LADDER_RATIO times the last, rounded down to a whole unit
LADDER_LINEAR = 8
LADDER_RATIO = 1.25


def _ladder_units(limit: int = 1 << 31) -> tuple[int, ...]:
    steps = list(range(1, LADDER_LINEAR + 1))
    while steps[-1] < limit:
        steps.append(int(steps[-1] * LADDER_RATIO))
    return tuple(steps)


_STEPS = _ladder_units()


def _ladder_index(n: int, unit: int = 1) -> int:
    """Position on the ladder of the smallest step holding ``n`` (0 for
    ``n <= 0``, 1 for one unit)."""
    return 0 if n <= 0 else bisect.bisect_left(_STEPS, -(-n // unit)) + 1


def ladder_step(n: int, unit: int, up: int = 0) -> int:
    """The smallest ladder step that holds ``n`` rows, moved ``up`` steps
    (down where negative, never below one unit); 0 where ``n <= 0``.

    Steps are multiples of ``unit`` (a block, or 1 for a tile count), so
    padding ``n`` to its step adds less than a quarter.
    """
    if n <= 0:
        return 0
    i = _ladder_index(n, unit) - 1 + up
    return _STEPS[min(max(i, 0), len(_STEPS) - 1)] * unit


def _log2_bucket(n: int) -> int:
    """Coarse pow-2 bucket of a count (0 stays 0)."""
    return 0 if n <= 0 else int(math.log2(n)) + 1


_BUCKET = re.compile(r"^k(\d+)q(\d+)r(\d+)w(\d+)$")
# an unseen bucket borrows the geometry of a tuned one this many query-row
# ladder steps away at most (same segment count, table rows and width)
FALLBACK_STEPS = 4


def shape_bucket(shapes: "Sequence[tuple[int, int, int]]") -> str:
    """Bucket key for a frontier's segment shapes.

    ``shapes`` is ``[(n_query_rows, n_table_rows, n_attrs), ...]``.  The
    *median* query rows take their ladder step (:func:`ladder_step`, one
    row a unit), the segment count and median table rows a pow-2 bucket,
    the max width itself: ``k{k}q{step}r{r}w{width}``.
    """
    if not shapes:
        return "empty"
    k = _log2_bucket(len(shapes))
    med_q = _ladder_index(int(sorted(s[0] for s in shapes)[len(shapes) // 2]))
    med_r = _log2_bucket(int(sorted(s[1] for s in shapes)[len(shapes) // 2]))
    width = max(s[2] for s in shapes)
    return f"k{k}q{med_q}r{med_r}w{width}"


class GeometryTuner:
    """Per-(backend, shape-bucket) launch-geometry table with measurement.

    ``pick`` is the one-stop API: cached winner when known, otherwise (if a
    ``runner`` is supplied) measure every candidate on the real workload and
    cache the winner.  Geometries are opaque int tuples — ``(block_q,
    block_r)`` for the kernels — so the table holds any backend's entries
    (an older store's ``np`` twin entries still load; nothing reads them).
    """

    def __init__(self) -> None:
        # parallel query workers race pick/lookup/to_manifest on one
        # tuner; the lock (rank 75, a leaf) guards only the table —
        # candidate measurement runs outside it, because runners execute
        # real workloads that take stats locks and fire metrics
        try:
            from repro.core import _locks

            self._lock = _locks.new_lock("autotune._lock")
        except ImportError:  # standalone use outside the repo tree
            self._lock = threading.Lock()
        self._table: dict[str, dict] = {}
        self.dirty = False

    # ------------------------------------------------------------------ #
    @staticmethod
    def _key(backend: str, bucket: str) -> str:
        return f"{backend}|{bucket}"

    def __len__(self) -> int:
        with self._lock:
            return len(self._table)

    def lookup(self, backend: str, bucket: str) -> "tuple[int, ...] | None":
        """The cached winning geometry, or None when this (backend, bucket)
        has never been measured — including after a backend change: entries
        are keyed by backend, so a table tuned elsewhere never answers."""
        with self._lock:
            rec = self._table.get(self._key(backend, bucket))
        if rec is None or rec.get("backend") != backend:
            return None
        try:
            return tuple(int(x) for x in rec["geometry"])
        except (KeyError, TypeError, ValueError):
            return None

    def nearest(
        self, backend: str, bucket: str, steps: int = FALLBACK_STEPS
    ) -> "tuple[int, ...] | None":
        """The geometry of the tuned bucket nearest to an unseen ``bucket``:
        same backend, segment count, table rows and width, query rows at
        most ``steps`` ladder steps away (the larger on a tie); None where
        there is none."""
        m = _BUCKET.match(bucket)
        if m is None:
            return None
        k, q, r, w = (int(x) for x in m.groups())
        with self._lock:
            recs = list(self._table.values())
        best = None
        for rec in recs:
            other = _BUCKET.match(str(rec.get("bucket", "")))
            if rec.get("backend") != backend or other is None:
                continue
            k2, q2, r2, w2 = (int(x) for x in other.groups())
            if (k2, r2, w2) != (k, r, w) or abs(q2 - q) > steps:
                continue
            rank = (abs(q2 - q), -q2)
            if best is None or rank < best[0]:
                best = (rank, rec)
        if best is None:
            return None
        try:
            return tuple(int(x) for x in best[1]["geometry"])
        except (KeyError, TypeError, ValueError):
            return None

    def pick(
        self,
        backend: str,
        bucket: str,
        runner: "Callable[[tuple[int, ...]], object] | None" = None,
        candidates: "Iterable[tuple[int, ...]]" = CANDIDATE_GEOMETRIES,
        default: "tuple[int, ...]" = DEFAULT_GEOMETRY,
        warmup: bool = True,
    ) -> "tuple[tuple[int, ...], object | None]":
        """Winning geometry for (backend, bucket), measuring on a miss.

        Returns ``(geometry, result)``: ``result`` is the winner's workload
        output when this call measured (so the tuning dispatch does the real
        work — no wasted evaluation), else ``None`` (cache hit, or no
        ``runner`` to measure with → ``default``).  ``warmup=True`` runs
        each candidate once untimed first so trace/compile cost never picks
        the winner (pointless for pure-numpy runners — pass ``False``).
        """
        cached = self.lookup(backend, bucket)
        if cached is not None:
            return cached, None
        if runner is None:
            return tuple(default), None
        best: "tuple[int, ...] | None" = None
        best_s = math.inf
        best_result: object = None
        measured: dict[str, float] = {}
        for geom in candidates:
            geom = tuple(int(x) for x in geom)
            if warmup:
                runner(geom)
            t0 = time.perf_counter()
            result = runner(geom)
            dt = time.perf_counter() - t0
            measured["x".join(str(x) for x in geom)] = round(dt * 1e6, 1)
            if dt < best_s:
                best, best_s, best_result = geom, dt, result
        assert best is not None, "no candidate geometries supplied"
        # concurrent measurers of the same key race benignly: last writer
        # wins and both winners came from real measurements
        with self._lock:
            self._table[self._key(backend, bucket)] = {
                "backend": backend,
                "bucket": bucket,
                "geometry": list(best),
                "us": round(best_s * 1e6, 1),
                "measured": measured,
            }
            self.dirty = True
        return best, best_result

    # ------------------------------------------------------------------ #
    # persistence (catalog sidecar)
    # ------------------------------------------------------------------ #
    def to_manifest(self) -> dict:
        with self._lock:
            return {"version": _TABLE_VERSION, "entries": dict(self._table)}

    def load_manifest(self, chunk: "dict | None") -> None:
        """Restore a persisted table, dropping anything malformed.

        Tolerant by design (the sidecar may be torn or from another
        version, whose buckets mean other shapes): a bad chunk loads as a
        cold table, and entries whose
        recorded backend disagrees with their key are discarded — they
        could only mislead a lookup.
        """
        self._table.clear()
        self.dirty = False
        if not isinstance(chunk, dict) or chunk.get("version") != _TABLE_VERSION:
            return
        entries = chunk.get("entries")
        if not isinstance(entries, dict):
            return
        for key, rec in entries.items():
            if not isinstance(rec, dict) or not isinstance(key, str):
                continue
            backend = rec.get("backend")
            if not isinstance(backend, str) or not key.startswith(backend + "|"):
                continue
            geom = rec.get("geometry")
            if not (
                isinstance(geom, list)
                and geom
                and all(isinstance(x, int) and x > 0 for x in geom)
            ):
                continue
            self._table[key] = rec
