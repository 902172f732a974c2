"""Faults planted under the timed path, to show that ``correct`` catches them.

    python3 -m bench.tests.faults <fault> --workload <name> --seed <n> --seconds <s> --trace 0

runs one cell with the fault in place (on the chip, as the benchmark does);
the tests in this directory do the same at a small size on the CPU.  Each
fault breaks one guarantee the configurations state:

* ``approximate`` (the control of the query cells): every answer is the
  bounding box of the exact one, a superset where the answer must be exact;
* ``drop_box``: the last box of every answer is left out;
* ``skip_add``: ``add_lineage`` returns without storing the entry;
* ``half_rows``: ``add_lineage`` stores half of the relation's rows;
* ``unwritten_log`` (the control of the ingest cell): write-ahead log
  records stay in memory and never reach the disk, so a crash loses
  acknowledged writes;
* ``skip_fsync``: the write-ahead log never calls ``fsync``, so an
  acknowledged write may sit in the operating system's buffers.
"""

from __future__ import annotations

import sys


def _answers(transform):
    from repro.core.catalog import DSLog
    from repro.core.query import QueryBox

    real = DSLog.prov_query

    def prov_query(self, *args, **kw):
        out = real(self, *args, **kw)
        res, tr = out if kw.get("trace") else (out, None)
        lo, hi = transform(res.lo, res.hi)
        res = QueryBox(res.shape, lo, hi)
        return (res, tr) if kw.get("trace") else res

    return [(DSLog, "prov_query", prov_query)]


def approximate():
    return _answers(lambda lo, hi: (lo.min(axis=0, keepdims=True),
                                    hi.max(axis=0, keepdims=True))
                    if len(lo) else (lo, hi))


def drop_box():
    return _answers(lambda lo, hi: (lo[:-1], hi[:-1]))


def skip_add():
    from repro.core.catalog import DSLog

    return [(DSLog, "add_lineage", lambda self, *a, **kw: None)]


def half_rows():
    from repro.core.catalog import DSLog
    from repro.core.relation import LineageRelation

    real = DSLog.add_lineage

    def add_lineage(self, src, dst, rel, *a, **kw):
        n = rel.n_rows // 2
        half = LineageRelation(rel.out_shape, rel.in_shape,
                               rel.out_idx[:n], rel.in_idx[:n])
        return real(self, src, dst, half, *a, **kw)

    return [(DSLog, "add_lineage", add_lineage)]


def unwritten_log():
    from repro.core.wal import WriteAheadLog

    held = []

    def append(self, rtype, meta, blobs=()):
        held.append((rtype, meta, list(blobs)))
        return -1

    return [(WriteAheadLog, "append", append)]


def skip_fsync():
    import os
    import types

    from repro.core import wal

    no_sync = types.SimpleNamespace(**vars(os))
    no_sync.fsync = lambda fd: None
    return [(wal, "os", no_sync)]


FAULTS = {f.__name__: f for f in (approximate, drop_box, skip_add, half_rows,
                                   unwritten_log, skip_fsync)}


def plant(name: str, setattr_=setattr) -> None:
    """Apply fault ``name``; ``setattr_`` may be pytest's ``monkeypatch.setattr``."""
    for owner, attr, value in FAULTS[name]():
        setattr_(owner, attr, value)


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    import os

    from bench import run

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    plant(argv[0])
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main())
