"""Pristine stores: built once per configuration and code, copied per run.

A query cell opens a store that holds the configuration's whole lineage.
Building one takes a minute or two of host compression, so it is built by
the first run that lacks it and kept under ``bench/.stores/`` (never
committed), keyed by the configuration file, the program's source tree
(``src/repro``), this module, ``workflows.py`` and ``ops/``: changed code
never reads a stale store.  Every run works on a clone, so views, cached answers and
tuned geometries written by one run never reach the next; the pristine
directory is only read.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

from . import workflows
from .workflows import pipeline_hops

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
STORES = os.path.join(BENCH_DIR, ".stores")
# immutable blobs are hard-linked into a clone; everything else is copied
_LINKED = (".prvc", ".idx")


def code_hash(cfg_name: str) -> str:
    """Hash of what a store's bytes depend on."""
    h = hashlib.sha256()
    files = [os.path.join(workflows.CONFIG_DIR, f"{cfg_name}.json"),
             os.path.join(BENCH_DIR, "workflows.py"),
             os.path.join(BENCH_DIR, "store.py")]
    for top in (os.path.join(BENCH_DIR, "ops"), os.path.join(ROOT, "src", "repro")):
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
            files += [os.path.join(dirpath, f) for f in sorted(filenames)
                      if f.endswith(".py")]
    for path in files:
        h.update(os.path.relpath(path, ROOT).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _, fs in os.walk(path) for f in fs)


def to_relation(hop):
    """A hop's rows as the program's relation type (the hand-off point)."""
    from repro.core.relation import LineageRelation

    return LineageRelation(
        hop.out_shape, hop.in_shape,
        np.stack(np.unravel_index(hop.out_flat, hop.out_shape), axis=1),
        np.stack(np.unravel_index(hop.in_flat, hop.in_shape), axis=1),
    )


def build(cfg: dict, root: str) -> dict:
    """Ingest every pipeline of ``cfg`` into a new durable store at ``root``."""
    from repro.core.catalog import DSLog

    t0 = time.perf_counter()
    rows = raw = 0
    with DSLog.open(root, durability=cfg["guarantees"]["durability"],
                    store_forward=cfg["store_forward"]) as log:
        for pipe in cfg["pipelines"]:
            for hop in pipeline_hops(cfg, pipe):
                for name, shape in ((hop.src, hop.in_shape), (hop.dst, hop.out_shape)):
                    if name not in log.arrays:
                        log.define_array(name, shape)
                log.add_lineage(hop.src, hop.dst, to_relation(hop), op_name=hop.op)
                rows += hop.n_rows
                raw += hop.raw_bytes()
        log.commit()
    return {"raw_rows": rows, "raw_bytes": raw,
            "stored_bytes": dir_bytes(root),
            "build_s": time.perf_counter() - t0}


def pristine(cfg: dict, out=print) -> tuple[str, dict]:
    """The configuration's pristine store, built here if it is missing."""
    key = f"{cfg['name']}-{code_hash(cfg['name'])}"
    path = os.path.join(STORES, key)
    meta_path = path + ".json"
    if not os.path.exists(meta_path):
        os.makedirs(STORES, exist_ok=True)
        for old in os.listdir(STORES):  # stores of older code: reclaim disk
            if old.startswith(cfg["name"] + "-") and not old.startswith(key):
                remove(os.path.join(STORES, old))
        tmp = f"{path}.partial"
        remove(tmp)
        remove(tmp + ".json")
        out(f"building store {key}")
        # in a child process, on the CPU: a build leaves gigabytes of freed
        # heap behind, and the parent holds the chip
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
        subprocess.run([sys.executable, "-m", "bench.store",
                        os.path.join(workflows.CONFIG_DIR, f"{cfg['name']}.json"),
                        tmp, tmp + ".json"], cwd=ROOT, env=env, check=True)
        os.replace(tmp, path)
        os.replace(tmp + ".json", meta_path)  # last: marks the store whole
        with open(meta_path) as f:
            out(f"built store {key}: {f.read()}")
    with open(meta_path) as f:
        return path, json.load(f)


def clone(src: str, dst: str) -> None:
    """Copy a store, hard-linking its immutable blobs where the disk allows."""
    remove(dst)
    os.makedirs(dst)
    for name in os.listdir(src):
        a, b = os.path.join(src, name), os.path.join(dst, name)
        if name.endswith(_LINKED):
            try:
                os.link(a, b)
                continue
            except OSError:
                pass
        shutil.copy2(a, b)


def remove(path: str) -> None:
    if os.path.isdir(path):
        shutil.rmtree(path)
    elif os.path.exists(path):
        os.remove(path)


def main(argv=None) -> int:
    """``python -m bench.store <config.json> <store dir> <meta.json>``"""
    cfg_path, root, meta_path = sys.argv[1:] if argv is None else argv
    with open(cfg_path) as f:
        meta = build(json.load(f), root)
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
