"""Record the small chip trace that ``test_devtrace.py`` reduces.

    python3 -m bench.tests.make_testdata <out_dir>

On a TPU: six range-join launches of three shapes, each under a
``bench.query`` span inside one ``bench.window`` span, traced with the
options the benchmark uses.  Writes ``v5e_range_join.xplane.pb`` to
``out_dir``; it is kept in ``bench/testdata``.
"""

from __future__ import annotations

import os
import shutil
import sys


def main(out_dir: str) -> int:
    from bench import devtrace, run
    from bench.context import profile

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    import jax
    import numpy as np
    from repro.kernels.ops import range_join_pairs

    if jax.devices()[0].platform != "tpu":
        print("make_testdata: needs a TPU", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    joins = []
    for nq, nr in ((64, 17), (300, 257), (1000, 3)):
        q_lo = rng.integers(0, 4096, (nq, 1))
        r_lo = rng.integers(0, 4096, (nr, 1))
        joins.append((q_lo, q_lo + 2, r_lo, r_lo + 64))
    for j in joins:  # compile outside the trace
        range_join_pairs(*j)
    trace_dir = os.path.join(out_dir, "trace")
    profile(trace_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for j in joins + joins:
            with jax.profiler.TraceAnnotation("bench.query"):
                range_join_pairs(*j)
    jax.profiler.stop_trace()
    src = devtrace.find_xplane(trace_dir)
    dst = os.path.join(out_dir, "v5e_range_join.xplane.pb")
    shutil.copy(src, dst)
    red = devtrace.reduce_file(dst)
    print(f"{dst}: {os.path.getsize(dst)} bytes; {red}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
