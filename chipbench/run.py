#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 chipbench/run.py --workload minitron-4b.decode --seed 7 \
        --seconds 40 --trace 0

The cell's configuration, traffic mix, limits and per-layer metric readers
are found by name from ``BENCHMARK.json`` (see lib/manifest.py).  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer ones), ``device``, with ``--trace 1`` a
``breakdown`` of device time and idle gaps, and last ``checks``: each
number the correctness comparison holds against its limit, which are also
the last lines of standard error.  With no TPU, or fewer chips than the
cell needs, it prints no result and exits 2; without the repository's
``src/`` beside it, it fails too.  JAX's compile cache is kept in
``.jax_cache/`` at the root of the checkout.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


if __name__ == "__main__":
    from lib import harness

    sys.exit(harness.main(parse(), T_START))
