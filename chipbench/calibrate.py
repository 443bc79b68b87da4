#!/usr/bin/env python3
"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 chipbench/calibrate.py --workload minitron-4b.decode \
        --seeds 11,12,13 --seconds 15 [--bits 3]

Runs the cell once per seed, as ``run.py`` does but with a window of
``--seconds``, and prints each run's compared numbers (the widest gap of
a served token below the plain reference's best logit, and the kernels
found in the decode and requant programs) as one JSON line per run.
``--bits`` serves the program at a lower weight precision instead: the
control, which the limits have to fail.  The limits, with the readings
they were set from, are kept in ``limits/<cell>.json`` and PERF.md.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--bits", type=int, default=None)
    args = ap.parse_args(argv)

    from lib import harness, manifest

    cell = manifest.resolve(args.workload)
    harness.configure_jax()
    monitor = harness.CompileMonitor()
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks = harness.run(cell, seed, args.seconds, False,
                                     time.perf_counter(), monitor,
                                     bits=args.bits)
        print(json.dumps({"seed": seed, "bits": args.bits,
                          "correct": result["correct"], "checks": checks,
                          "metrics": result["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
