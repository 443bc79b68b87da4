#!/usr/bin/env python3
"""Sweep the offered rate of an open-loop cell on the chip, in one process.

    python3 chipbench/sweep.py --workload minitron-4b.admit --seed 5 \
        --rates 2,4,6,8 --seconds 20

Set-up once (weights, engine, warm-up of the cell's shapes), then one
window per rate with the cell's traffic at that rate, each window's
prompts drawn from its own seed (``--seed`` + its index) so that no prompt
of an earlier window can hit the prefix cache.  Per rate it prints
the requests due and finished in the window, the p50/p95 time to first
token from the due time, and the p95 of the window's first and last
thirds: a backlog that grows shows as a last third far above the first.
The highest rate without a growing backlog is the knee; an ``*.admit``
cell offers about four fifths of it (its traffic file's
``arrivals.rate_per_s``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def sweep(cell, seed, rates, seconds):
    import jax

    from lib import harness, spans, stats, traffic, weights
    from lib.dims import dims

    harness.chips(cell.chips, True)
    d = dims(cell.config)
    params = weights.make(d, seed)
    eng = harness.build(cell.config, harness.model_config(cell.config),
                        params)
    jax.block_until_ready(params)
    harness.warm_up(eng, cell.traffic, seed)
    harness.log(f"set-up {time.perf_counter() - T_START:.1f}s")
    rows = []
    for i, rate in enumerate(rates):
        mix = dict(cell.traffic,
                   arrivals=dict(cell.traffic["arrivals"], rate_per_s=rate))
        c = dataclasses.replace(cell, traffic=mix)
        reqs = traffic.requests(mix, seed + 1 + i, d.V)
        win = harness.serve(eng, c, reqs, seconds, False, "",
                            spans.Counters())
        s = stats.summarize(win.records, win.t0, win.t1)
        third = (win.t1 - win.t0) / 3
        early = [r for r in win.records if r.due < win.t0 + third]
        late = [r for r in win.records if r.due >= win.t1 - third]

        def p95(recs):
            return 1e3 * stats.pct(stats.summarize(
                recs, win.t0, win.t1)["ttft"], 0.95)
        done = sum(r.done for r in win.records)
        row = dict(rate=rate, due=s["attempted"], finished=done,
                   failed=s["failed"],
                   ttft_p50_ms=1e3 * stats.pct(s["ttft"], 0.5),
                   ttft_p95_ms=1e3 * stats.pct(s["ttft"], 0.95),
                   ttft_p95_first_third_ms=p95(early),
                   ttft_p95_last_third_ms=p95(late),
                   itl_p95_ms=1e3 * stats.pct(s["itl"], 0.95),
                   generator_lag_s=win.extra)
        harness.log(json.dumps(row))
        rows.append(row)
        time.sleep(2.0)           # let cancelled streams drain
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from lib import harness, manifest

    cell = manifest.resolve(args.workload)
    if cell.traffic["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    harness.configure_jax()
    rows = sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
                 args.seconds)
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
