#!/usr/bin/env python3
"""Knee sweep for an open-loop cell (run on the chip, once, to fix its rate).

    python3 bench/sweep.py --workload dcgan.offline --max-batch 16 \
        --rates 100,200,400 --seconds 5 --seed 1 --out sweep.json

The configurations come from the cell ``--workload`` of ``BENCHMARK.json``,
served with ``--max-batch`` under Poisson arrivals in place of the cell's
own traffic.  One process sets this up once and offers load at each rate in
turn for ``--seconds``, with a drain of at most ``--drain`` seconds.  For
each rate it reports latency percentiles over the whole window and over
its first and last quarters (by due time), and what was left queued when
the window closed.  The knee is the highest rate whose backlog does not
grow: nothing left queued beyond a bucket or two, and a last quarter no
slower than the first.  The cell's traffic file then fixes 0.8 x knee.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import traffic as traffic_mix  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--drain", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload)
    cell.workload = dict(cell.workload, drain_s=args.drain,
                         max_batch=args.max_batch)
    rates = [float(r) for r in args.rates.split(",")]
    cell.traffic = traffic_mix.load("poisson", {"arrival": "poisson",
                                                "rate_per_s": rates[0]})
    harness.enable_compile_cache()
    devices = harness.find_chips(cell.chips)
    bench = harness.Bench(cell, args.seed)
    bench.warm()
    rows = []
    for rate in rates:
        traffic = traffic_mix.load("poisson", {"arrival": "poisson",
                                               "rate_per_s": rate})
        run = bench.run_window(args.seconds, time.perf_counter(),
                               traffic=traffic)
        lat = sorted(run.served, key=lambda r: r["rid"])
        q = max(1, len(lat) // 4)
        first = [r["latency_ms"] for r in lat[:q]]
        last = [r["latency_ms"] for r in lat[-q:]]
        n_window = sum(r["n"] for r in run.launches[:run.window_launches])
        row = {"rate_per_s": rate, "attempted": run.attempted,
               "failed": run.failed,
               "queued_at_close": run.attempted - n_window,
               "p50_ms": harness.percentile(run.latencies_ms(), 50),
               "p95_ms": harness.percentile(run.latencies_ms(), 95),
               "p95_first_quarter_ms": harness.percentile(first, 95),
               "p95_last_quarter_ms": harness.percentile(last, 95),
               "mean_launch_ms": run.mean_launch_ms(),
               "mean_rows": (run.images / run.window_launches
                             if run.window_launches else None)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": cell.name, "device": devices[0].device_kind,
                   "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
