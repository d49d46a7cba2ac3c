#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from (run on the chip).

    python3 bench/calibrate.py --workload dcgan.offline --seeds 11-22 \
        --control-seeds 11-13 --seconds 3 --out calib.json

One process sets the cell up once, then for each seed rebinds the server
to that seed's weights and inputs, runs a short window at the cell's own
load and reads the gaps (``Bench.gaps``) between the program's sampled
outputs and the reference at "highest": the lower readings.  For each
control seed it reads the same gaps for the control, the reference in
three bfloat16 passes ("high") in the program's place, on the same
sampled requests: the upper readings.  Each limit in a configuration file lies
between the readings (``witness.py`` reads where a gap to the host's
float64 lies).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def seed_list(text):
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b or a) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seed_list, required=True)
    ap.add_argument("--control-seeds", type=seed_list, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    cell = harness.Cell.load(args.workload)
    harness.enable_compile_cache()
    devices = harness.find_chips(cell.chips)
    t = time.perf_counter()
    bench = harness.Bench(cell, args.seeds[0])
    bench.warm()
    rows = []
    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        bench.reseed(seed)
        run = bench.run_window(args.seconds, t)
        ref = bench.reference(list(run.outputs))
        row = {"seed": seed, "attempted": run.attempted,
               "failed": run.failed, "compared": len(run.outputs)}
        if seed in args.seeds:
            row["program"] = bench.gaps(run.outputs, ref=ref)
        if seed in args.control_seeds:
            control = bench.reference(list(run.outputs), "high")
            row["control"] = bench.gaps(control, ref=ref)
        rows.append(row)
        print(json.dumps(row), flush=True)
    readings = {}
    for name in rows[0].get("program") or rows[0]["control"]:
        readings[name] = {
            "lower": max(r["program"][name] for r in rows if "program" in r),
            "upper": min((r["control"][name] for r in rows
                          if "control" in r), default=None)}
    summary = {"workload": cell.name, "device": devices[0].device_kind,
               "seconds": args.seconds, "readings": readings,
               "limits_in_force": {c: v["limits"]
                                   for c, v in cell.configs.items()},
               "rows": rows}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
