#!/usr/bin/env python3
"""On-chip benchmark of split-deconvolution serving: one run of one cell.

    python3 bench/run.py --workload dcgan.offline --seed 7 --seconds 10 \
        --trace 0

Sets the cell up (weights and inputs from ``--seed``, every shape the
traffic uses compiled and run once), drives the continuous-batching
scheduler for ``--seconds`` under the cell's traffic, checks a seeded
sample of the served outputs against the plain reference, and prints one
JSON line last on stdout: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics from a profiler trace of the window), ``device``,
``breakdown`` and ``notes`` (traced runs: the top device ops and idle
gaps, and which side bounds a roofline share) and ``checks`` (each
compared number with its limit, also printed last on stderr).  Without a
TPU it exits 3 and prints no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import trace_reduce  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def checks_of(run, bench) -> dict:
    """The numbers the run is judged by, each beside its limit: the gaps
    to the reference that each configuration sets a limit on, how many
    outputs were compared (at least one) and how many requests due in the
    window never finished (none)."""
    gaps = bench.gaps(run.outputs)
    checks = {}
    for config in bench.cell.configs.values():
        for metric, limit in config["limits"].items():
            name = f"{metric}.{config['name']}"
            checks[name] = {"value": gaps[name], "limit": limit}
    checks["compared"] = {"value": len(run.outputs), "limit": 1}
    checks["failed"] = {"value": run.failed, "limit": 0}
    return checks


def is_correct(checks) -> bool:
    return all(c["value"] >= c["limit"] if name == "compared"
               else c["value"] <= c["limit"]
               for name, c in checks.items())


def execute(args, find_chips=harness.find_chips, cell=None) -> dict:
    """One run; returns the result line.  ``find_chips`` is the look for
    the chip, which a test replaces (with ``cell`` at a test size) to
    drive the rest of a run on the CPU."""
    marks = [("imports", time.perf_counter())]
    cell = cell or harness.Cell.load(args.workload)
    harness.enable_compile_cache()
    devices = find_chips(cell.chips)
    marks.append(("chips", time.perf_counter()))
    peak = harness.peaks_for(devices[0].device_kind)
    bench = harness.Bench(cell, args.seed)
    marks.append(("server", time.perf_counter()))
    bench.warm()
    marks.append(("warm-up", time.perf_counter()))
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        run = bench.run_window(args.seconds, T_PROCESS, trace_dir=tmp)
        run.peak = peak
        run.memory_peak_bytes = bench.memory_peak_bytes()
        modules = bench.model_modules()
        bench.free_server()
        checks = checks_of(run, bench)
        if tmp:
            run.trace = trace_reduce.reduce(
                trace_reduce.load(tmp), model_modules=modules,
                host_spans=harness.HOST_SPANS)
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    metrics, notes = harness.evaluate(run, cell.per_layer if args.trace
                                      else cell.end_to_end)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": is_correct(checks), "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": device}
    if args.trace:
        if run.trace is not None:
            device["busy_s"] = run.trace["busy_s"]
            device["window_s"] = run.trace["window_s"]
            line["breakdown"] = {"device_ops": run.trace["device_ops"],
                                 "idle_gaps": run.trace["idle_gaps"]}
    if notes:
        line["notes"] = notes
    line["checks"] = checks
    t = T_PROCESS
    for name, at in marks:
        print(f"set-up {name}: {at - t:.3f} s", file=sys.stderr)
        t = at
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = execute(args)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
