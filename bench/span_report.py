#!/usr/bin/env python3
"""Where the serving loop's time goes, read from its spans (run on the chip).

    python3 bench/span_report.py --workload dcgan.offline --seed 7 \
        --seconds 10 --pairs 2 --out spans.json

One process sets the cell up once, then runs ``--pairs`` pairs of windows
at the cell's own load: one with no profiler, one under it.  Of each pair
it reports ``images_per_s`` of both windows (what tracing costs) and the
launch records' mean phase ms (what the ``host_*_ms`` metrics read), and
from the traced window's trace (``span_reduce``): each span's count, total
and self ms per launch and the device's idle time by the innermost span
that covered it.  Last it reads what the spans of one step cost with no
profiler running (``span_cost_us``).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402
import span_reduce  # noqa: E402
import trace_reduce  # noqa: E402

PHASES = ("ms", "inputs_ms", "dispatch_ms", "outputs_ms")


def span_cost_us(steps: int = 100_000) -> float:
    """Host us per step that the spans and phase clocks of one offline
    step cost with no profiler running, less the bare loop: sched.admit,
    sched.launch (four stats) around serve.inputs, serve.dispatch and
    sched.block, then sched.outputs, with the program's perf_counter
    reads and phase dicts."""
    import jax
    span, clock = jax.profiler.TraceAnnotation, time.perf_counter
    t = clock()
    for i in range(steps):
        with span("sched.admit"):
            pass
        with span("sched.launch", launch=i, net="dcgan", bucket=256,
                  n=256):
            t0 = clock()
            with span("serve.inputs"):
                pass
            t1 = clock()
            with span("serve.dispatch"):
                pass
            group_ms = {"inputs_ms": (t1 - t0) * 1e3,
                        "dispatch_ms": (clock() - t1) * 1e3}
            phase_ms = dict(group_ms)
            with span("sched.block"):
                pass
        with span("sched.outputs"):
            t2 = clock()
            rec = {"n": 256, **phase_ms}
            rec["outputs_ms"] = (clock() - t2) * 1e3
    spent = clock() - t
    t = clock()
    for i in range(steps):
        pass
    return (spent - (clock() - t)) / steps * 1e6


def phase_means(run) -> dict:
    """Mean of each phase the window's launch records carry, in ms."""
    window = run.window()
    return {k: sum(r[k] for r in window) / len(window)
            for k in PHASES if window and k in window[0]}


def traced_reading(bench, run, planes) -> dict:
    """What one traced window's trace says, per launch of the window."""
    launches = max(run.window_launches, 1)
    names = harness.HOST_SPANS + span_reduce.PROGRAM_SPANS
    table = span_reduce.span_table(planes, names)
    run.trace = trace_reduce.reduce(planes,
                                    model_modules=bench.model_modules(),
                                    host_spans=harness.HOST_SPANS)
    metrics, _ = harness.evaluate(run, bench.cell.per_layer)
    return {
        "launches": run.window_launches,
        "spans_ms_per_launch": {
            name: {"count": row["count"],
                   "total": 1e3 * row["total_s"] / launches,
                   "self": 1e3 * row["self_s"] / launches}
            for name, row in sorted(table.items())},
        "idle_by_span_s": span_reduce.idle_by_span(planes, names),
        "idle_gaps_s": run.trace and run.trace["idle_gaps"],
        "busy_s": run.trace and run.trace["busy_s"],
        "window_s": run.trace and run.trace["window_s"],
        "metrics": {k: v["value"] for k, v in metrics.items()},
    }


def report(cell, seed: int, seconds: float, pairs: int, find_chips) -> dict:
    """The reading of ``pairs`` pairs of windows (see the module doc);
    ``find_chips`` is the look for the chip, which a test replaces."""
    harness.enable_compile_cache()
    devices = find_chips(cell.chips)
    bench = harness.Bench(cell, seed)
    bench.warm()
    peak = harness.peaks_for(devices[0].device_kind)
    rows = []
    for _ in range(pairs):
        row = {}
        for traced in (False, True):
            tmp = tempfile.mkdtemp(prefix="span-trace-") if traced else None
            try:
                run = bench.run_window(seconds, time.perf_counter(),
                                       trace_dir=tmp)
                run.peak = peak
                key = "traced" if traced else "untraced"
                row[key] = {"images_per_s": run.images / run.window_s,
                            "phase_ms": phase_means(run)}
                if traced:
                    row[key].update(traced_reading(
                        bench, run, trace_reduce.load(tmp)))
            finally:
                if tmp:
                    shutil.rmtree(tmp, ignore_errors=True)
        rows.append(row)
        print(json.dumps(row), flush=True)
    out = {"workload": cell.name, "seed": seed, "seconds": seconds,
           "device": devices[0].device_kind,
           "span_cost_off_us_per_step": span_cost_us(), "pairs": rows}
    print(json.dumps({k: v for k, v in out.items() if k != "pairs"}),
          flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--pairs", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = report(harness.Cell.load(args.workload), args.seed, args.seconds,
                 args.pairs, harness.find_chips)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
