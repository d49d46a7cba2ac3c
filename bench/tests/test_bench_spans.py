"""The reduction of nested spans on a small synthetic trace (self times,
idle time to the innermost span), the ``host_*_ms`` readers, and
``span_report`` end to end on the CPU."""

import os
import sys

import jax
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import span_reduce  # noqa: E402
import span_report  # noqa: E402
import trace_reduce  # noqa: E402

NAMES = harness.HOST_SPANS + span_reduce.PROGRAM_SPANS
READERS = {"host_inputs_ms": "inputs_ms", "host_dispatch_ms": "dispatch_ms",
           "host_outputs_ms": "outputs_ms"}


def ev(name, start, dur, **stats):
    return {"name": name, "start_ns": start, "dur_ns": dur, "stats": stats}


def synthetic():
    """One step of the serving loop inside the harness's spans; the device
    runs while sched.block waits and once more in sched.outputs."""
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ev("bench.window", 0, 1000),
        ev("bench.traffic", 0, 50),
        ev("sched.step", 50, 850),
        ev("sched.admit", 50, 10),
        ev("sched.launch", 60, 540, launch=0, net="dcgan"),
        ev("serve.inputs", 70, 230), ev("serve.dispatch", 300, 20),
        ev("sched.block", 320, 280),
        ev("sched.outputs", 600, 280),
        ev("bench.sample", 900, 50),
        ev("PjitFunction(f)", 300, 10)]}]}      # not a span of the loop
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [ev("jit_f(1)", 320, 280)]},
        {"name": "XLA Ops", "events": [
            ev("fusion.1", 320, 80), ev("custom-call.2", 400, 200),
            ev("dynamic-slice.3", 610, 20)]}]}
    return [host, device]


def test_self_time_is_less_the_children():
    table = span_reduce.span_table(synthetic(), NAMES)
    want = {  # name: (total, self)
        "bench.traffic": (50, 50), "sched.step": (850, 20),
        "sched.admit": (10, 10), "sched.launch": (540, 10),
        "serve.inputs": (230, 230), "serve.dispatch": (20, 20),
        "sched.block": (280, 280), "sched.outputs": (280, 280),
        "bench.sample": (50, 50)}
    assert set(table) == set(want)
    for name, (total, own) in want.items():
        assert table[name]["count"] == 1
        assert table[name]["total_s"] == pytest.approx(total * 1e-9)
        assert table[name]["self_s"] == pytest.approx(own * 1e-9), name


def test_idle_goes_to_the_innermost_span():
    # idle: [0, 320) [600, 610) [630, 1000)
    idle = dict(span_reduce.idle_by_span(synthetic(), NAMES))
    want = {"bench.traffic": 50, "sched.admit": 10, "sched.launch": 10,
            "serve.inputs": 230, "serve.dispatch": 20,
            "sched.outputs": 10 + 250, "sched.step": 20,
            "bench.sample": 50, "(no span)": 50}
    assert set(idle) == set(want)
    for name, ns in want.items():
        assert idle[name] == pytest.approx(ns * 1e-9), name
    assert sum(idle.values()) == pytest.approx(700e-9)


def test_reduce_still_gives_idle_to_the_harness_spans():
    """``trace_reduce.reduce`` reads the same trace as before: all of the
    step's idle stays with ``sched.step``."""
    red = trace_reduce.reduce(synthetic(), model_modules=["jit_f"],
                              host_spans=harness.HOST_SPANS)
    gaps = dict(red["idle_gaps"])
    assert gaps["sched.step"] == pytest.approx(550e-9)
    assert gaps["bench.traffic"] == pytest.approx(50e-9)
    assert gaps["(no span)"] == pytest.approx(50e-9)


def test_a_span_that_outlasts_its_parent_is_cut():
    pieces = span_reduce.innermost([("a", 0, 10), ("b", 5, 20),
                                    ("c", 30, 40)])
    assert pieces == [("a", 0, 5), ("b", 5, 10), ("c", 30, 40)]


def test_no_device_ops_no_idle():
    assert span_reduce.idle_by_span(synthetic()[:1], NAMES) is None


def _run(trace, launches):
    return harness.Run(configs={}, setup_s=1.0, window_s=1.0,
                       launches=launches, window_launches=2, served=[],
                       attempted=0, failed=0, peak={}, trace=trace)


LAUNCHES = [{"n": 4, "bucket": 4, "ms": 9.0, "inputs_ms": 2.0,
             "dispatch_ms": 0.5, "outputs_ms": 3.0},
            {"n": 4, "bucket": 4, "ms": 9.0, "inputs_ms": 4.0,
             "dispatch_ms": 1.5, "outputs_ms": 5.0},
            {"n": 4, "bucket": 4, "ms": 9.0, "inputs_ms": 99.0,
             "dispatch_ms": 99.0, "outputs_ms": 99.0}]   # in the drain


@pytest.mark.parametrize("metric", sorted(READERS))
def test_host_readers(metric):
    reader = harness.load_module(os.path.join(BENCH, "metrics",
                                              metric + ".py"))
    key = READERS[metric]
    # the window's launches only
    assert reader.read(_run({}, LAUNCHES)) == pytest.approx(
        (LAUNCHES[0][key] + LAUNCHES[1][key]) / 2)
    # an untraced run
    assert reader.read(_run(None, LAUNCHES)) is None
    # a program whose launch records carry no phases
    bare = [{k: r[k] for k in ("n", "bucket", "ms")} for r in LAUNCHES]
    assert reader.read(_run({}, bare)) is None


def test_span_report_on_the_cpu(monkeypatch):
    """A traced window of a test-size cell through the real serving path:
    every launch shows each span of the loop once, the spans' self times
    match the launch records' phase ms, and with no device trace the
    host readers read nothing."""
    v5e = harness.read_json(BENCH, "peaks.json")["TPU v5 lite"]
    monkeypatch.setattr(harness, "peaks_for", lambda kind: v5e)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    cell = harness.Cell.load("dcgan.offline")
    cell.workload = dict(cell.workload, max_batch=4, pool=16, sample=16,
                         reference_block=4)
    out = span_report.report(cell, 2 ** 32 + 7, 0.5, 1,
                             lambda n: jax.devices())
    assert out["span_cost_off_us_per_step"] > 0
    untraced, traced = out["pairs"][0]["untraced"], out["pairs"][0]["traced"]
    assert untraced["images_per_s"] > 0 and traced["images_per_s"] > 0
    launches = traced["launches"]
    spans = traced["spans_ms_per_launch"]
    assert launches > 0
    for name in ("sched.admit", "sched.launch", "serve.inputs",
                 "serve.dispatch", "sched.block", "sched.outputs",
                 "sched.step"):
        assert spans[name]["count"] == launches, name
    assert "sched.wait" not in spans              # a backlog never waits
    phases = traced["phase_ms"]
    for name, key in (("serve.inputs", "inputs_ms"),
                      ("serve.dispatch", "dispatch_ms"),
                      ("sched.outputs", "outputs_ms")):
        assert spans[name]["self"] == pytest.approx(phases[key], rel=0.05,
                                                    abs=0.05), name
    assert traced["idle_by_span_s"] is None
    assert set(traced["metrics"]) == {"launch_ms.offline", "mfu_pct"}
