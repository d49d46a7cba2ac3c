"""The trace reduction on a small synthetic trace: busy time is the union
of op intervals, idle gaps go to the host span that covered them, and
Pallas custom calls are told apart from XLA ops inside the model's
programs."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402


def ev(name, start, dur, **stats):
    return {"name": name, "start_ns": start, "dur_ns": dur, "stats": stats}


def synthetic():
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ev("bench.window", 0, 1000),
        ev("sched.step", 0, 400), ev("bench.sample", 400, 100),
        ev("sched.step", 500, 500)]}]}
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ev("jit_f(1)", 100, 250), ev("jit_slice(2)", 420, 20),
            ev("jit_f(1)", 600, 300)]},
        {"name": "XLA Ops", "events": [
            ev("fusion.1", 100, 50), ev("custom-call.2", 140, 160),
            ev("convolution.3", 300, 50),
            ev("dynamic-slice.1", 420, 20),
            ev("fusion.1", 600, 100),
            ev("kernel", 700, 200, hlo_category="custom-call"),
            ev("fusion.1", 1500, 100)]}]}   # after the window: ignored
    return [host, device]


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [
        (0, 3), (5, 8)]


def test_reduce_busy_idle_and_split():
    red = trace_reduce.reduce(synthetic(), model_modules=["jit_f"],
                              host_spans=("sched.step", "bench.sample"))
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 350) + [420, 440) + [600, 900)
    assert red["busy_s"] == pytest.approx((250 + 20 + 300) * 1e-9)
    # custom calls: 160 + 200; XLA ops of jit_f: 50 - 10 overlap counted
    # per op (50 + 50 + 100); the slice program is not the model's.
    assert red["model_custom_s"] == pytest.approx(360e-9)
    assert red["model_xla_s"] == pytest.approx(200e-9)
    gaps = dict(red["idle_gaps"])
    # idle: [0,100) [350,420) [440,600) [900,1000)
    assert gaps["sched.step"] == pytest.approx((100 + 50 + 100 + 100) * 1e-9)
    assert gaps["bench.sample"] == pytest.approx((20 + 60) * 1e-9)
    assert "(no span)" not in gaps
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(150e-9)
    assert ops["custom-call.2"] == pytest.approx(160e-9)


def test_op_names_from_hlo_text():
    text = ("%_sd_fused_jit.5 = f32[256,64,64,3]{3,2,1,0:T(8,128)} "
            "custom-call(f32[256,34,40,64]{3,2,1,0:T(8,128)} %pad.4), "
            "custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.op_name(text) == (
        "_sd_fused_jit.5 custom-call f32[256,64,64,3]")
    assert trace_reduce.is_custom_call({"name": text, "stats": {}})
    assert trace_reduce.op_name("fusion.1") == "fusion.1"


def test_reduce_without_device_ops_reads_nothing():
    host = synthetic()[0]
    assert trace_reduce.reduce([host]) is None


def test_reduce_needs_the_window_span():
    planes = synthetic()
    planes[0]["lines"][0]["events"].pop(0)
    with pytest.raises(ValueError):
        trace_reduce.reduce(planes)
