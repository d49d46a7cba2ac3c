"""The pix2pix U-Net configuration: its file against the program's spec,
its operation count, its plain reference against the program, and the
``skip_join_mb`` reader, all on the CPU."""

import os
import sys

import jax
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import flops  # noqa: E402
import harness  # noqa: E402
import run  # noqa: E402

from repro.core import accounting  # noqa: E402
from repro.models.generative import GenerativeModel  # noqa: E402

CONFIG = harness.read_json(BENCH, "configs", "pix2pix_unet_f32.json")
REF = harness.load_module(os.path.join(BENCH, "references", "unet.py"))
GRAPH_KEYS = ("act", "norm", "bias", "skip")


def _config_of(spec, name):
    """A configuration file's content for a U-Net spec of the program."""
    layers = []
    for l in spec.layers:
        d = {"kind": l.kind, "name": l.name, "cin": l.cin, "cout": l.cout,
             "k": l.k, "s": l.s, "in_hw": list(l.in_hw),
             "padding": l.padding, "act": l.act, "norm": l.norm,
             "bias": l.bias, "skip": l.skip}
        if l.act == "leaky_relu":
            d["slope"] = accounting.LEAKY_SLOPE
        layers.append(d)
    return dict(CONFIG, name=name, net=spec.name, layers=layers)


SMALL = accounting.unet(32, (8, 16, 32, 64), (32, 16, 8, 3),
                        name="unet_small")


def test_config_is_the_program_spec():
    spec = accounting.WORKLOADS["pix2pix"]()
    harness.check_spec(spec, CONFIG)
    for want, layer in zip(CONFIG["layers"], spec.layers):
        assert {k: want[k] for k in GRAPH_KEYS} == {
            k: getattr(layer, k) for k in GRAPH_KEYS}, layer.name
        if layer.act == "leaky_relu":
            assert want["slope"] == accounting.LEAKY_SLOPE
    assert CONFIG["reduced"] == [] and CONFIG["reference"] == "unet"


def test_model_flops():
    layers = CONFIG["layers"]
    assert flops.model_flops(layers) / 2e9 == pytest.approx(6.05, abs=0.005)
    # Every deconv is one split-deconv kernel call, with no expansion.
    calls = flops.sd_kernel_launches(layers, 32, 4)
    assert [c["layer"] for c in calls] == [f"u{k}" for k in range(1, 9)]
    assert sum(c["flops"] for c in calls) / 32 / 2e9 == pytest.approx(
        4.03, abs=0.005)


@pytest.fixture(scope="module")
def small_case():
    config = _config_of(SMALL, "unet_small_f32")
    params = REF.init(config["layers"], jax.random.PRNGKey(11))
    x = jax.random.uniform(jax.random.PRNGKey(12), (2, 32, 32, 3))
    return config, params, x, np.asarray(REF.forward(config, params, x))


def _gap(out, ref):
    return float(np.linalg.norm(np.asarray(out) - ref) / np.linalg.norm(ref))


def test_reference_matches_program_plain_path(small_case):
    config, params, x, want = small_case
    out = GenerativeModel(SMALL, "native").apply(params, x)
    assert _gap(out, want) < 2e-6


def test_control_differs_from_highest(small_case):
    config, params, x, want = small_case
    high = REF.forward(config, params, x, "high")
    assert _gap(high, want) > 5e-6


def _run(launches, trace):
    return harness.Run(configs={"pix2pix": CONFIG}, setup_s=1.0,
                       window_s=1.0, launches=launches, window_launches=2,
                       served=[], attempted=0, failed=0, peak={},
                       trace=trace)


def test_skip_join_mb_reads_launch_records():
    reader = harness.load_module(os.path.join(BENCH, "metrics",
                                              "skip_join_mb.py"))
    rec = {"net": "pix2pix", "bucket": 32, "n": 32, "ms": 1.0}
    launches = [dict(rec, join_bytes=514_326_528),
                dict(rec, n=20, join_bytes=514_326_528),
                dict(rec, join_bytes=1)]               # after the window
    assert reader.read(_run(launches, {})) == pytest.approx(514.326528)
    assert reader.read(_run(launches, None)) is None   # untraced
    assert reader.read(_run([rec] * 3, {})) is None    # no such counter


def test_small_unet_cell_runs_correct(monkeypatch):
    """A whole run of the cell on the CPU at a test size, a 4-level
    U-Net at 32x32 in the configuration's place, comes out correct."""
    monkeypatch.setitem(accounting.WORKLOADS, "unet_small", lambda: SMALL)
    v5e = harness.read_json(BENCH, "peaks.json")["TPU v5 lite"]
    monkeypatch.setattr(harness, "peaks_for", lambda kind: v5e)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    cell = harness.Cell.load("pix2pix.offline")
    config = _config_of(SMALL, "unet_small_f32")
    config["limits"] = {"l2_rel_err": 2e-6}
    cell.configs, cell.shares = {config["name"]: config}, {config["name"]: 1}
    cell.workload = dict(cell.workload, max_batch=4, pool=16, sample=16,
                         reference_block=4)
    args = run.parse(["--workload", cell.name, "--seed", str(2 ** 32 + 7),
                      "--seconds", "1", "--trace", "0"])
    line = run.execute(args, find_chips=lambda n: jax.devices(), cell=cell)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
