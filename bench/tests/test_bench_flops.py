"""The benchmark's operation counts agree with the paper's tables and with
the program's own accounting, from the configuration files alone."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import harness  # noqa: E402


def _config(name):
    return harness.read_json(BENCH, "configs", name + ".json")


def test_dcgan_counts_match_paper_tables():
    layers = _config("dcgan_f32")["layers"]
    assert sum(flops.useful_macs(l) for l in layers) == 111_411_200
    assert round(sum(flops.sd_macs(l) for l in layers
                     if l["kind"] == "deconv") / 1e6, 2) == 158.07
    assert flops.model_flops(layers) == 2 * 111_411_200


def test_mde_counts():
    layers = _config("mde_f32")["layers"]
    assert round(sum(flops.useful_macs(l) for l in layers) / 1e9, 3) == 1.951
    # 3x3 stride-2 upconvs: the split filters add 16/9.
    up1 = [l for l in layers if l["name"] == "up1"][0]
    assert flops.sd_expansion(up1) == pytest.approx(16 / 9)


@pytest.mark.parametrize("config", ["dcgan_f32", "mde_f32"])
def test_counts_match_program_accounting(config):
    from repro.core.accounting import WORKLOADS
    cfg = _config(config)
    spec = WORKLOADS[cfg["net"]]()
    harness.check_spec(spec, cfg)
    for mine, theirs in zip(cfg["layers"], spec.layers):
        assert flops.useful_macs(mine) == theirs.macs()
        if theirs.kind == "deconv":
            assert flops.sd_macs(mine) == theirs.sd_macs()


def test_sd_bytes_and_least_time():
    d1 = _config("dcgan_f32")["layers"][1]
    # input 8x8x256, split filters 2*2 phases of 3x3x256x128, out 16x16x128
    want = (8 * 8 * 256 + 4 * 9 * 256 * 128 + 16 * 16 * 128) * 4
    assert flops.sd_bytes(d1, 1, 4) == want
    calls = [{"flops": 2e12, "bytes": 1e9}, {"flops": 1e9, "bytes": 8e9}]
    least, bound = flops.least_seconds(calls, 1e12, 1e9)
    assert least == pytest.approx(2.0 + 8.0)
    assert bound == "memory"


@pytest.mark.parametrize("config,bucket,bound", [("dcgan_f32", 256, "compute"),
                                                 ("mde_f32", 16, "memory")])
def test_roofline_share_names_its_bound(config, bucket, bound):
    """The kernel roofline reader gives a share under 100% for a kernel
    time above the least time, and names the side that bounds it."""
    cfg = _config(config)
    calls = flops.sd_kernel_launches(cfg["layers"], bucket, 4)
    peak = harness.read_json(BENCH, "peaks.json")["TPU v5 lite"]
    least, side = flops.least_seconds(calls, peak["bf16_flops_per_s"],
                                      peak["hbm_bytes_per_s"])
    assert side == bound
    launch = {"net": cfg["net"], "bucket": bucket, "n": bucket, "ms": 1.0}
    run = harness.Run(configs={cfg["net"]: cfg}, setup_s=1.0,
                      window_s=1.0, launches=[launch] * 3, window_launches=2,
                      served=[], attempted=0, failed=0, peak=peak,
                      trace={"model_custom_s": 4 * least})
    metrics, notes = harness.evaluate(
        run, [{"name": "sd_kernel_roofline", "unit": "%"}])
    assert metrics["sd_kernel_roofline"]["value"] == pytest.approx(50.0)
    assert notes == {"sd_kernel_roofline": bound + "-bound"}
