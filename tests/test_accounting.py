"""Tables 1-3 accounting must stay pinned to the paper's numbers."""

import pytest

from repro.core.accounting import (BENCHMARKS, PAPER_TABLE1, PAPER_TABLE2,
                                   PAPER_TABLE3)

M = 1e6
EXACT = {"dcgan", "sngan", "gpgan", "artgan", "fst"}


@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_table2_deconv_macs(name):
    net = BENCHMARKS[name]()
    orig, nzp, sd = PAPER_TABLE2[name]
    tol = 0.001 if name in EXACT else 0.03
    assert net.deconv_macs() / M == pytest.approx(orig, rel=tol)
    assert net.deconv_nzp_macs() / M == pytest.approx(nzp, rel=tol)
    assert net.deconv_sd_macs() / M == pytest.approx(sd, rel=tol)


@pytest.mark.parametrize("name", sorted(BENCHMARKS))
def test_table3_params(name):
    # the paper prints 2 decimals — allow rel 5% OR abs 0.02M rounding
    net = BENCHMARKS[name]()
    deform, sd, comp = PAPER_TABLE3[name]
    for ours, ref in [(net.deconv_params() / M, deform),
                      (net.deconv_sd_params() / M, sd),
                      (net.deconv_sd_params_compressed() / M, comp)]:
        assert abs(ours - ref) <= max(0.05 * ref, 0.02), (ours, ref)


def test_table1_dcgan_exact():
    net = BENCHMARKS["dcgan"]()
    total, deconv = PAPER_TABLE1["dcgan"]
    assert net.total_macs() / M == pytest.approx(total, rel=1e-3)
    assert net.deconv_macs() / M == pytest.approx(deconv, rel=1e-3)


def test_sd_expansion_ratios():
    """SD/orig per-kernel ratios: (s*ceil(K/s)/K)^2."""
    from repro.core.accounting import LayerSpec
    assert LayerSpec("deconv", 4, 4, k=4, s=2,
                     in_hw=(4, 4)).sd_expansion() == 1.0
    assert LayerSpec("deconv", 4, 4, k=5, s=2,
                     in_hw=(4, 4)).sd_expansion() == pytest.approx(36 / 25)
    assert LayerSpec("deconv", 4, 4, k=3, s=2,
                     in_hw=(4, 4)).sd_expansion() == pytest.approx(16 / 9)
    assert LayerSpec("deconv", 4, 4, k=5, s=1,
                     in_hw=(4, 4)).sd_expansion() == 1.0


def test_pix2pix_params_and_macs():
    """The U-Net at its published widths (arXiv:1611.07004, 6.1.1):
    54.4 M weights, 2.02 G encoder + 4.03 G decoder MACs per image."""
    from repro.core.accounting import WORKLOADS
    net = WORKLOADS["pix2pix"]()
    assert sum(l.params() for l in net.layers) / M == pytest.approx(54.4,
                                                                    abs=0.05)
    assert net.total_macs() / 1e9 == pytest.approx(6.05, abs=0.005)
    assert net.deconv_macs() / 1e9 == pytest.approx(4.03, abs=0.005)
    assert net.deconv_sd_macs() == net.deconv_macs()    # k=4, s=2
