"""Zero-copy fused pipeline: border-masked halo reads + in-kernel crop.

Parity of the single-HBM-touch path (in-kernel P_I pad, crop folded into
the epilogue, width-tiled launches, Pallas-backed backward) against the
pad+crop reference composition and against ``native_deconv``, across the
paper layer geometries, asymmetric padding, ``output_padding`` (incl.
the op > hi extension), bf16, and ranks 1/2/3.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.sd as sd
from repro.core.accounting import BENCHMARKS
from repro.core.deconv import (native_deconv, same_deconv_pads,
                               split_filters)
from repro.kernels.autotune import KernelPlan
from repro.kernels.ops import (sd_conv2d_valid, sd_deconv_presplit_fused,
                               sd_filter_grad_fused, sd_input_grad_fused,
                               ws_to_ocmajor)
from repro.kernels.sd_conv import sd_conv_pallas


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _layer_pads(layer):
    return (same_deconv_pads(layer.k, layer.s)
            if layer.padding == "same" else layer.pad)


# ---------------------------------------------------------------------------
# Kernel-level units: masked pad and output window of the conv kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pad", [((1, 1), (2, 2)), ((2, 0), (0, 1)),
                                 ((0, 0), (3, 3))])
def test_conv_kernel_in_kernel_pad(pad):
    """Border-masked halo reads == conv over a materialised jnp.pad."""
    x = _rand((2, 6, 7, 4), seed=1)
    w = _rand((3, 3, 4, 5), seed=2)
    (pt, pb), (pl_, pr) = pad
    ref = sd_conv_pallas(jnp.pad(x, ((0, 0), (pt, pb), (pl_, pr),
                                     (0, 0))), w, th=4, interpret=True)
    out = sd_conv_pallas(x, w, th=4, pad=pad, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_conv_kernel_output_window():
    """out_start/out_size == the same window sliced from the full conv."""
    x = _rand((1, 9, 8, 3), seed=3)
    w = _rand((3, 3, 3, 4), seed=4)
    full = sd_conv_pallas(x, w, th=3, pad=((2, 2), (2, 2)),
                          interpret=True)
    win = sd_conv_pallas(x, w, th=3, pad=((2, 2), (2, 2)),
                         out_start=(2, 2), out_size=(9, 8),
                         interpret=True)
    np.testing.assert_allclose(np.asarray(win),
                               np.asarray(full[:, 2:11, 2:10]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("tw", [2, 3, 5, 8])
def test_conv_kernel_width_tiling(tw):
    """tw width tiles (incl. non-dividing widths: trailing partial
    blocks) agree with the full-width launch."""
    x = _rand((1, 8, 11, 6), seed=5)
    w = _rand((2, 2, 6, 4), seed=6)
    ref = sd_conv_pallas(x, w, th=7, interpret=True)
    out = sd_conv_pallas(x, w, th=3, tw=tw, pad=((0, 0), (0, 0)),
                         interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Zero-copy fused path vs the pad+crop reference composition
# ---------------------------------------------------------------------------

def _both_paths(x, w, s, pads, op=0, bias=None, act="linear", plan=None):
    ws = ws_to_ocmajor(split_filters(w, s), s)
    kw = dict(output_padding=op, bias=bias, act=act, plan=plan)
    zc = sd_deconv_presplit_fused(x, ws, w.shape[:2], s, pads,
                                  zero_copy=True, **kw)
    pc = sd_deconv_presplit_fused(x, ws, w.shape[:2], s, pads,
                                  zero_copy=False, **kw)
    return zc, pc


@pytest.mark.parametrize("net", sorted(BENCHMARKS))
def test_zero_copy_matches_padcrop_on_paper_layers(net):
    """Every deconv layer geometry of the six paper nets: the zero-copy
    launch == the pad -> kernel -> crop composition == native."""
    spec = BENCHMARKS[net]()
    for layer in spec.deconv_layers():
        pads = _layer_pads(layer)
        x = _rand((1, *layer.in_hw, layer.cin), seed=layer.k)
        w = _rand((layer.k, layer.k, layer.cin, layer.cout),
                  seed=layer.s) * 0.05
        zc, pc = _both_paths(x, w, layer.s, pads)
        ref = native_deconv(x, w, layer.s, pads)
        np.testing.assert_allclose(np.asarray(zc), np.asarray(pc),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{net}/{layer.name}")
        np.testing.assert_allclose(np.asarray(zc), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"{net}/{layer.name}")


@pytest.mark.parametrize("K,s,pads", [
    (4, 2, ((1, 0), (0, 2))),
    (5, 2, ((0, 3), (2, 1))),
    (5, 3, ((2, 0), (1, 3))),
    (3, 2, ((1, 2), (0, 0))),
])
def test_zero_copy_asymmetric_padding(K, s, pads):
    x = _rand((1, 6, 8, 5), seed=K + 10)
    w = _rand((K, K, 5, 4), seed=s + 10)
    zc, pc = _both_paths(x, w, s, pads)
    ref = native_deconv(x, w, s, pads)
    assert zc.shape == ref.shape
    np.testing.assert_allclose(np.asarray(zc), np.asarray(pc),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zc), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,s,pad,op", [
    (4, 2, 1, 1),            # op <= hi: crop shrinks
    (5, 3, 2, 2),
    (3, 2, 0, 1),            # op > hi: zero-extension past the support
    (2, 2, 0, 1),
    (7, 4, 3, 3),
])
def test_zero_copy_output_padding(K, s, pad, op):
    """output_padding through the zero-copy path, including the op > hi
    extension — which the kernel now handles natively (masked input ->
    act(bias) rows), with no out-of-kernel fallback."""
    x = _rand((2, 5, 4, 3), seed=K)
    w = _rand((K, K, 3, 4), seed=s)
    bias = _rand((4,), seed=7)
    zc, pc = _both_paths(x, w, s, pad, op=op, bias=bias, act="relu")
    ref = jax.nn.relu(native_deconv(x, w, s, pad, output_padding=op)
                      + bias)
    assert zc.shape == ref.shape
    np.testing.assert_allclose(np.asarray(zc), np.asarray(pc),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(zc), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,s", [(5, 2), (4, 2), (5, 3)])
def test_zero_copy_bf16(K, s):
    x32 = _rand((2, 6, 5, 8), seed=K)
    w32 = _rand((K, K, 8, 4), seed=s)
    xb, wb = x32.astype(jnp.bfloat16), w32.astype(jnp.bfloat16)
    ws = ws_to_ocmajor(split_filters(wb, s), s)
    out = sd_deconv_presplit_fused(xb, ws, (K, K), s, 1, zero_copy=True)
    assert out.dtype == jnp.bfloat16
    ref = native_deconv(xb.astype(jnp.float32), wb.astype(jnp.float32),
                        s, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_zero_copy_width_tiled_plan():
    """A pinned (th, tw, tcin, tcout) plan with a non-dividing tw."""
    x = _rand((1, 8, 10, 6), seed=20)
    w = _rand((4, 4, 6, 4), seed=21)
    ref = native_deconv(x, w, 2, 1)
    for tw in (2, 3, 4):
        zc, _ = _both_paths(x, w, 2, 1,
                            plan=KernelPlan(th=2, tcin=3, tcout=2,
                                            tw=tw))
        np.testing.assert_allclose(np.asarray(zc), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Rank 1/2/3 through the functional fused backend
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape_x,shape_w,s,pad,op", [
    ((2, 9, 3), (5, 3, 2), 2, 1, 1),                 # rank 1
    ((1, 7, 2), (4, 2, 3), 3, (1, 0), 0),            # rank 1, asym
    ((2, 5, 6, 3), (4, 4, 3, 2), 2, 1, 0),           # rank 2
    ((1, 3, 4, 4, 2), (4, 4, 4, 2, 2), 2, 1, 1),     # rank 3
    # Cout 3, 16 and 64 run phase-packed, Cout 128 one phase per lane
    # tile: s 2 and 3, residual crops, output_padding, rank 1
    ((2, 5, 6, 8), (3, 3, 8, 3), 2, ((1, 0), (0, 1)), 1),
    ((1, 4, 5, 8), (5, 5, 8, 16), 3, 2, 2),
    ((2, 5, 4, 8), (4, 4, 8, 64), 2, 1, 1),
    ((1, 4, 4, 8), (4, 4, 8, 128), 2, 1, 1),
    ((2, 9, 8), (7, 8, 16), 3, 3, 2),                 # rank 1
])
def test_fused_backend_ranks(shape_x, shape_w, s, pad, op):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(*shape_x), jnp.float32)
    w = jnp.asarray(rng.randn(*shape_w), jnp.float32)
    plan = sd.plan(w.shape, s, pad, backend="fused", output_padding=op)
    ref = native_deconv(x, w, s, pad, output_padding=op)
    np.testing.assert_allclose(
        np.asarray(sd.conv_transpose(plan, x, w)), np.asarray(ref),
        rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pallas-backed backward: the two conv kernels + end-to-end grads
# ---------------------------------------------------------------------------

def test_input_grad_kernel_vs_lax():
    """sd_input_grad_fused == FULL lax conv + P_I crop."""
    from jax import lax
    from repro.core.deconv import conv_dimension_numbers
    rng = np.random.RandomState(4)
    dy1 = jnp.asarray(rng.randn(2, 7, 8, 12), jnp.float32)
    ws = jnp.asarray(rng.randn(3, 3, 5, 12), jnp.float32)
    pi, space = (2, 2), (5, 6)
    w_t = jnp.swapaxes(ws[::-1, ::-1], -1, -2)
    full = lax.conv_general_dilated(
        dy1, w_t, window_strides=(1, 1), padding=[(2, 2), (2, 2)],
        dimension_numbers=conv_dimension_numbers(2))
    ref = full[:, pi[0]:pi[0] + space[0], pi[1]:pi[1] + space[1]]
    out = sd_input_grad_fused(dy1, ws, pi, space)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_filter_grad_kernel_vs_lax():
    """sd_filter_grad_fused (in-kernel P_I activation pad) == the
    batch/channel-exchanged lax VALID conv over jnp.pad(x)."""
    from jax import lax
    from repro.core.deconv import conv_dimension_numbers
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(3, 6, 5, 4), jnp.float32)
    pi, kt = (2, 1), (3, 2)
    xp = jnp.pad(x, ((0, 0), (pi[0], pi[0]), (pi[1], pi[1]), (0, 0)))
    o1h = xp.shape[1] - kt[0] + 1
    o1w = xp.shape[2] - kt[1] + 1
    dy1 = jnp.asarray(rng.randn(3, o1h, o1w, 8), jnp.float32)
    lhs = xp.transpose(3, 1, 2, 0)
    rhs = dy1.transpose(1, 2, 0, 3)
    ref = lax.conv_general_dilated(
        lhs, rhs, window_strides=(1, 1), padding="VALID",
        dimension_numbers=conv_dimension_numbers(2)).transpose(1, 2, 0, 3)
    out = sd_filter_grad_fused(x, dy1, kt, pi)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("shape_x,shape_w,s,pad,op", [
    ((2, 5, 6, 3), (4, 4, 3, 2), 2, 1, 0),
    ((1, 4, 4, 2), (5, 5, 2, 3), 2, ((0, 2), (1, 1)), 1),
    ((2, 9, 3), (5, 3, 2), 2, 1, 1),                 # rank 1 lowering
])
def test_fused_backward_grad_parity(shape_x, shape_w, s, pad, op):
    """jax.grad through the fused backend == native autodiff: dx, dw
    and db all run on (or through) the Pallas kernels."""
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(*shape_x), jnp.float32)
    w = jnp.asarray(rng.randn(*shape_w), jnp.float32)
    b = jnp.asarray(rng.randn(shape_w[-1]), jnp.float32)
    plan = sd.plan(w.shape, s, pad, backend="fused", output_padding=op)

    def loss(xx, ww, bb):
        return jnp.sum(sd.conv_transpose(plan, xx, ww, bb) ** 2)

    def ref_loss(xx, ww, bb):
        return jnp.sum(
            (native_deconv(xx, ww, s, pad, output_padding=op) + bb) ** 2)

    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(x, w, b)
    for g, r, name in zip(got, want, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_fused_backward_bf16():
    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(1, 5, 5, 4), jnp.bfloat16)
    w = jnp.asarray(rng.randn(4, 4, 4, 2), jnp.bfloat16)
    plan = sd.plan(w.shape, 2, 1, backend="fused")
    plan_x = sd.plan(w.shape, 2, 1, backend="xla")
    g = jax.grad(lambda ww: jnp.sum(
        sd.conv_transpose(plan, x, ww).astype(jnp.float32) ** 2))(w)
    r = jax.grad(lambda ww: jnp.sum(
        sd.conv_transpose(plan_x, x, ww).astype(jnp.float32) ** 2))(w)
    assert g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(r, np.float32),
                               rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# In-kernel H/W pad of the 3-D lowering's per-tap convs
# ---------------------------------------------------------------------------

def test_conv2d_valid_pad_matches_prepadded():
    x = _rand((2, 5, 6, 4), seed=30)
    w = _rand((2, 2, 4, 8), seed=31)
    pad = ((1, 1), (1, 1))
    ref = sd_conv2d_valid(jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0))),
                          w)
    out = sd_conv2d_valid(x, w, pad=pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_zero_copy_empty_output_dim():
    """A zero-extent output dim (passes padding validation on size-1
    inputs) must return the empty array like the pad+crop reference,
    not crash the launch geometry."""
    x = _rand((2, 1, 1, 2), seed=40)
    w = _rand((5, 5, 2, 3), seed=41)
    pads = ((2, 2), (1, 4))          # out_space == (1, 0)
    zc, pc = _both_paths(x, w, 1, pads)
    assert zc.shape == pc.shape == (2, 1, 0, 3)
    ref = native_deconv(x, w, 1, pads)
    assert ref.shape == zc.shape


def test_filter_grad_channel_tiles_fit_vmem():
    """Unpinned filter-grad launches size their tiles to the dw kernel's
    own footprint (a band of padded input rows at full width plus the
    same rows of dy1), not the conv-band model — wide layers must not
    resolve to full channel depth and all O1 rows."""
    from repro.kernels.autotune import VMEM_BUDGET
    from repro.kernels.ops import _dw_tiles
    o1, wp = (130, 130), 132              # fst/artgan-scale extent
    tr, tcin, tcout = _dw_tiles(o1, wp, (3, 3), 128, 256)
    assert (tr, tcin, tcout) != (130, 128, 256)
    assert 4 * ((tr + 2) * wp * tcin + tr * o1[1] * tcout) <= VMEM_BUDGET
    assert tcin == 128 and tcout in (128, 256)      # 128-lane tiles
    # and grads stay exact under the clamped tiling (forced small
    # budget exercises multi-band row accumulation)
    import repro.kernels.autotune as at
    rng = np.random.RandomState(9)
    x = jnp.asarray(rng.randn(2, 6, 5, 8), jnp.float32)
    pi, kt = (1, 1), (2, 2)
    dy1 = jnp.asarray(rng.randn(2, 7, 6, 12), jnp.float32)
    want = sd_filter_grad_fused(x, dy1, kt, pi,
                                plan=KernelPlan(th=1, tcin=8, tcout=12))
    orig = at.VMEM_BUDGET
    try:
        at.VMEM_BUDGET = 1 << 12          # force one-row bands
        got = sd_filter_grad_fused(x, dy1, kt, pi)
    finally:
        at.VMEM_BUDGET = orig
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
