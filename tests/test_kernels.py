"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles.

Shape/dtype sweeps + hypothesis property tests, per the deliverable spec.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import native_deconv, same_deconv_pads, split_filters
from repro.core.deconv import depth_to_space
from repro.kernels.ops import (sd_conv2d_valid, sd_deconv_fused,
                               sd_deconv_kernel, ws_to_ocmajor)
from repro.kernels.ref import conv2d_valid_ref, sd_deconv_fused_ref
from repro.kernels.sd_conv import (phase_packed, sd_conv_pallas,
                                   sd_fused_pallas)
from repro.sd.plan import to_ocmajor


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


CONV_SHAPES = [
    # (B, H, W, Cin, Cout, KT)
    (1, 8, 8, 4, 4, 2),
    (2, 10, 9, 8, 16, 3),
    (1, 5, 12, 3, 5, 1),
    (2, 9, 7, 16, 8, 3),
    (1, 12, 6, 32, 8, 2),
]


@pytest.mark.parametrize("B,H,W,Cin,Cout,KT", CONV_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sd_conv_kernel_sweep(B, H, W, Cin, Cout, KT, dtype):
    x = _rand((B, H, W, Cin), seed=1, dtype=dtype)
    w = _rand((KT, KT, Cin, Cout), seed=2, dtype=dtype)
    out = sd_conv2d_valid(x, w)
    ref = conv2d_valid_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


def test_sd_conv_channel_tiling():
    """Cin/Cout grid tiling accumulates correctly."""
    x = _rand((1, 10, 8, 16), seed=3)
    w = _rand((3, 3, 16, 8), seed=4)
    ref = conv2d_valid_ref(x, w)
    out = sd_conv_pallas(x, w, th=4, tcout=4, tcin=4, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_fused_channel_tiling():
    """Fused kernel: Cin accumulation via VMEM scratch + Cout grid tiling
    agree with the untiled launch."""
    x = _rand((2, 7, 6, 12), seed=11)
    w = _rand((5, 5, 12, 8), seed=12)
    s = 2
    ref = native_deconv(x, w, s, 1)
    for th, tcin, tcout in [(2, 4, 2), (4, 12, 4), (2, 6, 8)]:
        from repro.kernels.autotune import KernelPlan
        out = sd_deconv_kernel(x, w, s, 1,
                               plan=KernelPlan(th=th, tcin=tcin, tcout=tcout))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


def test_fused_epilogue_bias_and_act():
    """In-VMEM bias + activation epilogue == composition outside."""
    x = _rand((1, 6, 6, 4), seed=21)
    w = _rand((4, 4, 4, 6), seed=22)
    bias = jnp.asarray(np.random.RandomState(23).randn(6), jnp.float32)
    s = 2
    base = native_deconv(x, w, s, 1) + bias
    for act, fn in [("linear", lambda y: y),
                    ("relu", lambda y: jnp.maximum(y, 0)),
                    ("tanh", jnp.tanh)]:
        out = sd_deconv_kernel(x, w, s, 1, bias=bias, act=act)
        np.testing.assert_allclose(np.asarray(out), np.asarray(fn(base)),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,s,pad", [
    (5, 2, "same"), (4, 2, 1), (3, 2, "same"), (5, 3, 2), (2, 2, 0),
    (7, 4, 3), (5, 1, 2),
    # s=3 / s=4 beyond the original set, incl. K not divisible by s
    (3, 3, 1), (6, 3, "same"), (4, 3, 0), (5, 3, "same"),
    (4, 4, 2), (5, 4, "same"), (8, 4, 3),
])
def test_fused_deconv_kernel(K, s, pad):
    pads = same_deconv_pads(K, s) if pad == "same" else pad
    x = _rand((2, 7, 6, 4), seed=K)
    w = _rand((K, K, 4, 3), seed=s)
    out = sd_deconv_kernel(x, w, s, pads)
    ref = native_deconv(x, w, s, pads)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,s,pads", [
    (4, 2, ((1, 0), (0, 2))),
    (5, 2, ((0, 3), (2, 1))),
    (5, 3, ((2, 0), (1, 3))),
    (3, 2, ((1, 2), (0, 0))),
])
def test_fused_deconv_asymmetric_padding(K, s, pads):
    """User padding with different top/bottom/left/right crop amounts."""
    x = _rand((1, 6, 8, 5), seed=K + 10)
    w = _rand((K, K, 5, 4), seed=s + 10)
    out = sd_deconv_kernel(x, w, s, pads)
    ref = native_deconv(x, w, s, pads)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("K,s", [(5, 2), (4, 2), (5, 3), (7, 4)])
def test_fused_deconv_bf16(K, s):
    """bf16 inputs, f32 MXU accumulation: compare against the f32
    reference computed from the same (bf16-rounded) operands."""
    x32 = _rand((2, 6, 5, 8), seed=K, dtype=jnp.float32)
    w32 = _rand((K, K, 8, 4), seed=s, dtype=jnp.float32)
    xb, wb = x32.astype(jnp.bfloat16), w32.astype(jnp.bfloat16)
    out = sd_deconv_kernel(xb, wb, s, 1)
    assert out.dtype == jnp.bfloat16
    ref = native_deconv(xb.astype(jnp.float32), wb.astype(jnp.float32), s, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_fused_kernel_padding_validation():
    """The fused kernel path rejects oversized padding like core impls."""
    x = _rand((1, 4, 4, 2))
    w = _rand((3, 3, 2, 2))
    with pytest.raises(ValueError, match="too large"):
        sd_deconv_kernel(x, w, 2, 3)


def test_fused_matches_unfused_path():
    """Kernel's in-VMEM interleave == conv + depth_to_space composition."""
    x = _rand((1, 9, 9, 6), seed=7)
    w = _rand((4, 4, 6, 5), seed=8)
    s = 2
    ws = split_filters(w, s)
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))  # P_I = 1
    ref = sd_deconv_fused_ref(xp, ws, s)
    out = sd_deconv_fused(xp, ws_to_ocmajor(ws, s), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_generative_model_kernel_impl():
    """deconv_impl='sd_kernel' end-to-end through DCGAN."""
    from repro.models.generative import build
    key = jax.random.PRNGKey(0)
    m_ref = build("dcgan", "native")
    m_ker = build("dcgan", "sd_kernel")
    params = m_ref.init(key)
    z = jax.random.normal(jax.random.PRNGKey(1), m_ref.input_shape(2))
    a, b = m_ref.apply(params, z), m_ker.apply(params, z)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    K=st.integers(2, 6), s=st.integers(2, 3),
    H=st.integers(3, 7), Cin=st.sampled_from([1, 3, 8]),
    Cout=st.sampled_from([1, 4]), seed=st.integers(0, 999),
)
def test_property_fused_kernel(K, s, H, Cin, Cout, seed):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(1, H, H + 1, Cin), jnp.float32)
    w = jnp.asarray(rng.randn(K, K, Cin, Cout), jnp.float32)
    p = min(1, K - 1)
    out = sd_deconv_kernel(x, w, s, p)
    ref = native_deconv(x, w, s, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Phase-packed lanes (Cout tiles under 128 lanes) vs the ref.py oracle
# ---------------------------------------------------------------------------

# name: (x spatial, s, pad, crop, out_space, th, tw).  "s2-res-op" has a
# residual crop in both dims and two output_padding rows past the
# support; "1d-s3" is the rank-1 lowering's (1, s) interleave.
PACKED_GEOMS = {
    "s2-res-op": ((5, 6), (2, 2), ((1, 1), (1, 1)), (1, 1), (13, 12), 2, 3),
    "s3": ((5, 6), (3, 3), ((1, 1), (1, 1)), (2, 0), (17, 21), 2, 0),
    "1d-s3": ((1, 6), (1, 3), ((0, 0), (2, 2)), (0, 4), (1, 22), 1, 4),
}
PACKED_CASES = ([(g, m) for g in PACKED_GEOMS
                 for m in ("float32", "bfloat16")]
                + [("s2-res-op", m) for m in ("int8", "int8-static",
                                              "int8-chained")]
                + [("1d-s3", "int8-static")])


def _packed_ref(x, ws, s, pad, crop, out_space, bias, act, scale):
    """``sd_deconv_fused_ref`` on the padded input, dequantized per
    n-major channel, cropped (and zero-extended) to the output, then
    bias and activation."""
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), *pad, (0, 0)))
    y = conv2d_valid_ref(xp, ws.astype(jnp.float32))
    if scale is not None:
        cout = ws.shape[-1] // (s[0] * s[1])
        y = y * jnp.swapaxes(scale.reshape(-1, cout, s[0] * s[1]), 1, 2
                             ).reshape(-1, 1, 1, ws.shape[-1])
    full = depth_to_space(y, s)
    (ch, cw), (oh, ow) = crop, out_space
    full = jnp.pad(full, ((0, 0), (0, max(0, ch + oh - full.shape[1])),
                          (0, max(0, cw + ow - full.shape[2])), (0, 0)))
    out = full[:, ch:ch + oh, cw:cw + ow] + bias
    return jnp.tanh(out) if act == "tanh" else jnp.maximum(out, 0.0)


@pytest.mark.parametrize("cout", [3, 16, 64, 128])
@pytest.mark.parametrize("geom,mode", PACKED_CASES)
def test_fused_packed_phases_match_ref(geom, mode, cout):
    """The fused kernel against ``ref.sd_deconv_fused_ref`` for Cout
    3, 16 and 64 (phases packed in the lanes) and 128 (one phase per
    lane tile, the unpacked control): f32, bf16, and int8 with dynamic
    or static dequant scales and with the chained int8 output."""
    hw, s, pad, crop, out_space, th, tw = PACKED_GEOMS[geom]
    ss, cin = s[0] * s[1], 8
    kt = (1 if s[0] == 1 else 2, 3 if s[0] == 1 else 2)
    assert phase_packed(ss, cout) == (cout < 128)
    rng = np.random.RandomState(cout + ss)
    x = rng.randn(2, *hw, cin)
    ws = rng.randn(*kt, cin, ss * cout) * 0.3
    bias = jnp.asarray(rng.randn(cout), jnp.float32)
    act = "relu" if mode.startswith("int8") else "tanh"
    scale = out_dtype = None
    if mode.startswith("int8"):
        x = np.clip(np.round(x * 40), -127, 127).astype(np.int8)
        ws = np.clip(np.round(ws * 200), -127, 127).astype(np.int8)
        rows = 2 if mode == "int8" else 1
        scale = jnp.asarray(rng.uniform(2e-3, 2e-2, (rows, ss * cout)),
                            jnp.float32)
        out_dtype = "int8" if mode == "int8-chained" else None
    dtype = jnp.int8 if mode.startswith("int8") else jnp.dtype(mode)
    x, ws = jnp.asarray(x, dtype), jnp.asarray(ws, dtype)
    out = sd_fused_pallas(x, to_ocmajor(ws, s), s, bias=bias, act=act,
                          scale=scale, th=th, tw=tw, pad=pad, crop=crop,
                          out_space=out_space, out_dtype=out_dtype,
                          interpret=True)
    ref = _packed_ref(x, ws, s, pad, crop, out_space, bias, act, scale)
    assert out.shape == (2, *out_space, cout)
    if out_dtype == "int8":
        assert out.dtype == jnp.int8
        ref = jnp.clip(jnp.round(ref), -127, 127)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), atol=1)
        return
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)
