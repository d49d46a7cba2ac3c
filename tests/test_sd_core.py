"""Core Split-Deconvolution correctness: SD == NZP == native, bit-exact.

Unit tests over the paper's cases + hypothesis property tests over the
full (K, s, p, H, W, C) space.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import assume, given, settings
from hypothesis import strategies as st

from repro.core import (chang_deconv, deconv_output_shape, depth_to_space,
                        dilate_input, native_deconv, nzp_deconv,
                        same_deconv_pads, sd_deconv, sd_deconv_presplit,
                        sd_geometry, shi_deconv, space_to_depth,
                        split_filters, ssim)

jax.config.update("jax_enable_x64", False)


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


CASES = [
    # (K, s, p, H, W, Cin, Cout) — includes every benchmark's geometry
    (5, 2, 0, 8, 8, 4, 3),      # DCGAN (K % s != 0)
    (4, 2, 1, 4, 4, 8, 4),      # SNGAN / GP-GAN / ArtGAN
    (3, 2, 0, 6, 5, 3, 2),      # MDE / FST
    (5, 1, 2, 7, 7, 2, 2),      # ArtGAN stride-1 deconv
    (5, 3, 2, 4, 6, 2, 3),      # K % s == 2
    (2, 2, 0, 3, 3, 1, 1),      # minimal
    (7, 4, 3, 5, 4, 2, 2),      # large stride, non-divisible
    (1, 1, 0, 4, 4, 3, 3),      # pointwise
]


@pytest.mark.parametrize("K,s,p,H,W,Cin,Cout", CASES)
def test_sd_equals_native(K, s, p, H, W, Cin, Cout):
    x = _rand((2, H, W, Cin), seed=K * 7 + s)
    w = _rand((K, K, Cin, Cout), seed=K + s)
    ref = native_deconv(x, w, s, p)
    out = sd_deconv(x, w, s, p)
    assert ref.shape == out.shape
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,s,p,H,W,Cin,Cout", CASES)
def test_nzp_equals_native(K, s, p, H, W, Cin, Cout):
    x = _rand((1, H, W, Cin), seed=1)
    w = _rand((K, K, Cin, Cout), seed=2)
    np.testing.assert_allclose(np.asarray(native_deconv(x, w, s, p)),
                               np.asarray(nzp_deconv(x, w, s, p)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("K,s", [(5, 2), (4, 2), (3, 2), (7, 3), (6, 4)])
def test_same_padding_doubles(K, s):
    """TF-SAME transposed conv must produce out = in * s exactly."""
    pads = same_deconv_pads(K, s)
    x = _rand((1, 9, 7, 3))
    w = _rand((K, K, 3, 2))
    ref = native_deconv(x, w, s, pads)
    out = sd_deconv(x, w, s, pads)
    assert ref.shape == (1, 9 * s, 7 * s, 2)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_presplit_matches_inline():
    """Offline filter splitting (the deployed path) == inline."""
    x, w = _rand((2, 6, 6, 4)), _rand((5, 5, 4, 8), seed=3)
    ws = split_filters(w, 2)
    assert ws.shape == (3, 3, 4, 4 * 8)
    a = sd_deconv(x, w, 2, 1)
    b = sd_deconv_presplit(x, ws, (5, 5), 2, 1)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_split_filters_preserve_weights():
    """Every original weight appears exactly once; rest are zeros."""
    w = _rand((5, 5, 2, 3))
    ws = split_filters(w, 2)
    assert np.isclose(np.abs(np.asarray(ws)).sum(),
                      np.abs(np.asarray(w)).sum(), rtol=1e-6)
    nz = int((np.asarray(ws) != 0).sum())
    assert nz == 5 * 5 * 2 * 3  # compressed-SD param count (Table 3)


def test_sd_geometry_paper_eqs():
    (kt, _), (pk, _), (pi, _) = sd_geometry(5, 2)
    assert (kt, pk, pi) == (3, 1, 2)   # K_T=ceil(5/2), P_K=2*3-5, P_I=K_T-1
    (kt, _), (pk, _), (pi, _) = sd_geometry(4, 2)
    assert (kt, pk, pi) == (2, 0, 1)


def test_depth_space_roundtrip():
    x = _rand((2, 6, 8, 12))
    np.testing.assert_array_equal(
        np.asarray(space_to_depth(depth_to_space(x, 2), 2)), np.asarray(x))


def test_dilate_input():
    x = jnp.arange(4, dtype=jnp.float32).reshape(1, 2, 2, 1)
    d = dilate_input(x, 2)
    assert d.shape == (1, 3, 3, 1)
    assert float(d[0, 0, 0, 0]) == 0.0 and float(d[0, 2, 2, 0]) == 3.0
    assert float(d[0, 1, 1, 0]) == 0.0  # inserted zero


def test_wrong_baselines_divergence():
    """Paper Table 4: SD exact; Shi/Chang wrong when K % s != 0."""
    x, w = _rand((1, 16, 16, 8)), _rand((5, 5, 8, 3), seed=5)
    pads = same_deconv_pads(5, 2)
    ref = native_deconv(x, w, 2, pads)
    assert np.allclose(np.asarray(sd_deconv(x, w, 2, pads)),
                       np.asarray(ref), atol=1e-4)
    assert not np.allclose(np.asarray(shi_deconv(x, w, 2, pads)),
                           np.asarray(ref), atol=1e-2)
    assert not np.allclose(np.asarray(chang_deconv(x, w, 2, pads)),
                           np.asarray(ref), atol=1e-2)


@pytest.mark.parametrize("bad_pad", [
    3,                      # symmetric, > K-1 = 2
    (1, 3),                 # per-axis, width too large
    ((0, 3), (1, 1)),       # asymmetric, one side too large
])
def test_padding_too_large_raises_consistently(bad_pad):
    """native / NZP / SD (and the paper-faithful variant) must reject the
    same bad paddings with the same error, not silently diverge."""
    x = _rand((1, 4, 4, 2))
    w = _rand((3, 3, 2, 2), seed=1)
    from repro.core.deconv import sd_deconv_paper
    for impl in (native_deconv, nzp_deconv, sd_deconv, sd_deconv_paper):
        with pytest.raises(ValueError, match="too large for kernel"):
            impl(x, w, 2, bad_pad)


def test_valid_padding_accepted_by_all():
    """Boundary case p = K-1 is legal everywhere and still agrees."""
    x = _rand((1, 5, 5, 2))
    w = _rand((3, 3, 2, 2), seed=2)
    ref = native_deconv(x, w, 2, 2)
    for impl in (nzp_deconv, sd_deconv):
        np.testing.assert_allclose(np.asarray(impl(x, w, 2, 2)),
                                   np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_ssim_identity_and_degradation():
    a = jnp.tanh(_rand((1, 32, 32, 3)))
    assert float(ssim(a, a)) == pytest.approx(1.0, abs=1e-5)
    b = jnp.roll(a, 1, axis=1)
    assert float(ssim(a, b)) < 0.9


def test_grad_flows_through_sd():
    """SD must be trainable: gradients flow to the original filter."""
    x = _rand((1, 5, 5, 2))
    w = _rand((4, 4, 2, 3), seed=7)

    def loss(w_):
        return jnp.sum(sd_deconv(x, w_, 2, 1) ** 2)

    g_sd = jax.grad(loss)(w)
    g_ref = jax.grad(lambda w_: jnp.sum(native_deconv(x, w_, 2, 1) ** 2))(w)
    np.testing.assert_allclose(np.asarray(g_sd), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Property-based: the invariant over the whole space.
# ---------------------------------------------------------------------------

@settings(max_examples=60, deadline=None)
@given(
    K=st.integers(1, 7), s=st.integers(1, 4),
    H=st.integers(2, 9), W=st.integers(2, 9),
    cin=st.integers(1, 4), cout=st.integers(1, 4),
    pfrac=st.floats(0.0, 1.0), seed=st.integers(0, 2**16),
)
def test_property_sd_equals_native(K, s, H, W, cin, cout, pfrac, seed):
    p = int(pfrac * (K - 1))
    oh, ow = deconv_output_shape((H, W), K, s, p)
    assume(oh > 0 and ow > 0)     # degenerate zero-size outputs excluded
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(1, H, W, cin), jnp.float32)
    w = jnp.asarray(rng.randn(K, K, cin, cout), jnp.float32)
    ref = native_deconv(x, w, s, p)
    out = sd_deconv(x, w, s, p)
    assert ref.shape == out.shape == \
        (1, *deconv_output_shape((H, W), K, s, p), cout)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=2e-4, atol=2e-4)


@settings(max_examples=25, deadline=None)
@given(K=st.integers(2, 6), s=st.integers(2, 4), seed=st.integers(0, 999))
def test_property_split_is_lossless(K, s, seed):
    """Filter splitting is a permutation-with-zero-fill of the weights."""
    rng = np.random.RandomState(seed)
    w = jnp.asarray(rng.randn(K, K, 2, 2), jnp.float32)
    ws = np.asarray(split_filters(w, s))
    kt = -(-K // s)
    assert ws.shape == (kt, kt, 2, s * s * 2)
    assert int((ws != 0).sum()) <= K * K * 2 * 2
    assert np.isclose(np.sort(np.abs(ws[ws != 0]).ravel()).sum(),
                      np.sort(np.abs(np.asarray(w)).ravel()).sum(), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(dtype=st.sampled_from(["float32", "bfloat16"]),
       K=st.sampled_from([3, 4, 5]), s=st.sampled_from([2, 3]))
def test_property_dtype_sweep(dtype, K, s):
    x = _rand((1, 6, 6, 4)).astype(dtype)
    w = _rand((K, K, 4, 4), seed=11).astype(dtype)
    ref = np.asarray(native_deconv(x, w, s, 1), np.float32)
    out = np.asarray(sd_deconv(x, w, s, 1), np.float32)
    tol = 1e-4 if dtype == "float32" else 5e-2
    np.testing.assert_allclose(ref, out, rtol=tol, atol=tol)
