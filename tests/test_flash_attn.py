"""Pallas flash-attention kernel vs pure-jnp oracle (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attn import flash_attention
from repro.kernels.ref import flash_attention_ref


def _qkv(b, h, s, d, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d) * 0.3, dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,h,s,d,bq,bk", [
    (1, 2, 128, 32, 64, 64),
    (2, 3, 256, 64, 64, 128),
    (1, 1, 192, 16, 64, 64),
])
def test_flash_matches_ref(causal, b, h, s, d, bq, bk):
    q, k, v = _qkv(b, h, s, d)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16():
    q, k, v = _qkv(1, 2, 128, 32, seed=3, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


@settings(max_examples=10, deadline=None)
@given(s=st.sampled_from([64, 128, 192]), d=st.sampled_from([16, 32]),
       seed=st.integers(0, 99))
def test_property_flash(s, d, seed):
    q, k, v = _qkv(1, 2, s, d, seed=seed)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_matches_model_blockwise():
    """The pure-XLA blockwise_attention (used by the models/dry-run) and
    the Pallas kernel implement the same schedule — outputs must agree."""
    from repro.models.layers import blockwise_attention
    rng = np.random.RandomState(5)
    b, s, hq, hkv, d = 1, 128, 4, 2, 16
    q = jnp.asarray(rng.randn(b, s, hq, d) * 0.3, jnp.float32)
    k = jnp.asarray(rng.randn(b, s, hkv, d) * 0.3, jnp.float32)
    v = jnp.asarray(rng.randn(b, s, hkv, d) * 0.3, jnp.float32)
    o_xla = blockwise_attention(q, k, v, causal=True, window=None,
                                q_offset=0, block=64)
    # GQA-expand for the kernel
    g = hq // hkv
    ke = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1)
    ve = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1)
    qe = q.transpose(0, 2, 1, 3).reshape(b, hkv, g, s, d)
    qe = qe.reshape(b, hq, s, d)  # (B,Hq,S,D) matching kv expansion order
    o_ker = flash_attention(qe, ke, ve, causal=True, bq=64, bk=64)
    o_ker = o_ker.transpose(0, 2, 1, 3)      # (B,S,Hq,D)
    np.testing.assert_allclose(np.asarray(o_xla, np.float32),
                               np.asarray(o_ker, np.float32),
                               rtol=2e-4, atol=2e-4)
