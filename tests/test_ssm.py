"""SSM/recurrent block invariants: parallel forms == recurrent references."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.ssm import (MLSTMState, init_mamba, init_mamba_state,
                              init_mlstm, init_mlstm_state, init_slstm,
                              init_slstm_state, mamba_forward, mamba_step,
                              mlstm_chunkwise, mlstm_recurrent, slstm_forward)


def test_mamba_parallel_equals_stepwise():
    key = jax.random.PRNGKey(0)
    p = init_mamba(key, 32, expand=2, d_state=8, d_conv=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 17, 32)) * 0.5
    st0 = init_mamba_state(2, p, jnp.float32)
    y_full, st_full = mamba_forward(p, x, st0, chunk=4)
    st2 = init_mamba_state(2, p, jnp.float32)
    ys = []
    for t in range(17):
        yt, st2 = mamba_step(p, x[:, t:t + 1], st2)
        ys.append(yt)
    y_step = jnp.concatenate(ys, 1)
    np.testing.assert_allclose(np.asarray(y_full), np.asarray(y_step),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_full.ssm), np.asarray(st2.ssm),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(S=st.integers(2, 24), chunk=st.sampled_from([2, 4, 8, 16]),
       seed=st.integers(0, 99))
def test_property_mlstm_chunkwise(S, chunk, seed):
    key = jax.random.PRNGKey(seed)
    p = init_mlstm(key, 16, n_heads=2)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, S, 16)) * 0.5
    st0 = init_mlstm_state(2, p, 2)
    y_rec, st_rec = mlstm_recurrent(p, x, st0, n_heads=2)
    y_chk, st_chk = mlstm_chunkwise(p, x, st0, n_heads=2, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y_rec), np.asarray(y_chk),
                               rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st_rec.c), np.asarray(st_chk.c),
                               rtol=1e-3, atol=1e-3)


def test_mlstm_state_continuation():
    key = jax.random.PRNGKey(3)
    p = init_mlstm(key, 16, n_heads=2)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 12, 16))
    st0 = init_mlstm_state(1, p, 2)
    y_all, _ = mlstm_chunkwise(p, x, st0, n_heads=2, chunk=4)
    y1, st1 = mlstm_chunkwise(p, x[:, :8], st0, n_heads=2, chunk=4)
    y2, _ = mlstm_chunkwise(p, x[:, 8:], st1, n_heads=2, chunk=4)
    np.testing.assert_allclose(np.asarray(y_all),
                               np.asarray(jnp.concatenate([y1, y2], 1)),
                               rtol=1e-4, atol=1e-4)


def test_slstm_continuation():
    key = jax.random.PRNGKey(5)
    p = init_slstm(key, 24, n_heads=4)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 13, 24)) * 0.5
    y_all, _ = slstm_forward(p, x)
    st = init_slstm_state(2, p)
    y1, st1 = slstm_forward(p, x[:, :7], st)
    y2, _ = slstm_forward(p, x[:, 7:], st1)
    np.testing.assert_allclose(np.asarray(y_all),
                               np.asarray(jnp.concatenate([y1, y2], 1)),
                               rtol=1e-4, atol=1e-4)


def test_mamba_long_context_constant_state():
    """The long_500k cell premise: state size independent of seq length."""
    p = init_mamba(jax.random.PRNGKey(0), 16, expand=2, d_state=4, d_conv=4)
    s1 = init_mamba_state(1, p, jnp.float32)
    _, s1 = mamba_forward(p, jnp.ones((1, 8, 16)), s1, chunk=4)
    s2 = init_mamba_state(1, p, jnp.float32)
    _, s2 = mamba_forward(p, jnp.ones((1, 64, 16)), s2, chunk=4)
    assert s1.ssm.shape == s2.ssm.shape == (1, 32, 4)
