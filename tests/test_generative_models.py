"""Whole-network equivalence across deconv implementations + training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import native_deconv, same_deconv_pads
from repro.core.deconv import sd_deconv_paper
from repro.models.generative import build

ALL_NETS = ["dcgan", "sngan", "artgan", "gpgan", "mde", "fst"]


@pytest.mark.parametrize("name", ALL_NETS)
def test_all_impls_agree(name):
    key = jax.random.PRNGKey(0)
    ref_model = build(name, "native")
    params = ref_model.init(key)
    scale = 0.1 if name in ("gpgan", "mde", "fst") else 1.0
    x = jax.random.normal(jax.random.PRNGKey(1),
                          ref_model.input_shape(2)) * scale
    ref = ref_model.apply(params, x)
    assert not bool(jnp.isnan(ref).any())
    for impl in ("sd", "nzp"):
        out = build(name, impl).apply(params, x)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-4, atol=1e-4)


def test_sd_paper_sequential_equals_grouped():
    """Algorithm-2-faithful (s^2 sequential convs) == grouped formulation."""
    rng = np.random.RandomState(0)
    for K, s in [(5, 2), (4, 2), (3, 2), (5, 3)]:
        x = jnp.asarray(rng.randn(2, 6, 7, 4), jnp.float32)
        w = jnp.asarray(rng.randn(K, K, 4, 3), jnp.float32)
        pads = same_deconv_pads(K, s)
        a = native_deconv(x, w, s, pads)
        b = sd_deconv_paper(x, w, s, pads)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_gan_training_descends():
    """A few G/D steps on the small DCGAN reduce both losses sanely."""
    import examples.train_dcgan as td
    d_hist, g_hist = td.main(["--steps", "8", "--small"])
    assert len(d_hist) == 8
    assert all(np.isfinite(v) for v in d_hist + g_hist)


def test_grad_flows_through_whole_sd_generator():
    m = build("sngan", "sd")
    params = m.init(jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), m.input_shape(2))

    def loss(p):
        return jnp.mean(m.apply(p, z) ** 2)

    g = jax.grad(loss)(params)
    total = sum(float(jnp.abs(x).sum()) for x in jax.tree.leaves(g))
    assert np.isfinite(total) and total > 0


@pytest.mark.parametrize("name", ["dcgan", "mde"])
def test_each_layer_runs_in_its_named_scope(name):
    """The compiled forward names every layer's ops by the layer's scope
    (``op_name`` metadata), the name a device trace groups them by."""
    import re
    model = build(name, "sd")
    params = model.init(jax.random.PRNGKey(0))
    x = jnp.zeros(model.input_shape(1))
    text = jax.jit(model.apply).lower(params, x).compile().as_text()
    scopes = set(re.findall(r'op_name="jit\(apply\)/([^/"]+)/', text))
    assert {layer.name for layer in model.spec.layers} <= scopes
