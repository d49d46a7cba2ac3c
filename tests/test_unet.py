"""The pix2pix U-Net (skip joins, per-request instance norm) against its
plain reference, on every execution path, at a small size.

The reference is ``bench/references/unet.py`` (plain ``lax`` at
"highest", no program code), run on a 4-level U-Net at 32x32 with the
published widths divided by 8 and seeded, non-trivial weights.  Paths:
the plain executors, the engine's cached plans (fused kernel in
interpret mode, and XLA), the plans passed as arguments, the traced
functional path, and ``GenServer`` behind ``ContinuousScheduler`` with
a partly filled bucket.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accounting import (LEAKY_SLOPE, LayerSpec, NetworkSpec,
                                   pix2pix, unet)
from repro.launch.serve_gen import GenRequest, GenServer, serve_async
from repro.models.generative import GenerativeModel, instance_norm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = unet(32, (8, 16, 32, 64), (32, 16, 8, 3), name="unet-small")
# f32 on the CPU: the paths read 2.5e-07 to 3.5e-07 from the reference,
# its three-bfloat16-pass control 1.4e-05.
TOL = 2e-6


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "unet_reference", os.path.join(ROOT, "bench", "references",
                                       "unet.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_reference()


def config_of(spec):
    """A configuration file's layer list for ``spec``."""
    layers = []
    for l in spec.layers:
        d = {"kind": l.kind, "name": l.name, "cin": l.cin, "cout": l.cout,
             "k": l.k, "s": l.s, "in_hw": list(l.in_hw),
             "padding": l.padding, "act": l.act, "norm": l.norm,
             "bias": l.bias, "skip": l.skip}
        if l.act == "leaky_relu":
            d["slope"] = LEAKY_SLOPE
        layers.append(d)
    return {"layers": layers, "final_tanh": spec.final_tanh}


CONFIG = config_of(SPEC)


@pytest.fixture(scope="module")
def case():
    params = REF.init(CONFIG["layers"], jax.random.PRNGKey(3))
    x = jax.random.uniform(jax.random.PRNGKey(4), (3, 32, 32, 3))
    return params, x, np.asarray(REF.forward(CONFIG, params, x))


def rel_err(out, ref):
    return float(np.linalg.norm(np.asarray(out) - ref) / np.linalg.norm(ref))


def _lean(params):
    """What the plans do not hold, as ``GenServer`` passes it."""
    deconv = {l.name for l in SPEC.deconv_layers()}
    return {n: ({k: v for k, v in p.items() if k not in ("w", "scale", "b")}
                if n in deconv else p) for n, p in params.items()}


PATHS = {
    "native": lambda p, x: GenerativeModel(SPEC, "native").apply(p, x),
    "sd": lambda p, x: GenerativeModel(SPEC, "sd").apply(p, x),
    "engine_fused": lambda p, x: GenerativeModel(
        SPEC, "sd_kernel", engine_backend="fused").apply(p, x),
    "engine_xla": lambda p, x: GenerativeModel(
        SPEC, "sd_kernel", engine_backend="xla").apply(p, x),
    "traced_functional": lambda p, x: jax.jit(GenerativeModel(
        SPEC, "sd_kernel", engine_backend="fused").apply)(p, x),
}


def _with_plans(params, x):
    m = GenerativeModel(SPEC, "sd_kernel", engine_backend="fused")
    m.engine.bind(params)
    return jax.jit(m.apply_with_plans)(_lean(params), m.engine.plans(), x)


PATHS["apply_with_plans_fused"] = _with_plans


@pytest.mark.parametrize("path", sorted(PATHS))
def test_paths_match_reference(case, path):
    params, x, want = case
    assert rel_err(PATHS[path](params, x), want) < TOL


def test_control_misses_the_tolerance(case):
    """Three bfloat16 passes in the reference's place fail ``TOL``: the
    comparison sees a precision loss."""
    params, x, want = case
    assert rel_err(REF.forward(CONFIG, params, x, "high"), want) > 4 * TOL


def _server(backend="fused", max_batch=4):
    return GenServer(nets=["u"], specs={"u": SPEC}, backend=backend,
                     max_batch=max_batch)


def test_server_partly_filled_bucket(case):
    """Three requests in a bucket of four through the scheduler: each
    output is the reference's, and the launch record counts the bytes
    the joins wrote at the bucket's batch."""
    params, x, want = case
    server = _server()
    server.swap_checkpoint("u", params)
    reqs = [GenRequest(rid=i, net="u", latent=x[i]) for i in range(3)]
    results, stats = serve_async(server, reqs)
    out = np.stack([np.asarray(results[i]) for i in range(3)])
    assert rel_err(out, want) < TOL
    assert stats["launches"] == 1
    joins = 4 * (4 * 4 * 64 + 8 * 8 * 32 + 16 * 16 * 16) * 4
    assert SPEC.join_elems() * 4 * 4 == joins == server.join_bytes("u", 4)
    assert stats["join_bytes"] == joins


def test_request_alone_or_batched_is_the_same(case):
    """Instance-norm statistics stay inside each request: a request's
    output does not move when the bucket also holds other requests (at
    100 times its scale) or padding rows."""
    params, x, _ = case
    server = _server(backend="xla", max_batch=8)
    server.swap_checkpoint("u", params)
    alone = np.asarray(server.run_group("u", [x[0]]))[0]
    others = [100.0 * x[1], -x[2], x[2] + 3.0]
    batched = np.asarray(server.run_group("u", [x[0], *others]))[0]
    padded = np.asarray(server.run_group("u", [x[0], x[1]] * 2 + [x[2]]))[0]
    np.testing.assert_allclose(batched, alone, rtol=0, atol=2e-6)
    np.testing.assert_allclose(padded, alone, rtol=0, atol=2e-6)


def test_instance_norm_is_per_request_and_channel():
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4, 2)) * 7.0 + 2.0
    y = np.asarray(instance_norm(h, jnp.ones(2), jnp.zeros(2)))
    np.testing.assert_allclose(y.mean(axis=(1, 2)), 0.0, atol=1e-5)
    np.testing.assert_allclose(y.std(axis=(1, 2)), 1.0, atol=1e-3)
    one = np.asarray(instance_norm(h[1:2], jnp.ones(2), jnp.zeros(2)))
    np.testing.assert_allclose(one[0], y[1], atol=1e-6)


def test_chain_stops_at_norms_and_joins():
    """Int8 chaining links two deconvs only over a plain edge: d1 has a
    norm, d2 feeds a join, d4 is kept for a join and d5 feeds one; only
    d3 -> d4 chains."""
    spec = NetworkSpec("chains", [
        LayerSpec("conv", 3, 8, k=4, s=2, in_hw=(32, 32), name="e1"),
        LayerSpec("conv", 8, 8, k=4, s=2, in_hw=(16, 16), name="e2"),
        LayerSpec("conv", 8, 8, k=4, s=2, in_hw=(8, 8), name="e3"),
        LayerSpec("deconv", 8, 8, k=4, s=2, in_hw=(4, 4), name="d1",
                  norm="instance"),
        LayerSpec("deconv", 8, 8, k=4, s=2, in_hw=(8, 8), name="d2"),
        LayerSpec("deconv", 16, 8, k=4, s=2, in_hw=(16, 16), name="d3",
                  skip="e1"),
        LayerSpec("deconv", 8, 8, k=4, s=2, in_hw=(32, 32), name="d4"),
        LayerSpec("deconv", 8, 8, k=3, s=1, in_hw=(64, 64), name="d5"),
        LayerSpec("deconv", 16, 3, k=4, s=2, in_hw=(64, 64), name="d6",
                  skip="d4"),
    ])
    assert [spec.plain_edge(i) for i in range(3, 9)] == [
        False, False, True, False, False, False]
    m = GenerativeModel(spec, "sd_kernel", engine_backend="xla",
                        engine_dtype="int8")
    params = m.init(jax.random.PRNGKey(0))
    m.calibrate(params, n=2, seed=0,
                latents=jax.random.uniform(jax.random.PRNGKey(1),
                                           (2, 32, 32, 3)))
    assert m.engine._chain_next() == {"d3": "d4"}
    plans = m.engine.plans()
    assert [n for n, p in plans.items() if p.chain_out] == ["d3"]
    # The plans' epilogues apply an activation only over a plain edge.
    assert {n: p.act for n, p in plans.items()} == {
        "d1": "linear", "d2": "linear", "d3": "relu", "d4": "linear",
        "d5": "linear", "d6": "linear"}


@pytest.mark.parametrize("bad,match", [
    (dict(skip="nowhere"), "not an earlier layer"),
    (dict(skip="e1", cin=8), "does not give its input"),
    (dict(act="gelu"), "act"),
])
def test_spec_rejects_a_bad_layer(bad, match):
    d2 = dict(kind="deconv", cin=16, cout=3, k=4, s=2, in_hw=(8, 8),
              name="d2", skip="e1")
    d2.update(bad)
    with pytest.raises(ValueError, match=match):
        NetworkSpec("bad", [
            LayerSpec("conv", 3, 8, k=4, s=2, in_hw=(16, 16), name="e1"),
            LayerSpec("conv", 8, 8, k=3, s=1, in_hw=(8, 8), name="c1"),
            LayerSpec(**d2)])


def test_pix2pix_graph():
    spec = pix2pix()
    assert [l.skip for l in spec.layers if l.kind == "deconv"] == [
        None, "e7", "e6", "e5", "e4", "e3", "e2", "e1"]
    assert [l.name for l in spec.layers if l.norm] == [
        "e2", "e3", "e4", "e5", "e6", "e7", "u1", "u2", "u3", "u4", "u5",
        "u6", "u7"]
    assert [l.name for l in spec.layers if l.bias] == ["u8"]
    # joins of u2..u8 per image, f32: 16.07 MB
    assert spec.join_elems() * 4 == 16_072_704
