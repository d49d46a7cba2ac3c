"""Int8 split-filter inference path (core/quant + int8 plans/kernels).

Covers the quantization contract end to end: per-channel round-trip
error bounds, the fused int8 Pallas kernel against the dequantized-f32
reference on every paper deconv layer, BN-scale folding commuting with
quantization, dtype-distinct plan/compile cache keys, and int8 serving
rebinds without recompilation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sd
from repro.core.accounting import BENCHMARKS
from repro.core.deconv import same_deconv_pads
from repro.core.quant import (QMAX, dequantize, quantize,
                              quantize_act, quantize_channelwise)
from repro.kernels.autotune import ConvGeom
from repro.core.ssim import ssim
from repro.models.generative import GenerativeModel, build
from repro.launch.serve_gen import GenServer, reduced_spec, serve_async


# ---------------------------------------------------------------------------
# core/quant: round-trip bounds.
# ---------------------------------------------------------------------------

def test_per_tensor_round_trip_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (7, 13)) * 3.0
    q, s = quantize(x)
    assert q.dtype == jnp.int8
    # symmetric: exact zeros survive, max error is half a step
    err = np.abs(np.asarray(dequantize(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-7
    assert float(jnp.max(jnp.abs(q))) <= QMAX


def test_per_channel_round_trip_bound():
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 4, 16, 24))
    # give channels wildly different ranges: per-tensor would clip
    w = w * (10.0 ** jnp.linspace(-2, 2, 24))
    q, scales = quantize_channelwise(w, axis=-1)
    assert q.dtype == jnp.int8 and scales.shape == (24,)
    err = np.abs(np.asarray(w) - np.asarray(q).astype(np.float32)
                 * np.asarray(scales))
    # each channel is bounded by ITS half-step — the point of
    # per-channel scales
    assert (err <= np.asarray(scales) / 2 + 1e-7).all()
    # per-tensor quantization of the same array violates the
    # small-channel bound (sanity that the test discriminates)
    qt, st = quantize(w)
    err_t = np.abs(np.asarray(w) - np.asarray(qt).astype(np.float32)
                   * float(st))
    assert err_t.max() > float(np.asarray(scales).min()) / 2


def test_per_sample_activation_scales():
    x = jnp.stack([jnp.ones((5, 5, 3)) * 0.01,
                   jnp.ones((5, 5, 3)) * 100.0,
                   jnp.zeros((5, 5, 3))])
    q, s = quantize_act(x)
    assert q.dtype == jnp.int8 and s.shape == (3,)
    # each sample quantized against its own amax: tiny sample keeps
    # full resolution next to a huge one
    assert int(q[0].max()) == 127 and int(q[1].max()) == 127
    # all-zero sample: no NaN/inf scale, exact zeros back
    assert np.isfinite(float(s[2]))
    np.testing.assert_array_equal(np.asarray(q[2]), 0)


def test_zero_padding_rows_cannot_perturb_real_samples():
    """Bucketed serving pads batches with zero rows; per-sample scales
    mean the padded batch quantizes real samples identically."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 4, 8))
    xp = jnp.concatenate([x, jnp.zeros((2, 4, 4, 8))])
    q1, s1 = quantize_act(x)
    q2, s2 = quantize_act(xp)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2[:2]))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2[:2]))


# ---------------------------------------------------------------------------
# Fused int8 kernel vs the dequantized-f32 reference — every paper layer.
# The two paths share the exact same quantized operands (same bind, same
# quantize_act); they may differ only by int32-vs-f32 accumulation order.
# ---------------------------------------------------------------------------

_PAPER_LAYERS = [(net, layer) for net in BENCHMARKS
                 for layer in BENCHMARKS[net]().deconv_layers()]


def _bound_pair(layer, key, dtype):
    k, s, cin, cout = layer.k, layer.s, layer.cin, layer.cout
    pads = (same_deconv_pads(k, s) if layer.padding == "same"
            else layer.pad)
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (k, k, cin, cout)) * 0.05
    bias = jax.random.normal(kb, (cout,)) * 0.1
    shape = (k, k, cin, cout)
    fused = sd.plan(shape, s, pads, backend="fused", act="relu",
                    dtype=dtype).bind(w, bias=bias)
    xla = sd.plan(shape, s, pads, backend="xla", act="relu",
                  dtype=dtype).bind(w, bias=bias)
    return fused, xla


@pytest.mark.parametrize("net,layer", _PAPER_LAYERS,
                         ids=[f"{n}-{l.name}" for n, l in _PAPER_LAYERS])
def test_int8_fused_matches_dequant_f32_reference(net, layer):
    fused, xla = _bound_pair(layer, jax.random.PRNGKey(3), "int8")
    x = jax.random.normal(jax.random.PRNGKey(4),
                          (1, *layer.in_hw, layer.cin))
    got = np.asarray(sd.execute(fused, x))      # int8 x int8 -> int32
    ref = np.asarray(sd.execute(xla, x))        # same quant, f32 conv
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-3)


def test_int8_execute_close_to_f32_engine():
    """End-to-end sanity that quantization noise stays quantization-
    sized: int8 vs native-dtype plans on one mid-size layer."""
    layer = list(BENCHMARKS["dcgan"]().deconv_layers())[1]
    f8, _ = _bound_pair(layer, jax.random.PRNGKey(5), "int8")
    f32, _ = _bound_pair(layer, jax.random.PRNGKey(5), "native")
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, *layer.in_hw, layer.cin))
    y8 = np.asarray(sd.execute(f8, x))
    y32 = np.asarray(sd.execute(f32, x))
    denom = np.abs(y32).max()
    assert np.abs(y8 - y32).max() / denom < 0.05


SSIM_MIN = 0.99     # int8 engine vs f32 engine, whole generator


@pytest.mark.parametrize("net", ["dcgan", "sngan"])
def test_int8_engine_ssim_vs_f32_engine(net):
    """Whole paper generators at full size: the int8 engine (dynamic
    per-sample activation scales) keeps SSIM >= SSIM_MIN against the
    f32 engine on the same weights and latents."""
    f32m = build(net, "sd_kernel")
    params = f32m.init(jax.random.PRNGKey(0))
    i8m = build(net, "sd_kernel", engine_dtype="int8")
    z = jax.random.normal(jax.random.PRNGKey(1), f32m.input_shape(4))
    s = float(ssim(f32m.apply(params, z), i8m.apply(params, z)))
    assert s >= SSIM_MIN, f"{net}: int8 SSIM {s:.4f} < {SSIM_MIN}"


# ---------------------------------------------------------------------------
# BN-scale folding commutes with quantization.
# ---------------------------------------------------------------------------

def test_scale_fold_commutes_with_quantization():
    """bind() folds the BN scale into the split filters *before*
    quantizing.  For exactly-representable per-channel scales (powers
    of two) the int8 codes must be bit-identical to the unscaled bind,
    with the fold carried entirely by wscale."""
    w = jax.random.normal(jax.random.PRNGKey(7), (4, 4, 8, 6))
    bias = jnp.zeros((6,))
    gamma = 2.0 ** jnp.arange(-2, 4)            # exact in f32
    mk = lambda: sd.plan((4, 4, 8, 6), 2, 1, backend="xla",
                         dtype="int8")
    p0 = mk().bind(w, bias=bias)
    pg = mk().bind(w, scale=gamma, bias=bias)
    np.testing.assert_array_equal(np.asarray(p0.ws), np.asarray(pg.ws))
    # n-major channel c = phase*cout + oc -> gamma tiles across phases
    np.testing.assert_allclose(
        np.asarray(pg.wscale),
        np.asarray(p0.wscale) * np.tile(np.asarray(gamma), p0.phases),
        rtol=1e-6)


def test_int8_bind_matches_f32_bn_fold_numerics():
    """The int8 path with a folded BN scale lands on the f32 BN-folded
    output, up to quantization noise — the fold itself adds no error."""
    w = jax.random.normal(jax.random.PRNGKey(8), (4, 4, 8, 6)) * 0.1
    gamma = jnp.linspace(0.5, 2.0, 6)
    bias = jax.random.normal(jax.random.PRNGKey(9), (6,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 6, 6, 8))
    mk = lambda d: sd.plan((4, 4, 8, 6), 2, 1, backend="xla", act="relu",
                           dtype=d)
    y32 = np.asarray(sd.execute(mk("native").bind(w, scale=gamma,
                                                  bias=bias), x))
    y8 = np.asarray(sd.execute(mk("int8").bind(w, scale=gamma,
                                               bias=bias), x))
    assert np.abs(y8 - y32).max() / max(np.abs(y32).max(), 1e-6) < 0.05


# ---------------------------------------------------------------------------
# dtype-distinct cache keys (autotune plan cache + jit compile cache).
# ---------------------------------------------------------------------------

def test_conv_geom_key_distinct_per_dtype():
    g32 = ConvGeom.from_deconv(1, 8, 8, 16, 8, 4, 2, padding=1)
    g8 = ConvGeom.from_deconv(1, 8, 8, 16, 8, 4, 2, padding=1,
                              dtype="int8")
    assert g32.key() != g8.key()
    assert "int8" in g8.key() and "int8" not in g32.key()
    # int8 operand tiles are modelled 4x smaller, f32 accumulator same
    assert g8.operand_itemsize == 1 and g32.operand_itemsize == 4


def test_plan_pytree_structure_distinct_per_dtype():
    """DeconvPlan.dtype lives in aux_data, so jitting execute() on an
    int8 plan can never reuse a float plan's executable (and vice
    versa) — the pytree structures differ."""
    mk = lambda d: sd.plan((4, 4, 8, 6), 2, 1, dtype=d)
    s32 = jax.tree_util.tree_structure(mk("native"))
    s8 = jax.tree_util.tree_structure(mk("int8"))
    assert s32 != s8
    # bound: int8 carries the wscale leaf, float plans flatten without it
    w, b = jnp.ones((4, 4, 8, 6)), jnp.ones((6,))
    assert len(jax.tree_util.tree_leaves(mk("native").bind(w, bias=b))) == 2
    assert len(jax.tree_util.tree_leaves(mk("int8").bind(w, bias=b))) == 3


def test_plan_rejects_unknown_dtype_and_training():
    with pytest.raises(ValueError):
        sd.plan((4, 4, 8, 6), 2, 1, dtype="int4")
    p = sd.plan((4, 4, 8, 6), 2, 1, dtype="int8")
    with pytest.raises(ValueError, match="inference-only"):
        sd.conv_transpose(p, jnp.ones((1, 6, 6, 8)),
                          jnp.ones((4, 4, 8, 6)))


# ---------------------------------------------------------------------------
# Serving: int8 engines rebind new weights without recompiling.
# ---------------------------------------------------------------------------

def test_serve_gen_int8_rebind_without_recompile():
    spec = reduced_spec()
    server = GenServer(nets=["g"], specs={"g": spec}, dtype="int8",
                       max_batch=4)
    assert server.engine_dtype == "int8" and server.dtype_name == "int8"
    reqs = server.random_requests("g", 4)
    results, _ = serve_async(server, reqs)
    assert server.compile_count == 1

    model, _ = server.model("g")
    new_params = GenerativeModel(spec, "native").init(
        jax.random.PRNGKey(11))
    model._engine.bind(new_params)
    server._models["g"] = (model, new_params)

    results, _ = serve_async(server, reqs)
    assert server.compile_count == 1    # same executable, new int8 plans
    # outputs track the f32 native reference up to quantization noise
    ref_model = GenerativeModel(spec, "native")
    x = jnp.stack([jnp.asarray(r.latent) for r in reqs])
    ref = np.asarray(ref_model.apply(new_params, x))
    out = np.stack([np.asarray(results[r.rid]) for r in reqs])
    assert np.abs(out - ref).max() < 0.1
    assert np.abs(out - ref).mean() < 0.02


def test_serve_gen_int8_and_f32_cells_coexist():
    """One process, same net+bucket, both dtypes: distinct compile
    cells, no cross-contamination."""
    spec = reduced_spec()
    s32 = GenServer(nets=["g"], specs={"g": spec}, max_batch=4)
    s8 = GenServer(nets=["g"], specs={"g": spec}, dtype="int8",
                   max_batch=4)
    k32 = ("g", 4, s32.dtype_name)
    k8 = ("g", 4, "int8")
    assert k32 != k8
    serve_async(s32, s32.random_requests("g", 4))
    serve_async(s8, s8.random_requests("g", 4))
    assert k32 in s32._compiled and k8 in s8._compiled
