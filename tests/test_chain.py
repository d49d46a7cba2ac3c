"""Static-calibrated int8 activation chaining (PR 10).

Covers the chained protocol end to end: the scale-folding algebra of
the chained epilogue against the unchained static path on every paper
deconv layer (both execution backends, interpret mode), saturating
clamp semantics on adversarial inputs, calibration determinism, the
engine's chain wiring (consecutive-deconv pairs only, first/last
boundary rules), bucket-pad exactness under static scales, the
zero-recompile checkpoint swap with chained plans, and — the whole
point — the asserted absence of any per-sample amax reduction in the
chained hot path's jaxpr.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import sd
from repro.core.accounting import BENCHMARKS, LayerSpec, NetworkSpec
from repro.core.deconv import same_deconv_pads
from repro.core.quant import (QMAX, amax_stat, load_calib, quantize_static,
                              save_calib, scale_from_amax)
from repro.core.ssim import ssim
from repro.models.generative import GenerativeModel, build
from repro.launch.serve_gen import GenServer, reduced_spec, serve_async

_PAPER_LAYERS = [(net, layer) for net in BENCHMARKS
                 for layer in BENCHMARKS[net]().deconv_layers()]


# ---------------------------------------------------------------------------
# core/quant: static quantization + saturating clamp on adversarial input.
# ---------------------------------------------------------------------------

def test_quantize_static_matches_dynamic_inside_range():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 5, 4))
    scale = scale_from_amax(jnp.max(jnp.abs(x)))
    q = quantize_static(x, scale)
    assert q.dtype == jnp.int8
    err = np.abs(np.asarray(x) - np.asarray(q).astype(np.float32) * scale)
    assert err.max() <= scale / 2 + 1e-7
    # exact zeros stay exactly zero
    assert int(quantize_static(jnp.zeros((4,)), scale)[0]) == 0


def test_quantize_static_saturating_clamp_never_wraps():
    """Out-of-calibration activations clamp to +/-127 — a wrapping int8
    cast would flip sign (e.g. 130 -> -126), which is catastrophically
    wrong; saturation is merely lossy."""
    scale = 1.0 / QMAX                      # calibrated for |x| <= 1
    adv = jnp.array([2.0, -2.0, 1e30, -1e30, jnp.inf, -jnp.inf, 0.0, 1.0])
    q = np.asarray(quantize_static(adv, scale))
    np.testing.assert_array_equal(q, [127, -127, 127, -127, 127, -127,
                                      0, 127])
    # NaN cannot masquerade as signal: quantizes to 0
    assert int(quantize_static(jnp.array([jnp.nan]), scale)[0]) == 0
    # the value JUST past the range must saturate, not wrap negative
    assert int(quantize_static(jnp.array([1.0 + 1e-2]), scale)[0]) == 127


def test_chained_epilogue_requant_saturates_in_kernel():
    """The fused kernel's int8 epilogue clamps too: shrink sx_out so
    the activated tile overflows the int8 range — every code must land
    on +/-127, never wrap."""
    w = jnp.ones((4, 4, 4, 4)) * 0.5
    x = jnp.ones((1, 4, 4, 4))
    for backend in ("fused", "xla"):
        p = sd.plan(w.shape, 2, 1, backend=backend, act="relu",
                    dtype="int8").bind(w, bias=jnp.zeros((4,)))
        sx_in = scale_from_amax(jnp.max(jnp.abs(x)))
        c = p.with_chain(sx_in=sx_in, sx_out=1e-6, chain_out=True)
        q = np.asarray(sd.execute(c, x))
        assert q.dtype == np.int8
        assert q.max() <= 127 and q.min() >= -127
        assert (np.abs(q) == 127).any()     # it DID saturate


def test_amax_stat_policies():
    x = jnp.concatenate([jnp.ones((999,)), jnp.array([100.0])])
    assert float(amax_stat(x, "max")) == 100.0
    # the 99th percentile ignores the single outlier
    assert float(amax_stat(x, "pct", pct=99.0)) == pytest.approx(1.0)
    with pytest.raises(ValueError, match="policy"):
        amax_stat(x, "median")


def test_calib_cache_round_trip(tmp_path):
    p = str(tmp_path / "calib.json")
    save_calib("dcgan/max", {"d1": 0.5, "d2": 0.25}, path=p)
    save_calib("sngan/max", {"u1": 0.125}, path=p)
    assert load_calib("dcgan/max", path=p) == {"d1": 0.5, "d2": 0.25}
    assert load_calib("sngan/max", path=p) == {"u1": 0.125}
    assert load_calib("missing/max", path=p) is None
    # overwrite wins per key, other keys untouched
    save_calib("dcgan/max", {"d1": 1.0}, path=p)
    assert load_calib("dcgan/max", path=p) == {"d1": 1.0}
    assert load_calib("sngan/max", path=p) == {"u1": 0.125}


# ---------------------------------------------------------------------------
# Chained-vs-unchained parity: every paper deconv layer, both backends.
# The chained epilogue folds 1/sx_out into scale+bias and re-quantizes;
# dequantizing its int8 output must land on the unchained static output
# to within the re-quantization half-step.
# ---------------------------------------------------------------------------

def _bound_static(layer, key, backend):
    k, s, cin, cout = layer.k, layer.s, layer.cin, layer.cout
    pads = (same_deconv_pads(k, s) if layer.padding == "same"
            else layer.pad)
    kw, kb = jax.random.split(key)
    w = jax.random.normal(kw, (k, k, cin, cout)) * 0.05
    bias = jax.random.normal(kb, (cout,)) * 0.1
    return sd.plan((k, k, cin, cout), s, pads, backend=backend,
                   act="relu", dtype="int8").bind(w, bias=bias)


@pytest.mark.parametrize("net,layer", _PAPER_LAYERS,
                         ids=[f"{n}-{l.name}" for n, l in _PAPER_LAYERS])
def test_chained_matches_unchained_static(net, layer):
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (1, *layer.in_hw, layer.cin))
    sx_in = scale_from_amax(jnp.max(jnp.abs(x)))
    for backend in ("fused", "xla"):
        p = _bound_static(layer, jax.random.PRNGKey(2), backend)
        ref = np.asarray(sd.execute(p.with_chain(sx_in=sx_in), x))
        sx_out = scale_from_amax(float(np.abs(ref).max()))
        q = np.asarray(sd.execute(
            p.with_chain(sx_in=sx_in, sx_out=sx_out, chain_out=True), x))
        assert q.dtype == np.int8
        # dequantized chained output == unchained static output up to
        # the chained epilogue's own rounding half-step
        np.testing.assert_allclose(q.astype(np.float32) * sx_out, ref,
                                   atol=sx_out / 2 + 1e-6)


def test_chained_layer_feeds_next_layer_exactly():
    """Layer i's int8 chained output consumed by layer i+1 (sx_in ==
    sx_out) must equal quantize_static(layer i's f32 static output)
    fed to the same layer i+1 — the chained tensor IS the next layer's
    quantized input, no re-quantization drift."""
    l1, l2 = list(BENCHMARKS["dcgan"]().deconv_layers())[1:3]
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (2, *l1.in_hw, l1.cin))
    for backend in ("fused", "xla"):
        p1 = _bound_static(l1, jax.random.PRNGKey(4), backend)
        p2 = _bound_static(l2, jax.random.PRNGKey(5), backend)
        s0 = scale_from_amax(jnp.max(jnp.abs(x)))
        y1 = sd.execute(p1.with_chain(sx_in=s0), x)       # f32 static
        s1 = scale_from_amax(jnp.max(jnp.abs(y1)))
        # chained: int8 straight through HBM
        q1 = sd.execute(p1.with_chain(sx_in=s0, sx_out=s1,
                                      chain_out=True), x)
        ya = np.asarray(sd.execute(p2.with_chain(sx_in=s1), q1))
        # unchained: f32 out, next layer re-quantizes statically
        yb = np.asarray(sd.execute(p2.with_chain(sx_in=s1), y1))
        # identical up to the one half-step the chain rounds at s1
        denom = max(np.abs(yb).max(), 1e-6)
        assert np.abs(ya - yb).max() / denom < 0.02


# ---------------------------------------------------------------------------
# Plan plumbing: with_chain validation, pytree structure, leaf counts.
# ---------------------------------------------------------------------------

def test_with_chain_validation():
    w, b = jnp.ones((4, 4, 8, 6)), jnp.ones((6,))
    pf = sd.plan((4, 4, 8, 6), 2, 1, dtype="native").bind(w, bias=b)
    with pytest.raises(ValueError, match="int8"):
        pf.with_chain(sx_in=0.1)
    p8 = sd.plan((4, 4, 8, 6), 2, 1, dtype="int8", act="relu").bind(
        w, bias=b)
    with pytest.raises(ValueError, match="sx_out"):
        p8.with_chain(sx_in=0.1, chain_out=True)
    pt = sd.plan((4, 4, 8, 6), 2, 1, dtype="int8", act="tanh").bind(
        w, bias=b)
    with pytest.raises(ValueError, match="tanh"):
        pt.with_chain(sx_in=0.1, sx_out=0.1, chain_out=True)
    # tanh may still HEAD a chain (static input, f32 output)
    assert pt.with_chain(sx_in=0.1).sx_in is not None


def test_chain_pytree_structure_and_leaves():
    """sx scales are leaves (recalibration never retraces); chain_out
    is aux (the output dtype is static, so it must key the jit cache).
    Unchained plans keep their historical leaf counts."""
    w, b = jnp.ones((4, 4, 8, 6)), jnp.ones((6,))
    p = sd.plan((4, 4, 8, 6), 2, 1, dtype="int8", act="relu").bind(
        w, bias=b)
    assert len(jax.tree_util.tree_leaves(p)) == 3       # ws, bias, wscale
    c = p.with_chain(sx_in=0.1, sx_out=0.2, chain_out=True)
    assert len(jax.tree_util.tree_leaves(c)) == 5       # + sx_in, sx_out
    tu = jax.tree_util
    assert (tu.tree_structure(c)
            != tu.tree_structure(p.with_chain(sx_in=0.1, sx_out=0.2)))
    # same chain config, different scale VALUES: same treedef — a
    # recalibrated plan reuses the compiled executable
    c2 = p.with_chain(sx_in=0.3, sx_out=0.4, chain_out=True)
    assert tu.tree_structure(c) == tu.tree_structure(c2)
    # unbind clears the chain state with the other leaves
    u = c.unbind()
    assert u.sx_in is None and u.sx_out is None and not u.chain_out


# ---------------------------------------------------------------------------
# Engine wiring: calibration -> chained plans, boundary rules.
# ---------------------------------------------------------------------------

def _int8_model(spec):
    m = GenerativeModel(spec, deconv_impl="sd_kernel",
                        engine_backend="xla", engine_dtype="int8")
    params = m.init(jax.random.PRNGKey(0))
    return m, params


def test_calibration_deterministic_under_fixed_seed():
    m, params = _int8_model(reduced_spec())
    s1 = m.calibrate(params, n=8, seed=0)
    s2 = m.calibrate(params, n=8, seed=0)
    assert s1 == s2 and set(s1) == {"d1", "d2"}
    assert all(v > 0 for v in s1.values())
    s3 = m.calibrate(params, n=8, seed=1)
    assert s3 != s1                         # the seed is really used


def test_engine_chains_consecutive_deconvs_only():
    """dcgan: d1->d2->d3 chain; the last deconv never chains out (its
    f32 output feeds the model tanh) but does consume int8 input."""
    m, params = _int8_model(BENCHMARKS["dcgan"]())
    m.calibrate(params, n=4, seed=0)
    plans = m.engine.plans()
    names = [l.name for l in m.spec.deconv_layers()]
    for name in names[:-1]:
        assert plans[name].chain_out, name
        assert plans[name].sx_out is not None
    last = plans[names[-1]]
    assert not last.chain_out and last.sx_out is None
    assert last.sx_in is not None           # consumes the chained int8
    # chained output scale i == input scale i+1: the HBM tensor needs
    # exactly one interpretation
    for a, b in zip(names[:-1], names[1:]):
        assert float(plans[a].sx_out) == float(plans[b].sx_in)
    # chained plans' tiles key under _q8out geometries
    geoms = {n: m.engine.layer_geom(l, qout=plans[l.name].chain_out)
             for n, l in zip(names, m.spec.deconv_layers())}
    for name in names[:-1]:
        assert "_q8out" in geoms[name].key()
    assert "_q8out" not in geoms[names[-1]].key()


def test_intervening_conv_breaks_the_chain():
    """A non-deconv layer between two deconvs (segnet's mid-net conv)
    forces f32 across that boundary: neither deconv chains out."""
    spec = NetworkSpec("chainbreak", [
        LayerSpec("fc", 16, 4 * 4 * 8, name="project"),
        LayerSpec("deconv", 8, 8, k=4, s=2, in_hw=(4, 4), name="d1"),
        LayerSpec("conv", 8, 8, k=3, s=1, in_hw=(8, 8), name="mid"),
        LayerSpec("deconv", 8, 3, k=4, s=2, in_hw=(8, 8), name="d2"),
    ])
    m, params = _int8_model(spec)
    m.calibrate(params, n=4, seed=0)
    plans = m.engine.plans()
    assert not plans["d1"].chain_out and not plans["d2"].chain_out
    # both still quantize statically (no amax on the hot path)
    assert plans["d1"].sx_in is not None
    assert plans["d2"].sx_in is not None
    # and the chained forward still matches the f32 reference closely
    x = jax.random.normal(jax.random.PRNGKey(1), m.input_shape(2))
    mf = GenerativeModel(spec, deconv_impl="sd_kernel",
                         engine_backend="xla")
    pf = mf.init(jax.random.PRNGKey(0))
    ref = np.asarray(mf.apply(pf, x))
    got = np.asarray(m.apply(params, x))
    assert np.abs(got - ref).max() < 0.1


def test_calibrate_binds_a_never_bound_engine():
    """calibrate() on a model whose engine was never bound (params came
    from another instance) must leave CHAINED plans visible immediately
    — regression: set_calibration only stored the scales and plans()
    came back empty until the first apply()."""
    spec = reduced_spec()
    m = GenerativeModel(spec, deconv_impl="sd_kernel",
                        engine_backend="xla", engine_dtype="int8")
    params = GenerativeModel(spec, "native").init(jax.random.PRNGKey(0))
    m.calibrate(params, n=4, seed=0)
    plans = m.engine.plans()
    assert plans and any(p.chain_out for p in plans.values())


def test_set_calibration_rejects_float_engine():
    m = GenerativeModel(reduced_spec(), deconv_impl="sd_kernel",
                        engine_backend="xla")
    m.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="int8"):
        m.engine.set_calibration({"d1": 0.1})
    with pytest.raises(ValueError, match="int8"):
        m.calibrate({}, n=2)


@pytest.mark.parametrize("net", ["dcgan", "sngan"])
def test_chained_int8_ssim_vs_f32_engine(net):
    """Whole paper generators at full size, calibrated on 32 latents:
    at least one plan chains int8 activations through HBM, and the
    chained engine keeps SSIM >= 0.999 against the f32 engine."""
    f32m = build(net, "sd_kernel")
    params = f32m.init(jax.random.PRNGKey(0))
    i8c = build(net, "sd_kernel", engine_dtype="int8")
    i8c.calibrate(params, n=32, seed=7)
    assert any(p.chain_out for p in i8c.engine.plans().values())
    z = jax.random.normal(jax.random.PRNGKey(1), f32m.input_shape(4))
    s = float(ssim(f32m.apply(params, z), i8c.apply(params, z)))
    assert s >= 0.999, f"{net}: chained int8 SSIM {s:.4f} < 0.999"


# ---------------------------------------------------------------------------
# Hot-path purity: NO per-sample amax reduction in the chained jaxpr.
# ---------------------------------------------------------------------------

def test_chained_jaxpr_has_no_amax_reduction():
    server = GenServer(nets=["g"], specs={"g": reduced_spec()},
                       dtype="int8", max_batch=4, calib=8)
    model, params = server.model("g")
    lean, plans = server._serving_args("g", 4)
    x = jnp.zeros((4, *model.input_shape(1)[1:]))
    jaxpr = str(jax.make_jaxpr(model.apply_with_plans)(lean, plans, x))
    assert "reduce_max" not in jaxpr
    # positive control: the dynamic int8 path DOES carry the reduction
    # (this is what makes the assertion above meaningful).  Pull the
    # plans straight off the engine — _serving_args caches on the
    # params object and would hand back the chained ones.
    model.engine.set_calibration(None)
    dyn_plans = model.engine.plans_for_batch(4)
    dyn = str(jax.make_jaxpr(model.apply_with_plans)(lean, dyn_plans, x))
    assert "reduce_max" in dyn


# ---------------------------------------------------------------------------
# Serving: bucket-pad exactness + zero-recompile swap with chained plans.
# ---------------------------------------------------------------------------

def test_bucket_pad_rows_exact_under_static_scales():
    """Static scales are sample-independent by construction, so the
    zero rows a bucket pads with cannot perturb real samples — the
    padded launch is BIT-identical on the real rows."""
    server = GenServer(nets=["g"], specs={"g": reduced_spec()},
                       dtype="int8", max_batch=4, calib=8)
    model, params = server.model("g")
    lean, plans = server._serving_args("g", 4)
    x = jax.random.normal(jax.random.PRNGKey(2),
                          (2, *model.input_shape(1)[1:]))
    xp = jnp.concatenate([x, jnp.zeros((2, *x.shape[1:]))])
    fn = server.compiled("g", 4)
    y_pad = np.asarray(fn(lean, plans, xp))[:2]
    lean2, plans2 = server._serving_args("g", 2)
    y = np.asarray(server.compiled("g", 2)(lean2, plans2, x))
    np.testing.assert_array_equal(y, y_pad)


def test_chained_checkpoint_swap_zero_recompile():
    spec = reduced_spec()
    server = GenServer(nets=["g"], specs={"g": spec}, dtype="int8",
                       max_batch=4, calib=8)
    reqs = server.random_requests("g", 4)
    serve_async(server, reqs)
    assert server.compile_count == 1
    plans = server.model("g")[0].engine.plans()
    assert any(p.chain_out for p in plans.values())  # really chained
    # hot-swap a new checkpoint: the engine rebinds AND keeps the
    # calibration, so the swapped plans chain too — same treedef, same
    # executable, zero recompiles
    new_params = GenerativeModel(spec, "native").init(
        jax.random.PRNGKey(11))
    server.swap_checkpoint("g", new_params)
    swapped = server.model("g")[0].engine.plans()
    assert any(p.chain_out for p in swapped.values())
    results, _ = serve_async(server, reqs)
    assert server.compile_count == 1
    # swapped chained outputs track the f32 reference of the NEW params
    ref_model = GenerativeModel(spec, "native")
    x = jnp.stack([jnp.asarray(r.latent) for r in reqs])
    ref = np.asarray(ref_model.apply(new_params, x))
    out = np.stack([np.asarray(results[r.rid]) for r in reqs])
    assert np.abs(out - ref).max() < 0.1
