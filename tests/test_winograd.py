"""Winograd fast-algorithm backend tests.

Three layers of coverage, mirroring how the backend is built:

* the Toom-Cook transform matrices and the offline filter transform
  (pure math, verified against the correlation identity);
* kernel/functional parity against the exact ``native_deconv`` across
  the paper's (K, s) geometries — at the *pinned* per-tap tolerance
  (``winograd.WINO_TOL``) the registry metadata and the CI gate read;
* the autotuner as algorithm selector: ``algo``-tagged cache keys,
  stale-cache back-compat, ``best_algo`` semantics, and the fused
  engine switching individual layers to winograd plans by measured
  cost only.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import accounting, native_deconv, same_deconv_pads
from repro.core.deconv import split_filters
from repro.engine import SDEngine
from repro.kernels import autotune, winograd
from repro.kernels.autotune import ConvGeom, KernelPlan
from repro.models.generative import GenerativeModel
from repro.sd.plan import to_ocmajor
import repro.sd as sd


def _rand(shape, seed=0, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape), dtype)


def _rel_err(out, ref):
    ref = np.asarray(ref, np.float32)
    out = np.asarray(out, np.float32)
    return np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-6)


# ---------------------------------------------------------------------------
# Transform math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r", [1, 2, 3, 4, 5])
def test_winograd_matrices_correlation_identity(r):
    """F(m, r) matrices satisfy y = A^T[(G g) .x. (B^T d)] where y is
    the plain correlation — for every supported tap count."""
    m = winograd.output_tile(r)
    at, g, bt = winograd.winograd_matrices(m, r)
    alpha = m + r - 1
    assert at.shape == (m, alpha)
    assert g.shape == (alpha, r)
    assert bt.shape == (alpha, alpha)
    rng = np.random.RandomState(r)
    d = rng.randn(alpha).astype(np.float64)
    gg = rng.randn(r).astype(np.float64)
    y = at.astype(np.float64) @ (
        (g.astype(np.float64) @ gg) * (bt.astype(np.float64) @ d))
    ref = np.array([sum(d[o + k] * gg[k] for k in range(r))
                    for o in range(m)])
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)


def test_winograd_matrices_rejects_unconstructible():
    with pytest.raises(ValueError, match="no point set"):
        winograd.winograd_matrices(6, 6)


def test_transform_filters_matches_GgGT():
    """The offline filter transform is U = G g G^T per (cin, phase
    channel), each tap dim expanded to alpha."""
    kt, cin, nc = 3, 4, 6
    ws = _rand((kt, kt, cin, nc), seed=3)
    u = winograd.transform_filters(ws)
    m = winograd.output_tile(kt)
    _, g, _ = winograd.winograd_matrices(m, kt)
    alpha = m + kt - 1
    assert u.shape == (alpha, alpha, cin, nc)
    ref = np.einsum("ak,khcn,bh->abcn", g, np.asarray(ws), g)
    np.testing.assert_allclose(np.asarray(u), ref, rtol=1e-5, atol=1e-5)


def test_transform_filters_preserves_dtype_and_rank1():
    ws = _rand((3, 2, 5), seed=4, dtype=jnp.bfloat16)   # 1-D: (KT, Ci, N*Co)
    u = winograd.transform_filters(ws)
    assert u.dtype == jnp.bfloat16 and u.shape == (4, 2, 5)


def test_transform_filters_rejects_unsupported():
    with pytest.raises(ValueError, match="unsupported tap geometry"):
        winograd.transform_filters(_rand((6, 6, 2, 2)))     # taps > 5
    with pytest.raises(ValueError, match="unsupported tap geometry"):
        winograd.transform_filters(_rand((2, 2, 2, 2, 2)))  # rank 3


def test_supported_and_tolerance_tables():
    assert winograd.supported((3, 3)) and winograd.supported((5,))
    assert not winograd.supported((6, 3))
    assert not winograd.supported((3, 3), dtype="int8")
    assert not winograd.supported((2, 2, 2))                # rank 3
    for t in range(1, 6):
        assert winograd.tolerance((t, t)) == winograd.WINO_TOL[t]
    assert winograd.tolerance((1, 5)) == winograd.WINO_TOL[5]


# ---------------------------------------------------------------------------
# Kernel parity vs the exact direct path (pinned tolerance)
# ---------------------------------------------------------------------------

def _wino_execute(x, w, s, pad, act="linear", bias=None,
                  output_padding=0):
    p = sd.plan(w.shape, s, pad, backend="winograd", act=act,
                output_padding=output_padding)
    return sd.execute(p.bind(w, bias=bias), x)


@pytest.mark.parametrize("K,s,pad", [
    (5, 2, "same"), (4, 2, 1), (3, 2, "same"), (2, 2, 0),
    (5, 1, 2),                       # artgan d4_s1: kt = 5, F(2,5)
    (5, 3, 2), (6, 3, "same"), (7, 4, 3), (5, 4, "same"),
])
def test_wino_parity_geometry_sweep(K, s, pad):
    pads = same_deconv_pads(K, s) if pad == "same" else pad
    x = _rand((2, 7, 6, 4), seed=K)
    w = _rand((K, K, 4, 3), seed=s)
    out = _wino_execute(x, w, s, pads)
    ref = native_deconv(x, w, s, pads)
    assert out.shape == ref.shape
    kt = -(-K // s)
    assert _rel_err(out, ref) <= winograd.tolerance((kt, kt))


def _paper_deconv_cases():
    cases = []
    for net, fn in accounting.BENCHMARKS.items():
        for l in fn().deconv_layers():
            cases.append(pytest.param(net, l, id=f"{net}-{l.name}"))
    return cases


def test_paper_has_22_deconv_layers():
    assert len(_paper_deconv_cases()) == 22


@pytest.mark.parametrize("net,layer", _paper_deconv_cases())
def test_wino_parity_paper_layers(net, layer):
    """Every paper deconv layer geometry (K, s, padding) passes at the
    pinned tolerance.  Channels/spatial are capped for test speed — the
    CI gate (scripts/ci.sh) runs the same 22 layers at full size."""
    cin, cout = min(layer.cin, 32), min(layer.cout, 32)
    hw = tuple(min(d, 16) for d in layer.in_hw)
    pads = (same_deconv_pads(layer.k, layer.s)
            if layer.padding == "same" else layer.pad)
    x = _rand((1, *hw, cin), seed=1)
    w = _rand((layer.k, layer.k, cin, cout), seed=2)
    out = _wino_execute(x, w, layer.s, pads, act="relu")
    ref = jax.nn.relu(native_deconv(x, w, layer.s, pads))
    assert out.shape == ref.shape
    kt = -(-layer.k // layer.s)
    assert _rel_err(out, ref) <= winograd.tolerance((kt, kt))


def test_wino_parity_1d():
    """1-D winograd lowering (H=1 trick) vs the rank-1 native deconv."""
    x = _rand((2, 11, 3), seed=7)
    w = _rand((9, 3, 4), seed=8)                  # kt = ceil(9/2) = 5
    out = _wino_execute(x, w, 2, 3)
    ref = native_deconv(x, w, 2, 3)
    assert out.shape == ref.shape
    assert _rel_err(out, ref) <= winograd.tolerance((5,))


def test_wino_output_padding_and_epilogue():
    x = _rand((1, 5, 6, 4), seed=9)
    w = _rand((5, 5, 4, 3), seed=10)
    bias = jnp.asarray(np.random.RandomState(11).randn(3), jnp.float32)
    out = _wino_execute(x, w, 2, same_deconv_pads(5, 2), act="tanh",
                        bias=bias, output_padding=1)
    ref = jnp.tanh(native_deconv(x, w, 2, same_deconv_pads(5, 2),
                                 output_padding=1) + bias)
    assert out.shape == ref.shape
    assert _rel_err(out, ref) <= winograd.tolerance((3, 3))


def test_wino_bf16():
    """bf16 plans store bf16 transformed filters; accumulation is f32 in
    the kernel, so the error budget is bf16 rounding, not the transform."""
    x32 = _rand((1, 6, 6, 8), seed=12)
    w32 = _rand((4, 4, 8, 4), seed=13)
    xb, wb = x32.astype(jnp.bfloat16), w32.astype(jnp.bfloat16)
    p = sd.plan(wb.shape, 2, 1, backend="winograd").bind(wb)
    assert p.ws.dtype == jnp.bfloat16
    out = sd.execute(p, xb)
    assert out.dtype == jnp.bfloat16
    ref = native_deconv(xb.astype(jnp.float32),
                        wb.astype(jnp.float32), 2, 1)
    assert _rel_err(out, ref) < 5e-2


def test_wino_tile_plans_accumulate():
    """Channel/row tiling through the transformed-domain accumulator
    agrees with the untiled launch."""
    x = _rand((1, 8, 7, 8), seed=14)
    w = _rand((4, 4, 8, 6), seed=15)
    ref = native_deconv(x, w, 2, 1)
    for th, tcin, tcout in [(2, 4, 2), (4, 8, 3), (3, 2, 6)]:
        p = sd.plan(w.shape, 2, 1, backend="winograd",
                    tile=KernelPlan(th=th, tcin=tcin, tcout=tcout))
        out = sd.execute(p.bind(w), x)
        assert _rel_err(out, ref) <= winograd.tolerance((2, 2))


def test_wino_conv_transpose_grad():
    """The in-trace form transforms freshly split filters; the
    custom_vjp backward is untouched, so grads match native."""
    x = _rand((1, 5, 5, 3), seed=16)
    w = _rand((4, 4, 3, 2), seed=17)
    p = sd.plan(w.shape, 2, 1, backend="winograd")

    def loss_sd(w):
        return jnp.sum(sd.conv_transpose(p, x, w) ** 2)

    def loss_native(w):
        return jnp.sum(native_deconv(x, w, 2, 1) ** 2)

    gs, gn = jax.grad(loss_sd)(w), jax.grad(loss_native)(w)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(gn),
                               rtol=1e-3, atol=1e-3)


def test_wino_plan_rejects_unsupported_geometry():
    with pytest.raises(ValueError, match="winograd backend"):
        sd.plan((11, 11, 4, 3), 2, 1, backend="winograd")   # kt = 6
    with pytest.raises(ValueError, match="winograd backend"):
        sd.plan((4, 4, 4, 4, 3), 2, 1, backend="winograd")  # rank 3
    with pytest.raises(ValueError, match="winograd backend"):
        sd.plan((4, 4, 4, 3), 2, 1, backend="winograd",
                dtype="int8")


def test_wino_bind_layout_and_pytree_structure():
    """A bound winograd plan stores the transformed filters as its ws
    leaf (layout 'wino'), and its pytree structure is distinct from the
    fused plan of the same layer — jit can never swap executables."""
    w = _rand((5, 5, 4, 3), seed=18)
    pw = sd.plan(w.shape, 2, 1, backend="winograd").bind(w)
    pf = sd.plan(w.shape, 2, 1, backend="fused").bind(w)
    assert pw.layout == "wino"
    assert pw.ws.shape == (4, 4, 4, 3 * 4)      # alpha=4 per dim, kt=3
    u = winograd.transform_filters(to_ocmajor(split_filters(w, 2), 2))
    np.testing.assert_allclose(np.asarray(pw.ws), np.asarray(u),
                               rtol=1e-6, atol=1e-6)
    assert (jax.tree_util.tree_structure(pw)
            != jax.tree_util.tree_structure(pf))


# ---------------------------------------------------------------------------
# Autotune: algo-tagged cache keys + measured-cost algorithm selection
# ---------------------------------------------------------------------------

def test_conv_geom_key_distinct_per_algo():
    g = ConvGeom.from_deconv(1, 8, 8, 16, 8, 4, 2, padding=1)
    gw = dataclasses.replace(g, algo="wino")
    assert gw.key() == g.key() + "_wino"
    # algo composes with the dtype tag and precedes the launch-role tag
    g8w = dataclasses.replace(g, dtype="int8", algo="wino")
    assert g8w.key().endswith("_int8_wino")
    gtw = dataclasses.replace(g, algo="wino", tag="dx")
    assert gtw.key().endswith("_wino_dx")


def test_wino_vmem_model_larger_than_direct():
    """The winograd footprint model charges the alpha-expanded filter
    block and the transformed-domain accumulator — a wino launch of the
    same tile is never modelled smaller than the direct one.  At full
    128-lane channel tiles: narrower ones pad each phase of the direct
    kernel's phase-major filter block to 128 lanes."""
    g = ConvGeom.from_deconv(1, 8, 8, 128, 128, 4, 2, padding=1)
    gw = dataclasses.replace(g, algo="wino")
    p = KernelPlan(th=4, tcin=128, tcout=128)
    assert (autotune.vmem_plan_bytes(gw, p)
            > autotune.vmem_plan_bytes(g, p))


def test_stale_cache_without_algo_field_still_loads(tmp_path):
    """Plan-cache entries written before the algo dimension existed
    keep their keys (direct = untagged) and keep loading; the wino
    variant of the same geometry misses and falls back to the
    heuristic — never to the direct entry."""
    cache = str(tmp_path / "plans.json")
    g = ConvGeom.from_deconv(1, 8, 8, 16, 8, 4, 2, padding=1)
    entry = {"th": 2, "tcin": 4, "tcout": 2, "tw": 0, "ms": 1.0,
             "source": "measured", "backend": jax.default_backend()}
    with open(cache, "w") as f:
        json.dump({"version": 1, "plans": {g.key(): entry}}, f)
    assert autotune.get_plan(g, path=cache) == KernelPlan(
        th=2, tcin=4, tcout=2, tw=0)
    gw = dataclasses.replace(g, algo="wino")
    assert autotune.get_plan(gw, path=cache) == autotune.heuristic_plan(gw)


def _measured(ms, plan=KernelPlan(th=2, tcin=4, tcout=2),
              backend=None):
    return {**dataclasses.asdict(plan), "ms": ms, "source": "measured",
            "backend": backend or jax.default_backend()}


def test_best_algo_requires_both_measurements(tmp_path):
    cache = str(tmp_path / "plans.json")
    g = ConvGeom.from_deconv(1, 8, 8, 16, 8, 4, 2, padding=1)
    gw = dataclasses.replace(g, algo="wino")
    # no entries at all -> direct
    assert autotune.best_algo(g, path=cache) == ""
    # only the wino variant measured -> still direct (never switch blind)
    autotune.save_cache({gw.key(): _measured(0.5)}, cache)
    assert autotune.best_algo(g, path=cache) == ""
    # both measured, wino faster -> wino
    autotune.save_cache({gw.key(): _measured(0.5),
                         g.key(): _measured(1.0)}, cache)
    assert autotune.best_algo(g, path=cache) == "wino"
    # both measured, direct faster -> direct
    autotune.save_cache({gw.key(): _measured(2.0),
                         g.key(): _measured(1.0)}, cache)
    assert autotune.best_algo(g, path=cache) == ""
    # measurements from another backend never steer this one
    autotune.save_cache(
        {gw.key(): _measured(0.5, backend="elsewhere"),
         g.key(): _measured(1.0, backend="elsewhere")}, cache)
    assert autotune.best_algo(g, path=cache) == ""


def test_engine_measured_cost_algorithm_selection(tmp_path, monkeypatch):
    """A fused engine binds winograd plans for exactly the layers whose
    geometry measured faster under the fast algorithm — and the served
    output stays within the pinned tolerance of the direct engine."""
    cache = str(tmp_path / "plans.json")
    monkeypatch.setenv("REPRO_SD_PLAN_CACHE", cache)
    from repro.core.accounting import LayerSpec, NetworkSpec
    spec = NetworkSpec("tiny", [
        LayerSpec("fc", 8, 4 * 4 * 8, name="project"),
        LayerSpec("deconv", 8, 8, k=5, s=2, in_hw=(4, 4), name="d1"),
        LayerSpec("deconv", 8, 3, k=5, s=2, in_hw=(8, 8), name="d2"),
    ])
    params = GenerativeModel(spec, "native").init(jax.random.PRNGKey(0))

    eng = SDEngine(spec, backend="fused").bind(params)
    assert all(p.backend == "fused" for p in eng.plans().values())

    # Inject measurements: winograd faster on d1, slower on d2.
    plans = {}
    for name, fast_wino in (("d1", True), ("d2", False)):
        layer = next(l for l in spec.layers if l.name == name)
        g = eng.layer_geom(layer)
        gw = dataclasses.replace(g, algo="wino")
        plans[g.key()] = _measured(1.0)
        plans[gw.key()] = _measured(0.5 if fast_wino else 2.0)
    autotune.save_cache(plans, cache)

    eng.bind(params)
    assert eng.plans()["d1"].backend == "winograd"
    assert eng.plans()["d1"].layout == "wino"
    assert eng.plans()["d2"].backend == "fused"
    assert "backend=winograd" in eng.describe()

    # Mixed-algorithm engine output vs the all-direct engine.
    x = _rand((2, 4, 4, 8), seed=20)
    mixed = np.asarray(eng.run("d2", eng.run("d1", x)))
    eng_direct = SDEngine(spec, backend="fused").bind(params)
    ref = np.asarray(eng_direct.run("d2", eng_direct.run("d1", x)))
    assert np.abs(mixed - ref).max() / max(np.abs(ref).max(), 1e-6) \
        <= winograd.tolerance((3, 3))

    # int8 engines never algorithm-switch (no int8 winograd path)
    eng8 = SDEngine(spec, backend="fused", dtype="int8").bind(params)
    assert all(p.backend == "fused" for p in eng8.plans().values())


def test_winograd_engine_end_to_end():
    """backend='winograd' pins the fast algorithm on every layer; the
    generator output tracks the native model within the pinned
    tolerance."""
    from repro.launch.serve_gen import reduced_spec
    spec = reduced_spec()
    params = GenerativeModel(spec, "native").init(jax.random.PRNGKey(1))
    ref_m = GenerativeModel(spec, "native")
    wm = GenerativeModel(spec, "sd_kernel", engine_backend="winograd")
    z = jax.random.normal(jax.random.PRNGKey(2), ref_m.input_shape(2))
    ref = np.asarray(ref_m.apply(params, z))
    out = np.asarray(wm.apply(params, z))
    assert np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-6) \
        <= winograd.tolerance((3, 3))


def test_registry_winograd_capability_metadata():
    from repro.core import registry
    info = registry.get_impl("winograd")
    assert info.needs_presplit and info.trainable
    assert not info.exact
    assert info.tolerance == winograd.WINO_TOL[5]
    assert info.ranks == (1, 2)
    assert "int8" not in info.dtypes
    assert "winograd" not in registry.exact_names()
