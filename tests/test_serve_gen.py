"""Batched generative serving stack (launch/serve_gen) + SDEngine under
serving conditions: bucketing, compile-cache reuse, dtype rebinds,
cross-instance plan reuse, and end-to-end parity."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.accounting import LayerSpec, NetworkSpec
from repro.engine import SDEngine, resolve_backend
from repro.kernels.autotune import ConvGeom, KernelPlan
from repro.launch.batching import pow2_bucket, pow2_floor, take_group
from repro.launch.serve_gen import (GenServer, main, reduced_spec,
                                    reduced_specs, serve_async)
from repro.models.generative import GenerativeModel

SPEC = reduced_spec()


def _server(**kw):
    kw.setdefault("nets", ["g"])
    kw.setdefault("specs", {"g": SPEC})
    return GenServer(**kw)


# ---------------------------------------------------------------------------
# Bucketing helpers (shared by LM + generative serving)
# ---------------------------------------------------------------------------

def test_pow2_bucket():
    assert [pow2_bucket(n) for n in (1, 2, 3, 4, 5, 16, 17)] == \
        [1, 2, 4, 4, 8, 16, 32]
    assert pow2_bucket(17, max_bucket=16) == 16
    with pytest.raises(ValueError):
        pow2_bucket(0)


def test_pow2_bucket_non_pow2_cap_clamped():
    """Regression: a non-power-of-two cap used to leak its own non-pow2
    value into the compile cache for large n; the cap is now clamped to
    the largest power of two below it, keeping the shape set closed."""
    assert pow2_floor(12) == 8 and pow2_floor(8) == 8 and pow2_floor(1) == 1
    with pytest.raises(ValueError):
        pow2_floor(0)
    assert pow2_bucket(13, max_bucket=12) == 8          # was 12 (leak)
    assert pow2_bucket(9, max_bucket=12) == 8
    for n in range(1, 14):
        b = pow2_bucket(n, max_bucket=12)
        assert b & (b - 1) == 0 and b <= 12             # pow2, capped
    # pow2 caps behave exactly as before
    assert [pow2_bucket(n, 16) for n in (1, 5, 16, 33)] == [1, 8, 16, 16]


def test_server_clamps_non_pow2_max_batch():
    """GenServer must reconcile its group-size cap with the clamped
    bucket cap, or an over-cap group would reach a smaller compiled
    cell and crash on shape mismatch."""
    server = _server(max_batch=12)
    assert server.max_batch == 8
    reqs = server.random_requests("g", 9)               # > clamped cap
    results, stats = serve_async(server, reqs)
    assert set(results) == {r.rid for r in reqs}
    assert all(k[1] & (k[1] - 1) == 0 for k in server._compiled)


def test_take_group_same_key_fifo():
    q = [(0, "a"), (1, "b"), (2, "a"), (3, "a"), (4, "b")]
    group, rest = take_group(q, lambda r: r[1], max_group=2)
    assert group == [(0, "a"), (2, "a")]        # head's key, FIFO order
    assert rest == [(1, "b"), (3, "a"), (4, "b")]
    group2, rest2 = take_group(rest, lambda r: r[1], max_group=2)
    assert group2 == [(1, "b"), (4, "b")]
    assert rest2 == [(3, "a")]


def test_take_group_head_of_line_fairness():
    """The oldest waiting request is NEVER starved: every drain builds
    its group around the queue head, whatever key mix follows — even
    adversarial interleavings where one key dominates arrivals."""
    # one old 'a' request buried under a flood of alternating keys
    q = [(0, "a")] + [(i, "b" if i % 2 else "c") for i in range(1, 20)]
    group, rest = take_group(q, lambda r: r[1], max_group=4)
    assert group[0] == (0, "a")                 # the head always goes
    # repeated drains: the front item of every intermediate queue is
    # served in that very drain (no starvation across rounds), and
    # completion order never reorders same-key requests.
    q = [(i, "abc"[i % 3]) for i in range(30)]
    served, rounds = [], 0
    while q:
        head = q[0]
        group, q = take_group(q, lambda r: r[1], max_group=4)
        assert group[0] == head
        served += group
        rounds += 1
    assert sorted(r[0] for r in served) == list(range(30))
    for key in "abc":
        ids = [r[0] for r in served if r[1] == key]
        assert ids == sorted(ids)               # per-key FIFO preserved


# ---------------------------------------------------------------------------
# The serving stack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("extra", [[], ["--dtype", "int8"],
                                   ["--dtype", "int8", "--calib", "8"]],
                         ids=["float32", "int8", "int8-calib"])
def test_dryrun_smoke(extra, tmp_path, monkeypatch):
    """--dryrun smokes one reduced net per workload family (2-D image,
    1-D audio, 3-D voxel, segmentation decoder): 2 requests each, one
    compiled cell each — in float32, on the int8 engines, and on int8
    engines calibrated at bind (static scales, chained plans)."""
    monkeypatch.setenv("REPRO_SD_CALIB_CACHE", str(tmp_path / "calib.json"))
    results, stats = main(["--dryrun", *extra])
    n_nets = len(reduced_specs())
    assert n_nets == 4
    assert stats["requests"] == 2 * n_nets
    assert stats["compiles"] == n_nets
    assert all(np.isfinite(np.asarray(v)).all() for v in results.values())
    if "--calib" in extra:                      # scales saved, redirected
        assert (tmp_path / "calib.json").exists()


def test_compile_cache_keyed_on_bucket():
    """Varying request counts that land in the same bucket must NOT
    retrace; a new bucket compiles exactly once more."""
    server = _server(max_batch=8)
    serve_async(server, server.random_requests("g", 3))          # bucket 4
    assert server.compile_count == 1
    serve_async(server, server.random_requests("g", 4, seed=2))  # 4 again
    assert server.compile_count == 1
    serve_async(server, server.random_requests("g", 2, seed=3))  # 2: new
    assert server.compile_count == 2
    assert {k[1] for k in server._compiled} == {2, 4}


def test_padding_cropped_and_outputs_match_unbatched():
    """Bucket padding must never leak into results: each request's
    output equals the same latent pushed through the model alone."""
    server = _server(max_batch=8)
    reqs = server.random_requests("g", 3)             # padded 3 -> 4
    results, stats = serve_async(server, reqs)
    model, params = server.model("g")
    for r in reqs:
        solo = model.apply(params, jnp.asarray(r.latent)[None])[0]
        np.testing.assert_allclose(np.asarray(results[r.rid]),
                                   np.asarray(solo), rtol=1e-5, atol=1e-5)


def test_server_parity_vs_native_reference():
    """Engine-served outputs == the native-deconv reference model."""
    server = _server(max_batch=4)
    reqs = server.random_requests("g", 4)
    results, _ = serve_async(server, reqs)
    model, params = server.model("g")
    ref_model = GenerativeModel(SPEC, "native")
    x = jnp.stack([jnp.asarray(r.latent) for r in reqs])
    ref = ref_model.apply(params, x)
    for i, r in enumerate(reqs):
        np.testing.assert_allclose(np.asarray(results[r.rid]),
                                   np.asarray(ref[i]),
                                   rtol=1e-4, atol=1e-4)


def test_bucket_respects_dp_divisibility_and_cap():
    """Buckets must divide by dp, cover the group, and stay within one
    dp-roundup of the (pow2-clamped) max_batch cap."""
    server = _server(max_batch=16)
    server.dp = 3                    # bucket math only; no mesh needed
    assert server.max_batch == 16    # pow2 cap untouched by dp
    for n in (1, 2, 4, 5, 8, 13, 16):
        b = server.bucket(n)
        assert b % 3 == 0 and n <= b <= 18, (n, b)   # 18 = dp-roundup(16)


def test_multi_net_fifo_grouping():
    spec_b = NetworkSpec("g2", list(SPEC.layers))
    server = GenServer(nets=["g", "g2"],
                       specs={"g": SPEC, "g2": spec_b}, max_batch=4)
    ra = server.random_requests("g", 2)
    rb = server.random_requests("g2", 2, seed=5)
    reqs = [ra[0], rb[0], ra[1], rb[1]]
    for i, r in enumerate(reqs):
        r.rid = i
    results, stats = serve_async(server, reqs)
    assert set(results) == {0, 1, 2, 3}
    assert stats["launches"] == 2               # one per net


def test_dp_shard_map_smoke():
    """--dp 2 over a 2-device CPU mesh (subprocess: device count is
    fixed at jax init)."""
    env = dict(os.environ,
               PYTHONPATH="src" + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=2"))
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_gen", "--dryrun",
         "--dp", "2"],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "served 8 requests" in out.stdout       # 2 per reduced net


# ---------------------------------------------------------------------------
# SDEngine under serving conditions
# ---------------------------------------------------------------------------

def test_engine_backend_resolution():
    assert resolve_backend("fused") == "fused"
    assert resolve_backend("xla") == "xla"
    assert resolve_backend("auto") in ("fused", "xla")
    with pytest.raises(ValueError, match="backend"):
        resolve_backend("cuda-graphs")


def test_xla_and_fused_backends_agree():
    """Both engine execution backends run the SAME presplit plans and
    must agree with each other and with native."""
    params = GenerativeModel(SPEC, "native").init(jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 16))
    ref = GenerativeModel(SPEC, "native").apply(params, z)
    outs = {}
    for backend in ("xla", "fused"):
        m = GenerativeModel(SPEC, "sd_kernel", engine_backend=backend)
        outs[backend] = m.apply(params, z)
        np.testing.assert_allclose(np.asarray(outs[backend]),
                                   np.asarray(ref), rtol=1e-4, atol=1e-4)


def test_engine_rebind_new_dtype_bf16():
    """Serving rebinding: the same engine fed bf16 params must rebuild
    its plans (identity fingerprint) and produce bf16-accurate output."""
    model = GenerativeModel(SPEC, "sd_kernel", engine_backend="xla")
    params = model.init(jax.random.PRNGKey(0))
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 16))
    out_f32 = model.apply(params, z)

    params_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    out_bf16 = model.apply(params_bf16, z.astype(jnp.bfloat16))
    eng = model._engine
    assert eng.bound_to(params_bf16) and not eng.bound_to(params)
    for plan in eng.plans().values():
        assert plan.ws_nmajor.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(out_bf16, np.float32)).all()
    np.testing.assert_allclose(np.asarray(out_bf16, np.float32),
                               np.asarray(out_f32), rtol=0.1, atol=0.1)


def test_varying_batch_hits_engine_not_rebind():
    """Different batch sizes across calls must reuse the bound plans
    (batch is not part of the plan fingerprint)."""
    import importlib
    # the package re-export `sd.plan` (function) shadows the submodule
    # attribute; importlib resolves the module for monkeypatching
    sd_plan_mod = importlib.import_module("repro.sd.plan")
    model = GenerativeModel(SPEC, "sd_kernel", engine_backend="xla")
    params = model.init(jax.random.PRNGKey(0))
    calls = []
    orig = sd_plan_mod.split_filters

    def counting(*a, **k):
        calls.append(1)
        return orig(*a, **k)

    sd_plan_mod.split_filters = counting
    try:
        for b in (1, 3, 8, 3, 1):
            model.apply(params, jax.random.normal(
                jax.random.PRNGKey(b), (b, 16)))
    finally:
        sd_plan_mod.split_filters = orig
    assert calls == []          # bound at init; no rebind for any batch


def test_plan_cache_shared_across_engine_instances(tmp_path, monkeypatch):
    """A measured tile plan written by one process/instance is picked up
    by every SDEngine binding the same geometry (JSON plan cache)."""
    cache = tmp_path / "plans.json"
    geom = ConvGeom.from_deconv(1, 4, 4, 32, 16, 5, 2)   # d1 of SPEC
    entry = {"th": 2, "tcin": 16, "tcout": 8, "ms": 0.1,
             "source": "measured", "backend": jax.default_backend()}
    cache.write_text(json.dumps(
        {"version": 1, "plans": {geom.key(): entry}}))
    monkeypatch.setenv("REPRO_SD_PLAN_CACHE", str(cache))

    params = GenerativeModel(SPEC, "native").init(jax.random.PRNGKey(0))
    engines = [SDEngine(SPEC).bind(params) for _ in range(2)]
    want = KernelPlan(th=2, tcin=16, tcout=8)
    for eng in engines:
        assert eng.plans()["d1"].tile == want
    # both instances resolved the identical measured plan — and the
    # second bind never re-measured (get_plan is lookup-only)
    assert engines[0].plans()["d1"].tile == engines[1].plans()["d1"].tile


def test_rebind_new_weights_reuses_compiled_executable():
    """Since the repro.sd redesign, params and bound plans are jit
    *arguments* (pytrees) of the compiled cell: serving a new weight set
    for the same (net, bucket, dtype) must not retrace."""
    server = _server(max_batch=4)
    reqs = server.random_requests("g", 4)
    serve_async(server, reqs)
    assert server.compile_count == 1

    model, _ = server.model("g")
    new_params = GenerativeModel(SPEC, "native").init(
        jax.random.PRNGKey(7))
    model._engine.bind(new_params)
    server._models["g"] = (model, new_params)

    results, _ = serve_async(server, reqs)
    assert server.compile_count == 1        # same executable, new weights
    ref_model = GenerativeModel(SPEC, "native")
    x = jnp.stack([jnp.asarray(r.latent) for r in reqs])
    ref = ref_model.apply(new_params, x)
    for i, r in enumerate(reqs):
        np.testing.assert_allclose(np.asarray(results[r.rid]),
                                   np.asarray(ref[i]),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# N-D workloads through the serving stack (rank-generalised engine).
# ---------------------------------------------------------------------------

def test_nd_nets_served_match_native_reference():
    """Every reduced workload family (1-D audio, 3-D voxel, 2-D image +
    segmentation) serves through the engine with outputs equal to the
    native-deconv reference model."""
    specs = reduced_specs()
    server = GenServer(nets=sorted(specs), specs=specs, max_batch=4)
    for net in sorted(specs):
        reqs = server.random_requests(net, 3)
        results, _ = serve_async(server, reqs)
        model, params = server.model(net)
        ref_model = GenerativeModel(specs[net], "native",
                                    final_tanh=model.final_tanh)
        x = jnp.stack([jnp.asarray(r.latent) for r in reqs])
        ref = ref_model.apply(params, x)
        for i, r in enumerate(reqs):
            np.testing.assert_allclose(
                np.asarray(results[r.rid]), np.asarray(ref[i]),
                rtol=1e-4, atol=1e-4, err_msg=net)


def test_segnet_head_is_logits():
    """The segmentation decoder must NOT squash its class scores: the
    served output equals the unsquashed native logits exactly, and for
    a large-magnitude input it escapes tanh's [-1, 1] range."""
    specs = reduced_specs()
    server = GenServer(nets=["segnet-dryrun"], specs=specs, max_batch=2)
    model, params = server.model("segnet-dryrun")
    assert model.final_tanh is False
    reqs = server.random_requests("segnet-dryrun", 2)
    for r in reqs:                      # push logit magnitudes past 1
        r.latent = jnp.asarray(r.latent) * 25.0
    results, _ = serve_async(server, reqs)
    out = np.stack([np.asarray(results[r.rid]) for r in reqs])
    assert out.shape[-1] == 3 and np.isfinite(out).all()
    assert np.abs(out).max() > 1.0      # a tanh head cannot produce this
    ref_model = GenerativeModel(specs["segnet-dryrun"], "native")
    x = jnp.stack([jnp.asarray(r.latent) for r in reqs])
    np.testing.assert_allclose(out, np.asarray(ref_model.apply(params, x)),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Zero-copy PR: bucket-keyed tiles + --pretune
# ---------------------------------------------------------------------------

def test_serving_tiles_keyed_to_bucket(monkeypatch):
    """The compiled cell for a bucket must carry plans whose tiles were
    resolved at THAT bucket's batch — a plan_batch=1 bind no longer
    leaks its tiles into batch-N launches."""
    import repro.engine.planner as planner_mod
    asked = []
    real = planner_mod.get_plan

    def spy(geom, path=None):
        asked.append(geom)
        return real(geom, path)

    monkeypatch.setattr(planner_mod, "get_plan", spy)
    server = _server(max_batch=8)
    reqs = server.random_requests("g", 8)
    serve_async(server, reqs)
    # the group of 8 launches bucket 8: its plan tiles were resolved
    # from batch-8 geometries, not the bind-time plan_batch=1
    assert any(g.b == 8 for g in asked)
    _, plans8 = server._serving_args("g", 8)
    model, _ = server.model("g")
    bind_plans = model.engine.plans()
    for name, p8 in plans8.items():
        assert p8.ws is bind_plans[name].ws       # shared split filters
    assert ("g", 8) in server._serving


def test_server_bucket_ladder_and_pretune_noop_on_xla():
    server = _server(max_batch=16, backend="xla")
    assert server.buckets() == [1, 2, 4, 8, 16]
    assert server.pretune() == {}                 # tiles steer fused only


def test_server_pretune_fused_persists(tmp_path, monkeypatch):
    cache = tmp_path / "plans.json"
    monkeypatch.setenv("REPRO_SD_PLAN_CACHE", str(cache))
    server = _server(max_batch=2, backend="fused")
    tuned = server.pretune(iters=1)
    # 2 deconv layers x buckets {1, 2} x 2 algorithms (kt=3 supports
    # winograd, so pretune measures the fast-algorithm variant too)
    assert len(tuned) == 8
    assert sum(1 for k in tuned if k.endswith("_wino")) == 4
    data = json.loads(cache.read_text())
    assert all(e["source"] == "measured" for e in data["plans"].values())
    # serving now resolves the measured tiles for its buckets — under
    # the algo key matching whichever backend each layer bound to
    # (pretune re-binds, so measured-faster layers may run winograd)
    _, plans = server._serving_args("g", 2)
    model, _ = server.model("g")
    for name, layer in ((l.name, l) for l in model.spec.deconv_layers()):
        algo = "wino" if plans[name].backend == "winograd" else ""
        geom = model.engine.layer_geom(layer, 2, algo=algo)
        assert plans[name].tile == tuned[geom.key()]


# ---------------------------------------------------------------------------
# Phase-packed share of the fused kernel's MACs
# ---------------------------------------------------------------------------

WIDE = NetworkSpec("wide", [
    LayerSpec("fc", 16, 4 * 4 * 128, name="project"),
    LayerSpec("deconv", 128, 256, k=4, s=2, in_hw=(4, 4), name="d1"),
    LayerSpec("deconv", 256, 128, k=4, s=2, in_hw=(8, 8), name="d2"),
])


@pytest.mark.parametrize("net,bucket", [
    ("dcgan", 256), ("mde", 16), ("pix2pix", 32), ("wide", 4)])
def test_packed_share_is_the_narrow_deconvs_mac_share(net, bucket):
    """At each cell's bucket, the share of the fused kernel's MACs that
    run phase-packed is the share of the Cout < 128 deconvs' SD MACs,
    from the layer shapes; 0 where every deconv has Cout >= 128."""
    specs = {"wide": WIDE} if net == "wide" else None
    server = GenServer(nets=[net], specs=specs, backend="fused",
                       max_batch=bucket)
    layers = server.model(net)[0].spec.deconv_layers()
    narrow = sum(l.sd_macs() for l in layers if l.cout < 128)
    want = narrow / sum(l.sd_macs() for l in layers)
    assert (want > 0) == (net != "wide")
    assert server.packed_share(net, bucket) == pytest.approx(want)


def test_packed_share_on_launch_records_and_stats():
    """Each launch record carries its cell's packed share and stats()
    reports it; the xla backend runs no fused kernel, so 0."""
    for backend, want in (("fused", 1.0), ("xla", 0.0)):
        server = _server(max_batch=4, backend=backend)
        _, stats = serve_async(server, server.random_requests("g", 3))
        assert server.packed_share("g", 4) == want     # both deconvs narrow
        assert stats["packed_share"] == want
