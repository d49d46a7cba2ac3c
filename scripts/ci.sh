#!/usr/bin/env bash
# CI entry point: tier-1 tests + registry consistency + serving smoke +
# a fast interpret-mode kernel-parity smoke.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== dev deps =="
python -m pip install -q -r requirements-dev.txt 2>/dev/null \
  || echo "  (pip install skipped — offline)"

echo "== tier-1 test suite =="
python -m pytest -x -q

echo "== executor-registry capabilities consistency =="
python -c "from repro.core import registry; registry.selfcheck(verbose=True)"

echo "== functional SD API selfcheck (repro.sd) =="
python -c "import repro.sd; repro.sd.selfcheck(verbose=True)"

echo "== trainable kernel-path smoke (1-step DCGAN, grad parity) =="
python examples/train_dcgan.py --steps 1 --small --deconv-impl sd_kernel --grad-check

echo "== generative serving smoke (serve_gen --dryrun: 2-D/1-D/3-D/seg; "
echo "   --pretune warms the (net, bucket) plan cache, no-op on xla) =="
python -m repro.launch.serve_gen --dryrun --pretune

echo "== open-loop serving smoke (loadgen: Poisson arrivals, deadlines, "
echo "   async-vs-drain on reduced specs; gates async goodput >= 0.9) =="
python -m benchmarks.loadgen --smoke --seed 0 --out /tmp/BENCH_load_smoke.json

echo "== open-loop serving gate: committed BENCH_load.json (no request "
echo "   lost, >= 3 QPS levels, async beats drain on p95) =="
python -m benchmarks.loadgen --check

echo "== int8 serving smoke (quantized engines end to end) =="
python -m repro.launch.serve_gen --dryrun --dtype int8

echo "== int8 calibration smoke (static scales swept at bind, chained "
echo "   plans served end to end; cache redirected to /tmp) =="
REPRO_SD_CALIB_CACHE=/tmp/ci_sd_calib.json \
python -m repro.launch.serve_gen --dryrun --dtype int8 --calib 8

echo "== int8 accuracy gate: committed BENCH_quant.json (every net's "
echo "   SSIM >= 0.99 vs the f32 engine — dynamic AND chained — int8 "
echo "   launch bytes < f32, chained bytes < int8 per layer) =="
python -m benchmarks.quant_bench --check

echo "== int8 accuracy gate: live SSIM on dcgan + sngan =="
python - <<'PY'
import jax, jax.numpy as jnp, numpy as np
from repro.core.ssim import ssim
from repro.models.generative import build
from benchmarks.quant_bench import SSIM_MIN

for name in ("dcgan", "sngan"):
    f32m = build(name, "sd_kernel")
    params = f32m.init(jax.random.PRNGKey(0))
    i8m = build(name, "sd_kernel", engine_dtype="int8")
    z = jax.random.normal(jax.random.PRNGKey(1), f32m.input_shape(4))
    ref = jnp.asarray(f32m.apply(params, z))
    out = jnp.asarray(i8m.apply(params, z))
    s = float(ssim(ref, out))
    assert s >= SSIM_MIN, f"{name}: int8 SSIM {s:.4f} < {SSIM_MIN}"
    print(f"  {name}: int8 vs f32 SSIM {s:.4f} (gate {SSIM_MIN})")
print("int8 SSIM gate: OK")
PY

echo "== chained-int8 accuracy gate: live SSIM >= 0.999 on dcgan + "
echo "   sngan (static calibration, int8 activations through HBM) =="
python - <<'PY'
import jax, jax.numpy as jnp
from repro.core.ssim import ssim
from repro.models.generative import build

for name in ("dcgan", "sngan"):
    f32m = build(name, "sd_kernel")
    params = f32m.init(jax.random.PRNGKey(0))
    i8c = build(name, "sd_kernel", engine_dtype="int8")
    i8c.calibrate(params, n=32, seed=7)
    plans = i8c.engine.plans()
    chained = sum(p.chain_out for p in plans.values())
    assert chained, f"{name}: no layer chained — wiring broken"
    z = jax.random.normal(jax.random.PRNGKey(1), f32m.input_shape(4))
    ref = jnp.asarray(f32m.apply(params, z))
    out = jnp.asarray(i8c.apply(params, z))
    s = float(ssim(ref, out))
    assert s >= 0.999, f"{name}: chained int8 SSIM {s:.4f} < 0.999"
    print(f"  {name}: chained SSIM {s:.4f} "
          f"({chained}/{len(plans)} layers chain int8 through HBM)")
print("chained-int8 SSIM gate: OK")
PY

echo "== N-D sweep smoke (nd_bench --smoke, parity-gated) =="
python -m benchmarks.nd_bench --smoke --iters 1 --out /tmp/BENCH_nd_smoke.json

echo "== N-D grad parity (1-D and 3-D conv_transpose vs native autodiff) =="
python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
import repro.sd as sd
from repro.core.deconv import native_deconv

rng = np.random.RandomState(0)
for shape_x, shape_w, s, p, op in [((2, 9, 3), (5, 3, 2), 2, 1, 1),
                                   ((1, 3, 4, 4, 2), (4, 4, 4, 2, 2),
                                    2, 1, 0)]:
    x = jnp.asarray(rng.randn(*shape_x), jnp.float32)
    w = jnp.asarray(rng.randn(*shape_w), jnp.float32)
    plan = sd.plan(w.shape, s, p, output_padding=op)
    np.testing.assert_allclose(
        np.asarray(sd.conv_transpose(plan, x, w)),
        np.asarray(native_deconv(x, w, s, p, output_padding=op)),
        rtol=1e-5, atol=1e-5)
    g = jax.grad(lambda ww: jnp.sum(sd.conv_transpose(plan, x, ww)**2))(w)
    gr = jax.grad(lambda ww: jnp.sum(
        native_deconv(x, ww, s, p, output_padding=op)**2))(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=1e-4, atol=1e-4)
print("N-D grad parity: OK")
PY

echo "== HBM-traffic regression gate (zero-copy vs pad/crop, DCGAN d1) =="
python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
from repro.core.deconv import same_deconv_pads, split_filters
from repro.kernels.autotune import ConvGeom, heuristic_plan
from repro.kernels.ops import sd_deconv_presplit_fused, ws_to_ocmajor
from repro.launch.hlo_analysis import cost_dict

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(1, 8, 8, 256), jnp.float32)      # DCGAN d1
w = jnp.asarray(rng.randn(5, 5, 256, 128) * 0.05, jnp.float32)
pads = same_deconv_pads(5, 2)
ws = ws_to_ocmajor(split_filters(w, 2), 2)
# Deterministic plan: the gate measures the pad/crop machinery, not
# whatever tile a stale tuner cache resolves on this machine.
plan = heuristic_plan(ConvGeom.from_deconv(1, 8, 8, 256, 128, 5, 2,
                                           padding=pads))

def bytes_of(zero_copy):
    f = jax.jit(lambda a: sd_deconv_presplit_fused(
        a, ws, (5, 5), 2, pads, plan=plan, zero_copy=zero_copy))
    cost = cost_dict(f.lower(x).compile().cost_analysis())
    return int(cost.get("bytes accessed", 0))

zc, pc = bytes_of(True), bytes_of(False)
assert zc < pc, (
    f"zero-copy path regressed: {zc:,} bytes accessed vs {pc:,} for "
    "the pad/crop composition")
print(f"HBM gate OK: zero-copy {zc:,} < pad/crop {pc:,} bytes "
      f"({1 - zc/pc:.0%} less)")
PY

echo "== kernel parity smoke (interpret mode) =="
python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
from repro.core import native_deconv
from repro.kernels.ops import sd_deconv_kernel
from repro.models.generative import build

rng = np.random.RandomState(0)
x = jnp.asarray(rng.randn(1, 6, 7, 8), jnp.float32)
w = jnp.asarray(rng.randn(5, 5, 8, 4), jnp.float32)
for s, pad in [(2, 1), (3, 2)]:
    ref = native_deconv(x, w, s, pad)
    out = sd_deconv_kernel(x, w, s, pad)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)

model = build("dcgan", "sd_kernel")
params = model.init(jax.random.PRNGKey(0))
z = jax.random.normal(jax.random.PRNGKey(1), model.input_shape(1))
ref = build("dcgan", "native").apply(params, z)
np.testing.assert_allclose(np.asarray(model.apply(params, z)),
                           np.asarray(ref), rtol=1e-4, atol=1e-4)
print("kernel parity smoke: OK")
PY

echo "== winograd parity gate: all 22 paper deconv layers at full size "
echo "   vs native, within the pinned per-tap tolerance =="
python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
import repro.sd as sd
from repro.core import accounting, native_deconv, same_deconv_pads
from repro.kernels import winograd

rng = np.random.RandomState(0)
n = 0
for net, fn in accounting.BENCHMARKS.items():
    for l in fn().deconv_layers():
        pads = (same_deconv_pads(l.k, l.s) if l.padding == "same"
                else l.pad)
        x = jnp.asarray(rng.randn(1, *l.in_hw, l.cin), jnp.float32)
        w = jnp.asarray(rng.randn(l.k, l.k, l.cin, l.cout) * 0.05,
                        jnp.float32)
        p = sd.plan(w.shape, l.s, pads, backend="winograd").bind(w)
        out = np.asarray(sd.execute(p, x))
        ref = np.asarray(native_deconv(x, w, l.s, pads))
        kt = -(-l.k // l.s)
        tol = winograd.tolerance((kt, kt))
        rel = np.abs(out - ref).max() / max(np.abs(ref).max(), 1e-6)
        assert rel <= tol, (f"{net}/{l.name}: winograd rel err "
                            f"{rel:.2e} > pinned {tol:.0e}")
        n += 1
assert n == 22, f"expected 22 paper deconv layers, saw {n}"
print(f"winograd parity gate OK: {n} layers within pinned tolerance")
PY

echo "== winograd end-to-end gate: dcgan generator SSIM >= 0.999 vs "
echo "   the exact native model =="
python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
from repro.core.ssim import ssim
from repro.models.generative import build

ref_m = build("dcgan", "native")
params = ref_m.init(jax.random.PRNGKey(0))
wm = build("dcgan", "sd_kernel", engine_backend="winograd")
z = jax.random.normal(jax.random.PRNGKey(1), ref_m.input_shape(2))
ref = jnp.asarray(ref_m.apply(params, z))
out = jnp.asarray(wm.apply(params, z))
s = float(ssim(ref, out))
assert s >= 0.999, f"dcgan winograd SSIM {s:.5f} < 0.999"
rel = float(jnp.abs(out - ref).max() / jnp.abs(ref).max())
print(f"winograd end-to-end gate OK: dcgan SSIM {s:.5f}, "
      f"max rel err {rel:.2e}")
PY

echo "== 2-device Cout-shard parity gate: all 22 paper deconv layers, "
echo "   sharded execution bit-exact vs unsharded =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" \
python - <<'PY'
import numpy as np
import jax, jax.numpy as jnp
import repro.sd as sd
from repro.core import accounting, same_deconv_pads

assert jax.device_count() == 2, jax.devices()
mesh = jax.make_mesh((1, 2), ("data", "model"))
rng = np.random.RandomState(0)
n = sharded = 0
for net, fn in accounting.BENCHMARKS.items():
    for l in fn().deconv_layers():
        pads = (same_deconv_pads(l.k, l.s) if l.padding == "same"
                else l.pad)
        x = jnp.asarray(rng.randn(1, *l.in_hw, l.cin), jnp.float32)
        w = jnp.asarray(rng.randn(l.k, l.k, l.cin, l.cout) * 0.05,
                        jnp.float32)
        b = jnp.asarray(rng.randn(l.cout), jnp.float32)
        p = sd.plan(w.shape, l.s, pads, backend="xla", act="relu")
        ref = np.asarray(sd.execute(p.bind(w, bias=b), x))
        if l.cout % 2 == 0:     # narrow layers replicate (engine policy)
            bp = p.bind(w, bias=b, mesh=mesh, axis="model")
        else:
            bp = p.bind(w, bias=b)
        out = np.asarray(sd.execute_spmd(bp, x, mesh))
        assert (out == ref).all(), (
            f"{net}/{l.name}: sharded not bit-exact, "
            f"maxabs {np.abs(out - ref).max():.2e}")
        n += 1
        sharded += int(bp.shards == 2)
assert n == 22, f"expected 22 paper deconv layers, saw {n}"
print(f"Cout-shard parity gate OK: {n} layers bit-exact "
      f"({sharded} sharded 2-way, {n - sharded} replicated narrow)")
PY

echo "== (data x model) mesh serving gate: dp2xmp2 parity vs single "
echo "   device + zero recompiles across a checkpoint swap =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
python - <<'PY'
import numpy as np
import jax
from repro.launch.serve_gen import GenServer, reduced_specs

specs = reduced_specs()
nets = list(specs)
ref = GenServer(nets=nets, specs=specs, backend="auto", seed=3)
srv = GenServer(nets=nets, specs=specs, backend="auto", seed=3,
                dp=2, mp=2)
for net in nets:
    zs = [r.latent for r in ref.random_requests(net, 2, seed=7)]
    d = float(np.max(np.abs(np.asarray(ref.run_group(net, zs))
                            - np.asarray(srv.run_group(net, zs)))))
    assert d <= 1e-5, f"{net}: mesh parity maxabs {d:.2e}"
net = nets[0]
assert srv.cell_key(net, 2)[-1] == "dp2xmp2"
n0 = srv.compile_count
m, _ = srv.model(net)
srv.swap_checkpoint(net, m.init(jax.random.PRNGKey(99)))
zs = [r.latent for r in srv.random_requests(net, 2, seed=11)]
srv.run_group(net, zs)
assert srv.compile_count == n0, (
    f"checkpoint swap recompiled: {n0} -> {srv.compile_count}")
print(f"mesh serving gate OK: {len(nets)} nets parity <= 1e-5, "
      f"{n0} compiles closed over swap")
PY

echo "== DP x MP grid smoke (shard_bench on reduced specs over 4 host"
echo "   devices, parity-gated) =="
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=4" \
python -m benchmarks.shard_bench --reduced --iters 1 \
  --out /tmp/BENCH_shard_smoke.json
python - <<'PY'
import json
data = json.load(open("/tmp/BENCH_shard_smoke.json"))
bad = [n for n, r in data["nets"].items() if not r["parity_ok"]]
assert not bad, f"shard smoke parity failed: {bad}"
print(f"shard smoke OK: {len(data['nets'])} nets, parity everywhere")
PY
