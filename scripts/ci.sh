#!/usr/bin/env bash
# CI entry point: tier-1 tests + the smokes and gates tier-1 does not
# run (DCGAN training step with grad check, serving CLI with --pretune,
# zero-copy HBM traffic, Winograd parity and end to end).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== dev deps =="
python -m pip install -q -r requirements-dev.txt 2>/dev/null \
  || echo "  (pip install skipped — offline)"

echo "== tier-1 test suite =="
python -m pytest -x -q

echo "== trainable kernel-path smoke (1-step DCGAN, grad parity) =="
python examples/train_dcgan.py --steps 1 --small --deconv-impl sd_kernel --grad-check

echo "== generative serving smoke (serve_gen --dryrun: 2-D/1-D/3-D/seg; "
echo "   --pretune warms the (net, bucket) plan cache, no-op on xla) =="
python -m repro.launch.serve_gen --dryrun --pretune

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

