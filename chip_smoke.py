#!/usr/bin/env python3
"""Smoke test of the split-deconv serving path on a TPU.

Drives the main path once through the entry points a user calls, at the
paper's published widths, with random weights made from ``--seed``:

  (a) the device: fails unless JAX's first device is a TPU;
  (b) DCGAN f32 served by ``GenServer`` + ``ContinuousScheduler`` on the
      fused Pallas kernels, in three calls of 16, 4 and 1 requests (one
      launch each of buckets 16, 4 and 1).  Every output is compared
      with the native deconv reference at "highest" matmul precision,
      every compiled cell must hold the Pallas kernel
      (``tpu_custom_call``), and serving the same requests again must
      compile nothing;
  (c) the same DCGAN in bf16 against that reference, then chained int8
      (static calibration on 64 latents) by SSIM against the f32 server;
  (d) MDE (256x512 input) at batch 1, whose 512-wide decoder layers take
      the width-tiled kernels, against the native reference;
  (e) one training step on the fused backend (``make_sharded_train_step``
      on a 1x1 mesh, DCGAN, batch 16): filter and input gradients
      against ``jax.grad`` of the native model.

``--four-chip`` runs only the (data x model) mesh path instead: dp1
serving against dp4, dp2xmp2 and mp4 on DCGAN and GP-GAN, with the
placement of the bound plans checked, and one dp2xmp2 training step
against the 1x1 step.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chip   # four chips

Each phase prints what it found (errors, compiles, seconds; the times
are informational).  The last line of stdout is
``{"ok": true, "device": {...}}``; any failure exits non-zero without
it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def rel_err(out, ref) -> float:
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(out - ref).max() / np.abs(ref).max())


def native_forward(spec, params, z):
    """The plain reference: XLA's transposed conv on f32 params at
    "highest" matmul precision."""
    from repro.models.generative import GenerativeModel
    ref = GenerativeModel(spec, deconv_impl="native")
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(ref.apply)(p32, jnp.asarray(z, jnp.float32)))


def serve(server, net, n, seed):
    """``n`` requests through the continuous-batching scheduler, all
    arriving at once; returns (latents, outputs, stats)."""
    from repro.launch.serve_gen import serve_async
    reqs = server.random_requests(net, n, seed=seed)
    results, stats = serve_async(server, reqs)
    z = np.stack([np.asarray(r.latent) for r in reqs])
    out = np.stack([np.asarray(results[r.rid]) for r in reqs])
    return z, out, stats


# ---------------------------------------------------------------------------
# One chip
# ---------------------------------------------------------------------------

BUCKETS = (16, 4, 1)


def phase_f32(seed):
    from repro.launch.serve_gen import GenServer
    server = GenServer(nets=["dcgan"], backend="fused", max_batch=16,
                       seed=seed)
    model, params = server.model("dcgan")
    t0 = time.perf_counter()
    batches = [serve(server, "dcgan", n, seed + 1 + i)
               for i, n in enumerate(BUCKETS)]
    cold_s = time.perf_counter() - t0
    compiles = server.compile_count
    cells = {str(server.cell_key("dcgan", b)) for b in BUCKETS}
    check(set(batches[-1][2]["compile_cache"]) == cells,
          f"cells {batches[-1][2]['compile_cache']} != {sorted(cells)}")
    err = max(rel_err(out, native_forward(model.spec, params, z))
              for z, out, _ in batches)
    check(err <= 1e-4, f"dcgan f32 max rel err {err:.3e} > 1e-4")
    for b in BUCKETS:
        lean, plans = server._serving_args("dcgan", b)
        z0 = jnp.zeros((b, *model.input_shape(1)[1:]), server.dtype)
        hlo = server.compiled("dcgan", b).lower(lean, plans, z0)
        check("tpu_custom_call" in hlo.compile().as_text(),
              f"bucket {b}: no Pallas kernel in the compiled cell")
    before = server.compile_count
    t0 = time.perf_counter()
    for i, n in enumerate(BUCKETS):
        serve(server, "dcgan", n, seed + 1 + i)
    warm_s = time.perf_counter() - t0
    check(server.compile_count == before,
          f"second pass compiled {server.compile_count - before} cells")
    print(f"(b) dcgan f32 fused, buckets {sorted(BUCKETS)}: max rel err "
          f"{err:.3e} vs native (bound 1e-4); {compiles} compiles, "
          f"tpu_custom_call in all {len(BUCKETS)} cells; first pass "
          f"{cold_s:.2f}s (compiles included), second pass {warm_s:.2f}s "
          f"with 0 new compiles", flush=True)
    return batches


def phase_bf16_int8(seed, f32_batches):
    from repro.core.ssim import ssim
    from repro.launch.serve_gen import GenRequest, GenServer, serve_async
    t0 = time.perf_counter()
    srv = GenServer(nets=["dcgan"], dtype=jnp.bfloat16, backend="fused",
                    max_batch=16, seed=seed)
    model, params = srv.model("dcgan")
    z, out, stats = serve(srv, "dcgan", 16, seed + 1)
    err = rel_err(out, native_forward(model.spec, params, z))
    check(err <= 2e-2, f"dcgan bf16 max rel err {err:.3e} > 2e-2")
    print(f"(c) dcgan bf16 fused, bucket 16: max rel err {err:.3e} vs "
          f"native (bound 2e-2); {stats['compiles']} compiles, "
          f"{time.perf_counter() - t0:.2f}s", flush=True)

    t0 = time.perf_counter()
    i8 = GenServer(nets=["dcgan"], dtype="int8", calib=64, backend="fused",
                   max_batch=16, seed=seed)
    m8, _ = i8.model("dcgan")
    plans = m8.engine.plans()
    chained = sum(p.chain_out for p in plans.values())
    check(chained > 0, "int8 calibration chained no layer")
    z32, out32, _ = f32_batches[0]
    reqs = [GenRequest(rid=i, net="dcgan", latent=jnp.asarray(zi))
            for i, zi in enumerate(z32)]
    results, stats = serve_async(i8, reqs)
    out8 = np.stack([np.asarray(results[r.rid]) for r in reqs])
    s = float(ssim(jnp.asarray(out32), jnp.asarray(out8)))
    check(s >= 0.999, f"chained int8 SSIM {s:.5f} < 0.999")
    print(f"(c) dcgan chained int8 (calib 64, {chained}/{len(plans)} "
          f"layers chain), bucket 16: SSIM {s:.5f} vs the f32 server "
          f"(bound 0.999); {stats['compiles']} compiles, "
          f"{time.perf_counter() - t0:.2f}s", flush=True)


def phase_mde(seed):
    from repro.launch.serve_gen import GenServer
    t0 = time.perf_counter()
    srv = GenServer(nets=["mde"], backend="fused", max_batch=1, seed=seed)
    model, params = srv.model("mde")
    tiled = [name for name, p in model.engine.plans_for_batch(1).items()
             if p.tile is not None and p.tile.tw]
    check(tiled, "no MDE layer takes the width-tiled kernel")
    z, out, stats = serve(srv, "mde", 1, seed + 1)
    err = rel_err(out, native_forward(model.spec, params, z))
    check(err <= 1e-4, f"mde f32 max rel err {err:.3e} > 1e-4")
    print(f"(d) mde f32 fused, batch 1, width-tiled layers {tiled}: max "
          f"rel err {err:.3e} vs native (bound 1e-4); {stats['compiles']} "
          f"compiles, {time.perf_counter() - t0:.2f}s", flush=True)


def _train_setup(seed, batch):
    from repro.core.accounting import dcgan
    from repro.models.generative import GenerativeModel
    model = GenerativeModel(dcgan(), deconv_impl="sd_kernel",
                            engine_backend="fused")
    params = model.init(jax.random.PRNGKey(seed))
    kz, kt = jax.random.split(jax.random.PRNGKey(seed + 1))
    z = jax.random.normal(kz, model.input_shape(batch))
    target = jax.random.uniform(kt, (batch, 64, 64, 3), minval=-1.0,
                                maxval=1.0)
    return model, params, z, target


def _train_step(model, params, z, target, dp, mp, lr):
    from repro.launch.mesh import make_dev_mesh
    from repro.launch.train_gen import make_sharded_train_step, place_params
    mesh = make_dev_mesh(dp, mp)
    step, specs = make_sharded_train_step(model, mesh, lr=lr)
    new, loss = step(place_params(params, mesh, specs), z, target)
    return jax.tree_util.tree_map(np.asarray, new), float(loss)


def phase_train(seed):
    t0 = time.perf_counter()
    model, params, z, target = _train_setup(seed, 16)
    new, loss = _train_step(model, params, z, target, 1, 1, lr=1e-2)
    check(np.isfinite(loss), f"train step loss {loss}")
    check(all(np.isfinite(a).all() and not np.array_equal(a, np.asarray(p))
              for a, p in zip(jax.tree_util.tree_leaves(new),
                              jax.tree_util.tree_leaves(params))
              if a.size), "train step left a parameter unchanged")

    def mse(apply, ps, zz):
        return jnp.mean((apply(ps, zz) - target) ** 2)

    from repro.models.generative import GenerativeModel
    native = GenerativeModel(model.spec, deconv_impl="native")
    grad = jax.grad(mse, argnums=(1, 2))
    gp, gz = jax.jit(lambda ps, zz: grad(model.apply, ps, zz))(params, z)
    with jax.default_matmul_precision("highest"):
        rp, rz = jax.jit(lambda ps, zz: grad(native.apply, ps, zz))(params, z)
    errs = {f"{l}.w": rel_err(gp[l]["w"], rp[l]["w"])
            for l in ("d1", "d2", "d3")}
    errs["z"] = rel_err(gz, rz)
    worst = max(errs.values())
    check(worst <= 1e-4, f"fused grads vs native: {errs} (bound 1e-4)")
    print(f"(e) dcgan train step, fused backend, 1x1 mesh, batch 16: loss "
          f"{loss:.6f}; grads vs native max rel err "
          + ", ".join(f"{k} {v:.3e}" for k, v in errs.items())
          + f" (bound 1e-4); {time.perf_counter() - t0:.2f}s", flush=True)


# ---------------------------------------------------------------------------
# Four chips
# ---------------------------------------------------------------------------

MESHES = (("dp1", 1, 1), ("dp4", 4, 1), ("dp2xmp2", 2, 2), ("mp4", 1, 4))


def phase_mesh_serving(seed):
    from repro.launch.serve_gen import GenServer
    for net in ("dcgan", "gpgan"):
        t0 = time.perf_counter()
        ref = None
        found = []
        for name, dp, mp in MESHES:
            srv = GenServer(nets=[net], backend="fused", seed=seed, dp=dp,
                            mp=mp)
            model, _ = srv.model(net)
            if dp * mp > 1:
                plans = model.engine.plans()
                for lname, p in plans.items():
                    devs = p.ws.sharding.device_set
                    check(len(devs) == dp * mp,
                          f"{net}/{name}/{lname}: ws on {len(devs)} devices")
                sharded = [l for l, p in plans.items() if p.shards > 1]
                check(bool(sharded) == (mp > 1),
                      f"{net}/{name}: Cout-sharded layers {sharded}")
                for l in sharded:
                    spec = plans[l].ws.sharding.spec
                    check(spec[-1] == "model",
                          f"{net}/{name}/{l}: ws spec {spec}")
            ys = [np.asarray(srv.run_group(net, [
                r.latent for r in srv.random_requests(net, n, seed=s)]))
                for n, s in ((1, seed + 5), (8, seed + 6))]
            if ref is None:
                ref = ys
                continue
            d = max(float(np.abs(y - r).max()) for y, r in zip(ys, ref))
            check(d <= 1e-5, f"{net} {name} vs dp1: max |delta| {d:.3e}")
            found.append(f"{name} {d:.2e}")
        print(f"(mesh) {net} fused serving, batches 1 and 8, max |delta| "
              f"vs dp1: {', '.join(found)} (bound 1e-5); "
              f"{time.perf_counter() - t0:.2f}s", flush=True)


def phase_mesh_train(seed):
    t0 = time.perf_counter()
    model, params, z, target = _train_setup(seed, 16)
    # A large step makes the update, not the weights, dominate the new
    # parameters, so rounding of p - lr*g cannot hide a gradient error.
    lr = 1e3
    ref, ref_loss = _train_step(model, params, z, target, 1, 1, lr)
    new, loss = _train_step(model, params, z, target, 2, 2, lr)
    check(abs(loss - ref_loss) <= 1e-5 * abs(ref_loss),
          f"dp2xmp2 loss {loss} vs 1x1 {ref_loss}")
    worst = 0.0
    for a, r, p in zip(jax.tree_util.tree_leaves(new),
                       jax.tree_util.tree_leaves(ref),
                       jax.tree_util.tree_leaves(params)):
        step = np.abs(r - np.asarray(p)).max()
        if step:
            worst = max(worst, float(np.abs(a - r).max() / step))
    check(worst <= 1e-4, f"dp2xmp2 update vs 1x1: rel err {worst:.3e}")
    print(f"(mesh) dcgan train step dp2xmp2 vs 1x1, batch 16: loss "
          f"{loss:.6f} vs {ref_loss:.6f}; update max rel err {worst:.3e} "
          f"(bound 1e-4); {time.perf_counter() - t0:.2f}s", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the (data x model) mesh path on 4 chips")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    check(dev["platform"] == "tpu", f"no TPU: JAX's first device is "
          f"{dev['platform']} ({dev['kind']})")
    want = 4 if args.four_chip else 1
    check(dev["count"] >= want, f"needs {want} chips, found {dev['count']}")
    print(f"(a) jax {jax.__version__}: {dev['count']} x {dev['kind']} "
          f"({dev['platform']})", flush=True)

    sys.path.insert(0, SRC)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"    compile cache: {enable_compile_cache()}", flush=True)

    if args.four_chip:
        phase_mesh_serving(args.seed)
        phase_mesh_train(args.seed)
    else:
        batches = phase_f32(args.seed)
        phase_bf16_int8(args.seed, batches)
        phase_mde(args.seed)
        phase_train(args.seed)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
