"""End-to-end GAN training with the SD deconvolution path.

Trains the paper's DCGAN (generator runs its deconvs through Split
Deconvolution — gradients flow through the split/pixel-shuffle transform)
against synthetic smooth images, non-saturating GAN loss, checkpointed.

  PYTHONPATH=src python examples/train_dcgan.py --steps 200
  PYTHONPATH=src python examples/train_dcgan.py --steps 10 --small  # CI
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.core import registry
from repro.core.accounting import NetworkSpec, LayerSpec
from repro.data import GANLatentPipeline
from repro.models.generative import (DCGANDiscriminator, GenerativeModel,
                                     build)
from repro.optim import adamw_init, adamw_update


def small_spec():
    return NetworkSpec("DCGAN-small", [
        LayerSpec("fc", 32, 4 * 4 * 64, name="project"),
        LayerSpec("deconv", 64, 32, k=5, s=2, in_hw=(4, 4), name="d1"),
        LayerSpec("deconv", 32, 3, k=5, s=2, in_hw=(8, 8), name="d2"),
    ])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--deconv-impl", "--deconv", dest="deconv",
                    default="sd",
                    # gradients must flow through the deconv: only impls
                    # the registry marks trainable AND exact are offered.
                    # Since the repro.sd redesign that includes sd_kernel
                    # and sd_fn — traced params route through the
                    # custom_vjp functional path (shi/chang stay out:
                    # wrong-baseline reproductions)
                    choices=sorted(set(registry.trainable_names())
                                   & set(registry.exact_names())))
    ap.add_argument("--grad-check", action="store_true",
                    help="before training, check jax.grad of the "
                    "generator loss through --deconv-impl against the "
                    "native reference (1e-4)")
    ap.add_argument("--out", default="runs/dcgan")
    args = ap.parse_args(argv)

    if args.small:
        gen = GenerativeModel(small_spec(), deconv_impl=args.deconv)
        img_hw = (16, 16)
    else:
        gen = build("dcgan", deconv_impl=args.deconv)
        img_hw = (64, 64)

    class SmallD(DCGANDiscriminator):
        CHANNELS = (3, 16, 32, 64) if args.small else (3, 64, 128, 256)

    disc = SmallD(img_hw)
    kg, kd = jax.random.split(jax.random.PRNGKey(0))
    gp, dp = gen.init(kg), disc.init(kd)
    g_opt, d_opt = adamw_init(gp), adamw_init(dp)
    z_dim = gen.spec.layers[0].cin
    pipe = GANLatentPipeline(z_dim=z_dim, global_batch=args.batch)
    mgr = CheckpointManager(args.out + "/ckpt", keep=2)

    def bce(logits, target_ones):
        t = jnp.ones_like(logits) if target_ones else jnp.zeros_like(logits)
        return jnp.mean(jnp.maximum(logits, 0) - logits * t
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    @jax.jit
    def d_step(dp, d_opt, gp, z, real):
        def loss(dp_):
            fake = gen.apply(gp, z)
            return bce(disc.apply(dp_, real), True) + \
                bce(disc.apply(dp_, fake), False)
        l, g = jax.value_and_grad(loss)(dp)
        dp, d_opt = adamw_update(dp, g, d_opt, lr=2e-4, b1=0.5,
                                 weight_decay=0.0)
        return dp, d_opt, l

    @jax.jit
    def g_step(gp, g_opt, dp, z):
        def loss(gp_):
            return bce(disc.apply(dp, gen.apply(gp_, z)), True)
        l, g = jax.value_and_grad(loss)(gp)
        gp, g_opt = adamw_update(gp, g, g_opt, lr=2e-4, b1=0.5,
                                 weight_decay=0.0)
        return gp, g_opt, l

    if args.grad_check:
        # Same loss, same params: grads through the chosen impl must
        # match the native-deconv reference (the repro.sd custom_vjp
        # contract that makes sd_kernel/sd_fn trainable).
        import numpy as np
        ref = (GenerativeModel(small_spec(), deconv_impl="native")
               if args.small else build("dcgan", deconv_impl="native"))
        z0 = pipe.batch(0)

        def gen_loss(model):
            return lambda p: bce(disc.apply(dp, model.apply(p, z0)), True)

        g_impl = jax.jit(jax.grad(gen_loss(gen)))(gp)
        g_ref = jax.grad(gen_loss(ref))(gp)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4),
            g_impl, g_ref)
        print(f"grad check: {args.deconv} grads match native (1e-4)")

    d_hist, g_hist = [], []
    for step in range(args.steps):
        t0 = time.time()
        z = pipe.batch(step)
        real = pipe.images(step, img_hw)
        dp, d_opt, dl = d_step(dp, d_opt, gp, z, real)
        gp, g_opt, gl = g_step(gp, g_opt, dp, z)
        d_hist.append(float(dl))
        g_hist.append(float(gl))
        if (step + 1) % 25 == 0 or step == 0:
            print(f"step {step+1:4d} d_loss {float(dl):.3f} "
                  f"g_loss {float(gl):.3f} ({(time.time()-t0)*1e3:.0f}ms)")
        if (step + 1) % 100 == 0:
            mgr.save(step + 1, {"g": gp, "d": dp})
    mgr.save(args.steps, {"g": gp, "d": dp}, blocking=True)
    print(f"done. d_loss {d_hist[0]:.3f}->{d_hist[-1]:.3f}, "
          f"g_loss {g_hist[0]:.3f}->{g_hist[-1]:.3f}")
    return d_hist, g_hist


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
