"""DCGAN with amp — the multi-model / multi-optimizer / multi-loss exercise.

The reference's ``examples/dcgan`` is an empty README promising exactly
this usage; the API hooks it would exercise are ``amp.initialize`` with
model/optimizer *lists* and ``num_losses``, plus per-loss ``loss_id`` in
``scale_loss`` (reference ``frontend.py:248-254``,
``_initialize.py:232-236``). This example makes it concrete:

- two models (G, D) -> ``amp.initialize([netG, netD], [optG, optD],
  num_losses=3)``;
- three losses with independent dynamic scalers: D-on-real (loss_id 0),
  D-on-fake (loss_id 1), G (loss_id 2) — each can overflow and skip
  independently, the behavior the big L0 cross-product test validates in
  the reference (``test_multiple_models_optimizers_losses.py``);
- D's two loss grads are accumulated with per-loss unscaling via
  ``unscale_grads(..., stashed=...)`` — the ``unscale_with_stashed``
  path (reference ``scaler.py:149-180``).

Data is synthetic noise-shaped images by default (no dataset download);
the point is the amp protocol, not FID.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from apex_tpu import amp, models
from apex_tpu.utils import AverageMeter, maybe_print
from apex_tpu.utils.compile_cache import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser(description="DCGAN amp example (TPU)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--b", "--batch-size", type=int, default=64, dest="b")
    p.add_argument("--image-size", type=int, default=64)
    p.add_argument("--nz", type=int, default=100)
    p.add_argument("--lr", type=float, default=2e-4)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--opt-level", default="O1",
                   choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None)
    p.add_argument("--print-freq", type=int, default=5)
    return p.parse_args()


def main():
    args = parse_args()
    enable_compile_cache()

    netG = models.Generator(z_dim=args.nz)
    netD = models.Discriminator()
    optG_tx = optax.adam(args.lr, b1=args.beta1, b2=0.999)
    optD_tx = optax.adam(args.lr, b1=args.beta1, b2=0.999)

    # model list + optimizer list + 3 independently-scaled losses
    [netG, netD], [optG, optD] = amp.initialize(
        [netG, netD], [optG_tx, optD_tx], opt_level=args.opt_level,
        loss_scale=args.loss_scale, num_losses=3)

    rngG, rngD, rng_noise = jax.random.split(jax.random.PRNGKey(0), 3)
    z0 = jnp.ones((1, args.nz), jnp.float32)
    x0 = jnp.ones((1, args.image_size, args.image_size, 3), jnp.float32)
    varsG = netG.init(rngG, z0, train=True)
    varsD = netD.init(rngD, x0, train=True)
    pG, sG = varsG["params"], varsG.get("batch_stats", {})
    pD, sD = varsD["params"], varsD.get("batch_stats", {})
    optG_state = optG.init(pG)
    optD_state = optD.init(pD)

    def bce_logits(logits, target):
        return optax.sigmoid_binary_cross_entropy(
            logits, jnp.full_like(logits, target)).mean()

    @jax.jit
    def train_step(pG, sG, pD, sD, optG_state, optD_state, real, z):
        # ---- D step: two losses, two scalers, grad accumulation ----
        def d_real_loss(pd):
            logits, upd = netD.apply({"params": pd, "batch_stats": sD},
                                     real, train=True,
                                     mutable=["batch_stats"])
            loss = bce_logits(logits, 1.0)
            with amp.scale_loss(loss, optD_state, loss_id=0) as scaled:
                return scaled, (loss, upd["batch_stats"])
        gradsDr, (errD_real, sD1) = jax.grad(d_real_loss, has_aux=True)(pD)

        fake, sG1_upd = netG.apply({"params": pG, "batch_stats": sG}, z,
                                   train=True, mutable=["batch_stats"])

        def d_fake_loss(pd):
            logits, upd = netD.apply({"params": pd, "batch_stats": sD1},
                                     jax.lax.stop_gradient(fake), train=True,
                                     mutable=["batch_stats"])
            loss = bce_logits(logits, 0.0)
            with amp.scale_loss(loss, optD_state, loss_id=1) as scaled:
                return scaled, (loss, upd["batch_stats"])
        gradsDf, (errD_fake, sD2) = jax.grad(d_fake_loss, has_aux=True)(pD)

        # per-loss unscale; second call accumulates into the first's grads
        # (the unscale_with_stashed path, reference scaler.py:149-180)
        gDr, ovfr, optD_state1 = optD.unscale_grads(gradsDr, optD_state,
                                                    loss_id=0)
        gD, ovff, optD_state2 = optD.unscale_grads(gradsDf, optD_state1,
                                                   loss_id=1, stashed=gDr)
        pD_new, optD_state3 = optD.apply_gradients(pD, gD, optD_state2,
                                                   ovfr | ovff)

        # ---- G step: third loss, its own scaler ----
        def g_loss(pg):
            fake_g, updG = netG.apply({"params": pg, "batch_stats": sG}, z,
                                      train=True, mutable=["batch_stats"])
            logits = netD.apply({"params": pD_new, "batch_stats": sD2},
                                fake_g, train=True,
                                mutable=["batch_stats"])[0]
            loss = bce_logits(logits, 1.0)
            with amp.scale_loss(loss, optG_state, loss_id=2) as scaled:
                return scaled, (loss, updG["batch_stats"])
        gradsG, (errG, sG2) = jax.grad(g_loss, has_aux=True)(pG)
        pG_new, optG_state1 = optG.step(pG, gradsG, optG_state, loss_id=2)

        return (pG_new, sG2, pD_new, sD2, optG_state1, optD_state3,
                errD_real + errD_fake, errG)

    rng_np = np.random.RandomState(0)
    meterD, meterG, batch_time = AverageMeter(), AverageMeter(), AverageMeter()
    for epoch in range(args.epochs):
        end = time.time()
        for i in range(args.iters):
            real = jnp.asarray(rng_np.rand(
                args.b, args.image_size, args.image_size, 3)
                .astype(np.float32) * 2 - 1)
            rng_noise, sub = jax.random.split(rng_noise)
            z = jax.random.normal(sub, (args.b, args.nz))
            (pG, sG, pD, sD, optG_state, optD_state,
             errD, errG) = train_step(pG, sG, pD, sD, optG_state,
                                      optD_state, real, z)
            if i % args.print_freq == 0:
                batch_time.update(time.time() - end)
                meterD.update(float(errD))
                meterG.update(float(errG))
                maybe_print(
                    f"[{epoch}][{i}/{args.iters}] "
                    f"Loss_D {meterD.val:.4f} Loss_G {meterG.val:.4f} "
                    f"Time {batch_time.val:.3f} "
                    f"scales "
                    f"{float(optD.loss_scale(optD_state, 0)):.0f}/"
                    f"{float(optD.loss_scale(optD_state, 1)):.0f}/"
                    f"{float(optG.loss_scale(optG_state, 2)):.0f}",
                    rank0=True)
                end = time.time()


if __name__ == "__main__":
    main()
