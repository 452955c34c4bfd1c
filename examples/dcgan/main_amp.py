"""DCGAN — the multi-model / multi-loss amp example.

Reference: `examples/dcgan/main_amp.py:214-253` — the canonical exercise
of ``amp.initialize([netD, netG], [optD, optG], num_losses=3)`` with a
``loss_id`` per backward, so each of the three losses (D-real, D-fake, G)
gets its own loss scaler.

TPU-native: two Amp bundles (one per model/optimizer pair, D's with
``num_losses=2``), each backward tagged with its ``loss_id``. The whole
G+D update is one jitted step.

    python main_amp.py --niter 200 --batchSize 64 --opt_level O2
"""

import argparse

import os
import sys

# allow running from a source checkout without installation
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..")))

import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import amp, models
from apex_tpu.optim import FusedAdam
from apex_tpu.utils import enable_compile_cache


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--batchSize", type=int, default=64)
    p.add_argument("--imageSize", type=int, default=64)
    p.add_argument("--nz", type=int, default=100)
    p.add_argument("--ngf", type=int, default=64)
    p.add_argument("--ndf", type=int, default=64)
    p.add_argument("--niter", type=int, default=100,
                   help="number of steps (synthetic data)")
    p.add_argument("--lr", type=float, default=0.0002)
    p.add_argument("--beta1", type=float, default=0.5)
    p.add_argument("--manualSeed", type=int, default=0)
    p.add_argument("--opt_level", default="O2")
    p.add_argument("--print-freq", type=int, default=20)
    return p.parse_args()


def bce_with_logits(logits, target):
    """Binary CE on logits — numerically safe in half precision, the
    fix-it the reference's banned-function message demands
    (`apex/amp/lists/functional_overrides.py` bans `binary_cross_entropy`
    on sigmoided inputs)."""
    logits = logits.astype(jnp.float32)
    return jnp.mean(jnp.maximum(logits, 0) - logits * target
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


def main():
    args = parse_args()
    enable_compile_cache()
    rng = np.random.RandomState(args.manualSeed)

    netG = models.Generator(nz=args.nz, ngf=args.ngf)
    netD = models.Discriminator(ndf=args.ndf)

    policy = amp.Policy.from_opt_level(args.opt_level)
    z0 = jnp.zeros((2, 1, 1, args.nz), jnp.float32)
    x0 = jnp.zeros((2, args.imageSize, args.imageSize, 3), jnp.float32)
    varG = netG.init(jax.random.PRNGKey(1), z0, train=True)
    varD = netD.init(jax.random.PRNGKey(2), x0, train=True)

    # amp.initialize([netD, netG], [optD, optG], num_losses=3)
    # (`examples/dcgan/main_amp.py:214`): D's bundle owns losses 0 (real)
    # and 1 (fake), G's bundle owns loss 2 — scaler-per-loss parity.
    ampD = amp.Amp(policy, FusedAdam(lr=args.lr, betas=(args.beta1, 0.999)),
                   num_losses=2)
    ampG = amp.Amp(policy, FusedAdam(lr=args.lr, betas=(args.beta1, 0.999)))
    stateD = ampD.init(varD["params"])
    stateG = ampG.init(varG["params"])
    bsD, bsG = varD["batch_stats"], varG["batch_stats"]

    def step(stateD, stateG, bsD, bsG, real, z):
        # --- update D: two backwards, two scalers ------------------------
        def d_real_loss(p):
            logits, mut = netD.apply({"params": p, "batch_stats": bsD},
                                     real, train=True,
                                     mutable=["batch_stats"])
            return bce_with_logits(logits, 1.0), mut["batch_stats"]

        (errD_real, bsD1), gR, stateD, finR = ampD.backward(
            stateD, d_real_loss, loss_id=0, has_aux=True)

        fake, mutG = netG.apply({"params": stateG.params if not
                                 policy.master_weights else
                                 policy.cast_params(stateG.params),
                                 "batch_stats": bsG},
                                z, train=True, mutable=["batch_stats"])

        def d_fake_loss(p):
            logits, mut = netD.apply({"params": p, "batch_stats": bsD1},
                                     jax.lax.stop_gradient(fake),
                                     train=True, mutable=["batch_stats"])
            return bce_with_logits(logits, 0.0), mut["batch_stats"]

        (errD_fake, bsD2), gF, stateD, finF = ampD.backward(
            stateD, d_fake_loss, loss_id=1, has_aux=True)

        grads = jax.tree_util.tree_map(lambda a, b: a + b, gR, gF)
        stateD = ampD.apply_gradients(
            stateD, grads, jnp.logical_and(finR, finF)
            if not isinstance(finR, bool) else (finR and finF))

        # --- update G ----------------------------------------------------
        def g_loss(p):
            img, mut = netG.apply({"params": p, "batch_stats": bsG},
                                  z, train=True, mutable=["batch_stats"])
            logits, _ = netD.apply(
                {"params": policy.cast_params(stateD.params),
                 "batch_stats": bsD2},
                img, train=True, mutable=["batch_stats"])
            return bce_with_logits(logits, 1.0), mut["batch_stats"]

        (errG, bsG1), gG, stateG, finG = ampG.backward(
            stateG, g_loss, loss_id=0, has_aux=True)
        stateG = ampG.apply_gradients(stateG, gG, finG)
        return stateD, stateG, bsD2, bsG1, errD_real + errD_fake, errG

    jstep = jax.jit(step, donate_argnums=(0, 1, 2, 3))

    t0 = time.perf_counter()
    for i in range(args.niter):
        real = jnp.asarray(
            rng.rand(args.batchSize, args.imageSize, args.imageSize, 3)
            .astype(np.float32) * 2 - 1)
        z = jnp.asarray(
            rng.randn(args.batchSize, 1, 1, args.nz).astype(np.float32))
        stateD, stateG, bsD, bsG, errD, errG = jstep(
            stateD, stateG, bsD, bsG, real, z)
        if (i + 1) % args.print_freq == 0:
            print(f"[{i+1}/{args.niter}] Loss_D {float(errD):.4f} "
                  f"Loss_G {float(errG):.4f} "
                  f"({args.batchSize*(i+1)/(time.perf_counter()-t0):.1f} "
                  "img/s)")


if __name__ == "__main__":
    main()
