"""Per-op profile of the ResNet-50 bench step (PERF.md methodology).

Usage: python scripts/prof_resnet.py [--unfused] [--batch N] [--top N]
Prints device time, bytes accessed, MFU, and the top fusions.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    fused = "--unfused" not in sys.argv
    batch = 256
    top = 25
    if "--batch" in sys.argv:
        batch = int(sys.argv[sys.argv.index("--batch") + 1])
    if "--top" in sys.argv:
        top = int(sys.argv[sys.argv.index("--top") + 1])

    from apex_tpu import amp, models, ops, prof
    from apex_tpu.optim import FusedSGD
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()
    peak = prof.device_peak_flops()     # unknown device: raises

    policy = amp.Policy.from_opt_level("O2")
    dx_dist = os.environ.get("APEX_TPU_DX_DISTRIBUTE") or None
    model = models.ResNet50(num_classes=1000, dtype=policy.compute_dtype,
                            fused_bn=fused, dx_distribute=dx_dist)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 224, 224, 3).astype(np.float32))
    y = jnp.asarray(rng.randint(0, 1000, batch), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    amp_opt = amp.Amp(policy, FusedSGD(lr=0.1, momentum=0.9))
    state = amp_opt.init(params)

    # APEX_TPU_REMAT: checkpoint policy over the whole forward — the
    # round-5 bytes-vs-FLOPs experiment (PERF.md round-5 ResNet section)
    remat = os.environ.get("APEX_TPU_REMAT")  # "nothing" | "dots"

    def apply_fn(variables, xb, **kw):
        if not remat:
            return model.apply(variables, xb, **kw)
        pol = {"nothing": jax.checkpoint_policies.nothing_saveable,
               "dots": jax.checkpoint_policies.checkpoint_dots}[remat]

        def inner(mp, bs, xb):
            return model.apply({"params": mp, "batch_stats": bs}, xb,
                               train=True, mutable=["batch_stats"])

        return jax.checkpoint(inner, policy=pol)(
            variables["params"], variables["batch_stats"], xb)

    def step(state, batch_stats, xb, yb):
        def loss_fn(mp):
            logits, mut = apply_fn(
                {"params": mp, "batch_stats": batch_stats}, xb,
                train=True, mutable=["batch_stats"])
            loss = jnp.mean(ops.softmax_cross_entropy_loss(logits, yb))
            return loss, mut["batch_stats"]

        (loss, new_bs), grads, state, finite = amp_opt.backward(
            state, loss_fn, has_aux=True)
        state = amp_opt.apply_gradients(state, grads, finite)
        return state, new_bs, loss

    import tempfile
    import time

    jstep = jax.jit(step, donate_argnums=(0, 1))
    from apex_tpu.prof import hlo as _hlo
    cost = _hlo.cost_analysis(jstep, state, batch_stats, x, y)
    for _ in range(3):
        state, batch_stats, loss = jstep(state, batch_stats, x, y)
    float(loss)

    iters = 5
    logdir = tempfile.mkdtemp(prefix="apex_tpu_prof_")
    t0 = time.perf_counter()
    with prof.trace(logdir):
        for _ in range(iters):
            state, batch_stats, loss = jstep(state, batch_stats, x, y)
        float(loss)
    wall = (time.perf_counter() - t0) / iters

    from apex_tpu.prof import xplane as _xplane
    profile = _xplane.parse_trace(logdir)
    dev_us = profile.module_us_per_run()    # no device runs: raises
    print(f"fused_bn={fused} batch={batch}")
    print(f"wall/iter={wall*1e6:.0f}us device/iter={dev_us:.0f}us "
          f"flops={cost['flops']:.3g} bytes={cost['bytes_accessed']:.3g}")
    cats = "  ".join(f"{k}={v:.0f}us"
                     for k, v in list(profile.by_category().items())[:8])
    print(cats)
    print(profile.table(top=top))
    print("MFU:", cost["flops"] / (dev_us * 1e-6) / peak)
    print("img/s:", batch / (dev_us * 1e-6))


if __name__ == "__main__":
    main()
