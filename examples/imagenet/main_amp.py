"""ImageNet ResNet trainer — the `examples/imagenet/main_amp.py` mirror.

Reference: `examples/imagenet/main_amp.py` (argparse flags mapping 1:1 to
``amp.initialize`` kwargs `:157-161`, ``--sync_bn`` conversion `:142-145`,
apex DDP wrap `:168-175`, CUDA-stream ``data_prefetcher`` with async H2D +
fp16 cast `:264-317`, train loop printing img/s `:319`).

TPU-native translation:

- one SPMD program over a data mesh replaces the per-rank launch;
  ``--local_rank`` is gone (`jax.distributed` handles multi-host);
- the prefetcher overlaps host→device transfer with compute by keeping
  ``--prefetch`` batches in flight (JAX dispatch is async, so a plain
  bounded queue of device-put batches is the whole machinery);
- ``--opt-level/--keep-batchnorm-fp32/--loss-scale`` build the Policy
  exactly like the reference feeds ``amp.initialize``.

Runs out of the box on synthetic data (no dataset in the image); point
``--data`` at an ImageFolder-style tree to train on real JPEGs through
``apex_tpu.data`` (threaded PIL decode + RandomResizedCrop/flip + device
prefetch). At startup with ``--data`` the loader-only throughput is
measured and printed next to the compute throughput, so input-bound
configs are called out explicitly.

    python main_amp.py -b 128 --epochs 1 --steps-per-epoch 50
    python main_amp.py --sync_bn --opt-level O2 --loss-scale dynamic
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
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, models, ops, parallel
from apex_tpu.data import (DevicePrefetcher, ImageFolderSource,
                           measure_source, synthetic_source)
from apex_tpu.optim import FusedSGD
from apex_tpu.utils import enable_compile_cache


ARCHS = {
    "resnet18": models.ResNet18,
    "resnet50": models.ResNet50,
    "resnet101": models.ResNet101,
}


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description="apex_tpu ImageNet")
    parser.add_argument("--data", metavar="DIR", default=None,
                        help="path to dataset (synthetic if omitted)")
    parser.add_argument("--arch", "-a", default="resnet50", choices=ARCHS)
    parser.add_argument("--epochs", default=1, type=int)
    parser.add_argument("--steps-per-epoch", default=100, type=int)
    parser.add_argument("-b", "--batch-size", default=128, type=int,
                        help="GLOBAL batch size (split over the mesh)")
    parser.add_argument("--lr", "--learning-rate", default=0.1, type=float)
    parser.add_argument("--momentum", default=0.9, type=float)
    parser.add_argument("--weight-decay", "--wd", default=1e-4, type=float)
    parser.add_argument("--print-freq", "-p", default=10, type=int)
    parser.add_argument("--image-size", default=224, type=int)
    parser.add_argument("--prof", default=-1, type=int,
                        help="profile this many steps into ./prof_trace")
    parser.add_argument("--deterministic", action="store_true")
    parser.add_argument("--sync_bn", action="store_true",
                        help="sync BN stats over the data axis")
    parser.add_argument("--opt-level", type=str, default="O2")
    parser.add_argument("--keep-batchnorm-fp32", type=str, default=None)
    parser.add_argument("--loss-scale", type=str, default=None)
    parser.add_argument("--cache", metavar="CACHEDIR", default=None,
                        help="packed pre-decoded uint8 shard cache "
                             "(built from --data on first use) — the "
                             "DALI-class input path")
    parser.add_argument("--prefetch", default=2, type=int)
    parser.add_argument("--loader-workers", default=None, type=int,
                        help="decode threads for --data (default: cores)")
    return parser.parse_args(argv)


# the device-put prefetcher lives in apex_tpu.data now; keep the example
# name for readers of the reference script
Prefetcher = DevicePrefetcher
synthetic_batches = synthetic_source


def main(argv=None, devices=None):
    """Train; ``argv`` defaults to the command line and ``devices`` to
    every local device. Returns a summary of the run (losses, timings,
    the final state, the last batch and the jitted step — which
    ``step.lower(state, batch_stats, *last_batch)`` lowers again) for
    callers that check it —
    ``chip_smoke.py`` drives this function rather than a copy of it."""
    args = parse_args(argv)
    enable_compile_cache()
    if args.deterministic:
        # one seed, highest matmul precision — the cudnn.deterministic
        # analogue (`main_amp.py:120-128`)
        jax.config.update("jax_default_matmul_precision", "highest")

    mesh = parallel.data_parallel_mesh(devices)
    n_dev = mesh.shape[parallel.DATA_AXIS]
    if args.batch_size % n_dev:
        raise SystemExit(f"global batch {args.batch_size} must divide "
                         f"over {n_dev} devices")

    # --opt-level/--keep-batchnorm-fp32/--loss-scale -> Policy, exactly the
    # reference's amp.initialize kwarg plumbing (`main_amp.py:157-161`)
    overrides = {}
    if args.keep_batchnorm_fp32 is not None:
        overrides["keep_batchnorm_fp32"] = \
            args.keep_batchnorm_fp32.lower() == "true"
    if args.loss_scale is not None:
        overrides["loss_scale"] = (
            "dynamic" if args.loss_scale == "dynamic"
            else float(args.loss_scale))
    policy = amp.Policy.from_opt_level(args.opt_level, **overrides)

    model = ARCHS[args.arch](
        num_classes=1000, dtype=policy.compute_dtype,
        bn_axis_name=parallel.DATA_AXIS if args.sync_bn else None)

    ddp = parallel.DistributedDataParallel(mesh)
    tx = FusedSGD(lr=args.lr, momentum=args.momentum,
                  weight_decay=args.weight_decay)

    x0 = jnp.zeros((2, args.image_size, args.image_size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x0, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    amp_opt = amp.Amp(policy, tx)
    # the DDP construction-time broadcast: state starts replicated on the
    # mesh, so the first step already runs the steady-state executable
    state = parallel.replicate(amp_opt.init(params), mesh)
    batch_stats = parallel.replicate(batch_stats, mesh)

    def step(state, batch_stats, xb, yb):
        if xb.dtype == jnp.uint8:
            # packed-cache raw mode: normalize on-device (the DALI
            # GPU-side normalize — quarters host->device bytes and
            # keeps the single host core off the float convert)
            xb = xb.astype(policy.compute_dtype or jnp.float32) \
                * (1.0 / 255.0)

        def loss_fn(mp):
            logits, mut = model.apply(
                {"params": mp, "batch_stats": batch_stats}, xb, train=True,
                mutable=["batch_stats"])
            loss = jnp.mean(ops.softmax_cross_entropy_loss(logits, yb))
            acc = jnp.mean((jnp.argmax(logits, -1) == yb).astype(jnp.float32))
            return jax.lax.pmean(loss, ddp.axis_name), (mut["batch_stats"], acc)

        (loss, (new_bs, acc)), grads, state, finite = amp_opt.backward(
            state, loss_fn, has_aux=True)
        grads = ddp.sync(grads)
        state = amp_opt.apply_gradients(state, grads, finite)
        return state, new_bs, loss, jax.lax.pmean(acc, ddp.axis_name)

    spmd_step = jax.jit(
        jax.shard_map(step, mesh=mesh,
                      in_specs=(P(), P(), P(parallel.DATA_AXIS),
                                P(parallel.DATA_AXIS)),
                      out_specs=(P(), P(), P(), P()), check_vma=False),
        donate_argnums=(0, 1))

    batch_sharding = parallel.batch_sharding(mesh)
    folder = None
    if args.data and args.cache:
        from apex_tpu.data import PackedSource, build_cache
        build_cache(args.data, args.cache)
        # raw uint8 out: augmented crops ship as-is and normalize
        # on-device in the step (see the uint8 branch there)
        folder = PackedSource(args.cache, args.batch_size,
                              args.image_size, dtype=np.uint8,
                              workers=args.loader_workers)
    elif args.data:
        folder = ImageFolderSource(
            args.data, args.batch_size, args.image_size,
            workers=args.loader_workers)
    if folder is not None:
        # loader-only throughput probe: input-bound configs announced up
        # front instead of silently capping the training numbers. Runs
        # on its OWN source instance — probing the training source would
        # advance its epoch/shuffle state and make seeded runs
        # non-reproducible (ADVICE r3 item 3).
        if args.cache:
            from apex_tpu.data import PackedSource
            probe_ctx = PackedSource(args.cache, args.batch_size,
                                     args.image_size, dtype=np.uint8,
                                     workers=args.loader_workers)
        else:
            probe_ctx = ImageFolderSource(args.data, args.batch_size,
                                          args.image_size,
                                          workers=args.loader_workers)
        with probe_ctx as probe_src:
            probe = measure_source(
                probe_src.batches(min(6, args.steps_per_epoch) + 1),
                steps=min(5, args.steps_per_epoch))
        print(f"loader: {probe:.0f} img/s with {folder.workers} "
              f"{'cache-read' if args.cache else 'decode'} threads "
              f"(training is input-bound below this rate)")
    losses, last_batch = [], None
    first_step_s = t_steady = None
    for epoch in range(args.epochs):
        src = (folder.batches(args.steps_per_epoch)
               if folder is not None else
               synthetic_batches(args.batch_size, args.image_size,
                                 args.steps_per_epoch, seed=epoch))
        # transfer inputs pre-cast to the compute dtype — the reference
        # prefetcher's side-stream half cast (`main_amp.py:264-317`);
        # halves host->device bytes under O2/O3. Packed-cache batches
        # ship raw uint8 (already the smallest wire format; the step
        # normalizes on-device), so no host cast for THAT source —
        # keyed on the actual source kind, not the flag (synthetic
        # runs that happen to pass --cache still want the half cast).
        uint8_src = folder is not None and args.cache is not None
        cast = (policy.compute_dtype
                if policy.cast_model_type is not None and not uint8_src
                else None)
        pre = Prefetcher(src, sharding=batch_sharding, cast_dtype=cast,
                         depth=args.prefetch)

        t0, seen = time.perf_counter(), 0
        prof_ctx = None
        for i, (xb, yb) in enumerate(pre):
            if i == 0 and 0 < args.prof:
                prof_ctx = jax.profiler.trace("./prof_trace")
                prof_ctx.__enter__()
            state, batch_stats, loss, acc = spmd_step(
                state, batch_stats, xb, yb)
            losses.append(loss)
            last_batch = (xb, yb)
            if first_step_s is None:
                # the one step that pays the compile, timed on its own
                jax.block_until_ready(loss)
                first_step_s = time.perf_counter() - t0
                t_steady = time.perf_counter()
            seen += args.batch_size
            if prof_ctx is not None and i + 1 == args.prof:
                float(loss)
                prof_ctx.__exit__(None, None, None)
                prof_ctx = None
            if (i + 1) % args.print_freq == 0:
                lv = float(loss)          # syncs the pipeline
                dt = time.perf_counter() - t0
                print(f"epoch {epoch} step {i+1}: loss {lv:.4f} "
                      f"acc {float(acc):.3f}  {seen/dt:.1f} img/s "
                      f"({seen/dt/n_dev:.1f}/chip)")
        if prof_ctx is not None:
            prof_ctx.__exit__(None, None, None)
    jax.block_until_ready(state)
    steady_step_s = ((time.perf_counter() - t_steady) / (len(losses) - 1)
                     if len(losses) > 1 else None)
    print("done. amp state_dict:", amp_opt.state_dict(state))
    return {
        "global_batch": args.batch_size,
        "losses": [float(l) for l in losses],
        # the first step pays the compile; the rest are timed together,
        # closed once by block_until_ready
        "first_step_s": first_step_s, "steady_step_s": steady_step_s,
        "state": state, "batch_stats": batch_stats,
        "last_batch": last_batch, "step": spmd_step,
    }


if __name__ == "__main__":
    main()
