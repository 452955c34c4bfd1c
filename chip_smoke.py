"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py

One process takes a few training steps of the two full-width models the
repo supports, over every local device, through the entry points a user
calls (``amp.Amp``/``amp.Policy``, ``optim.Fused*``,
``parallel.data_parallel_mesh`` + ``DistributedDataParallel`` +
``jax.shard_map``, ``data.DevicePrefetcher``):

- ResNet-50, 224x224, 128 images a chip, amp O2 + FusedSGD, SyncBN — the
  step and input path of ``examples/imagenet/main_amp.py``, called, not
  copied;
- BERT-Large (24 x 1024 x 16 heads, vocabulary 30 522), 16 sequences of
  512 a chip, amp O1 + FusedLAMB — ``bench._bert_step_builder``, the one
  construction the bench, the lint flagship and ``prof_bert.py`` share.
  This is the leg in which the Pallas attention, LayerNorm and
  cross-entropy kernels compile inside a real donated step.

Each leg must give finite losses that start near ln(classes) and move,
a ``state.step`` equal to the steps taken, and a lowered step that holds
Mosaic custom calls (no kernel was interpreted). On four or more chips
it also asserts what a never-distributed program gets wrong, and runs
``__graft_entry__.dryrun_multichip`` on the chips.

It needs a TPU: without one it says so and exits non-zero. The last line
of stdout is ``{"ok": true, "device": {...}}``; the lines before it give
cold-compile and steady step seconds per leg as information, not as a
benchmark. ``--rehearse`` runs the same code at toy sizes on whatever
backend there is (here: the CPU, kernels interpreted) to debug the
script itself; its last line says ``"ok": false, "rehearsal": true``.
"""

import argparse
import importlib.util
import json
import math
import os
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))

#: per-leg sizes: what the contract names, and the toy ones of --rehearse
FULL = {
    "resnet": {"arch": "resnet50", "size": 224, "per_chip": 128},
    "bert": {"encoder": None, "per_chip": 16, "seq": 512, "vocab": 30522},
    # SyncBN twin (four chips): same global batch on four devices and one
    "syncbn": {"arch": "resnet50", "size": 224, "global_batch": 64},
}
TOY = {
    "resnet": {"arch": "resnet18", "size": 32, "per_chip": 4},
    "bert": {"encoder": dict(vocab_size=512, hidden=64, layers=2, heads=2,
                             max_len=128),
             "per_chip": 2, "seq": 128, "vocab": 512},
    "syncbn": {"arch": "resnet18", "size": 32, "global_batch": 16},
}
STEPS = 4           # the first pays the compile, three run steady


def _imagenet_example():
    spec = importlib.util.spec_from_file_location(
        "imagenet_main_amp",
        os.path.join(_ROOT, "examples", "imagenet", "main_amp.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check(ok, what, *detail):
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}: {detail}")


def _check_leg(name, losses, n_classes, state):
    check(len(losses) == STEPS and all(math.isfinite(l) for l in losses),
          f"{name}: losses not finite", losses)
    # a fresh model predicts near-uniformly: the first loss sits near
    # ln(classes) — a wrong loss scale or a dead kernel does not
    ref = math.log(n_classes)
    check(abs(losses[0] - ref) < 0.5 * ref,
          f"{name}: first loss far from ln({n_classes})", losses[0], ref)
    check(max(losses) - min(losses) > 1e-3,
          f"{name}: the loss does not move", losses)
    check(int(state.step) == STEPS,
          f"{name}: state.step is not the steps taken", int(state.step))


def _mosaic_calls(lowered, on_tpu):
    n = lowered.as_text().count("tpu_custom_call")
    check(n > 0 or not on_tpu, "no Mosaic custom call in the lowered step "
                               "— the Pallas kernels were interpreted")
    return n


def _compile_delta(before):
    from apex_tpu.prof import compile_watch
    now = compile_watch.global_counters()
    req = int(now["compiles"] - before["compiles"])
    hits = int(now["cache_hits"] - before["cache_hits"])
    return {"compile_requests": req, "cache_hits": hits,
            "backend_compiles": req - hits,
            "compile_s": round(now["compile_secs"]
                               - before["compile_secs"], 2)}


def _run_imagenet(cfg, global_batch, steps, devices=None):
    """The example's own ``main``: O2 + FusedSGD + SyncBN + DDP over
    ``devices`` (default: all), synthetic input through its prefetcher."""
    return _imagenet_example().main(
        ["--arch", cfg["arch"], "--image-size", str(cfg["size"]),
         "-b", str(global_batch), "--opt-level", "O2", "--sync_bn",
         "--steps-per-epoch", str(steps), "--print-freq", str(steps)],
        devices=devices)


def resnet_leg(cfg, on_tpu):
    import jax

    from apex_tpu.prof import compile_watch

    n = jax.device_count()
    before = compile_watch.global_counters()
    run = _run_imagenet(cfg, cfg["per_chip"] * n, STEPS)
    _check_leg("resnet", run["losses"], 1000, run["state"])
    # the example's step, lowered on the arrays its run left behind (same
    # shapes and shardings as every step it took)
    run["lowered"] = run["step"].lower(
        run["state"], run["batch_stats"], *run["last_batch"])
    info = {"leg": "resnet", "arch": cfg["arch"], "image_size": cfg["size"],
            "global_batch": run["global_batch"], "n_devices": n,
            "losses": [round(l, 4) for l in run["losses"]],
            "first_step_s": round(run["first_step_s"], 2),
            "steady_step_s": round(run["steady_step_s"], 4),
            "mosaic_custom_calls": _mosaic_calls(run["lowered"], on_tpu),
            **_compile_delta(before)}
    return info, run


def bert_leg(cfg, on_tpu):
    """BERT MLM + LAMB through ``bench._bert_step_builder``, data-parallel
    over every local device."""
    import jax
    from jax.sharding import PartitionSpec as P

    import bench
    from apex_tpu import models, parallel
    from apex_tpu.prof import compile_watch

    before = compile_watch.global_counters()
    mesh = parallel.data_parallel_mesh()
    n = mesh.shape[parallel.DATA_AXIS]
    ddp = parallel.DistributedDataParallel(mesh)
    enc = (models.BertEncoder(**cfg["encoder"]) if cfg["encoder"]
           else models.BertLarge(cfg["vocab"]))
    step, state, batch, _policy, enc, _vars = bench._bert_step_builder(
        cfg["per_chip"] * n, cfg["seq"], encoder=enc, vocab=cfg["vocab"],
        ddp=ddp)
    jstep = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(ddp.axis_name), P(ddp.axis_name)),
        out_specs=(P(), P()), check_vma=False), donate_argnums=(0,))
    state = parallel.replicate(state, mesh)
    toks, labels = jax.device_put(batch, parallel.batch_sharding(mesh))
    lowered = jstep.lower(state, toks, labels)

    losses = []
    t0 = time.perf_counter()
    for i in range(STEPS):
        state, loss = jstep(state, toks, labels)
        losses.append(loss)
        if i == 0:
            jax.block_until_ready(loss)
            first_step_s = time.perf_counter() - t0
            t1 = time.perf_counter()
    jax.block_until_ready(state)
    steady_step_s = (time.perf_counter() - t1) / (STEPS - 1)
    losses = [float(l) for l in losses]
    _check_leg("bert", losses, enc.vocab_size, state)
    return {"leg": "bert", "layers": enc.layers, "hidden": enc.hidden,
            "heads": enc.heads, "vocab": enc.vocab_size,
            "global_batch": cfg["per_chip"] * n, "seq": cfg["seq"],
            "n_devices": n, "losses": [round(l, 4) for l in losses],
            "first_step_s": round(first_step_s, 2),
            "steady_step_s": round(steady_step_s, 4),
            "mosaic_custom_calls": _mosaic_calls(lowered, on_tpu),
            **_compile_delta(before)}


def distributed_phase(run, cfg, on_tpu):
    """What a program that never ran on more than one device gets wrong,
    asserted on the ResNet leg's own run, then the multichip dry run."""
    import jax
    import numpy as np

    from apex_tpu import lint

    devices = jax.devices()
    n = len(devices)
    ids = sorted(d.id for d in devices)

    # the batch: one addressable shard on each device
    xb, _ = run["last_batch"]
    shard_ids = sorted(s.device.id for s in xb.addressable_shards)
    check(shard_ids == ids and all(s.data.shape[0] * n == xb.shape[0]
                                   for s in xb.addressable_shards),
          "the batch is not one shard per device", shard_ids, ids)

    # every device holds state and says so (the CPU reports no stats)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    check(all(in_use) or not on_tpu,
          "a device reports no memory in use", in_use)

    # the compiled step reduces over ALL devices in one group
    schedule = lint.extract_collective_schedule(
        run["lowered"].compile().as_text())
    everyone = (tuple(range(n)),)
    whole_mesh = [c for c in schedule if c.opcode == "all-reduce"
                  and c.replica_groups == everyone]
    check(whole_mesh, "no all-reduce over all devices in the compiled step",
          [c.describe() for c in schedule])

    # parameters and BN statistics after the steps: equal on every device
    for leaf in jax.tree_util.tree_leaves(
            (run["state"].params, run["batch_stats"])):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        check(len(shards) == n
              and all(np.array_equal(shards[0], s) for s in shards[1:]),
              "replicas diverged after the steps", leaf.shape)

    # SyncBN: the running statistics N devices leave after one step on a
    # global batch are those one device leaves on the same batch
    multi = _run_imagenet(cfg, cfg["global_batch"], 1)
    single = _run_imagenet(cfg, cfg["global_batch"], 1,
                           devices=devices[:1])
    worst = 0.0
    for a, b in zip(jax.tree_util.tree_leaves(multi["batch_stats"]),
                    jax.tree_util.tree_leaves(single["batch_stats"])):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # bf16 activations reduced in another order: a few bf16 ulps
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-3)
        worst = max(worst, float(np.max(np.abs(a - b))))

    import __graft_entry__
    __graft_entry__.dryrun_multichip(4)

    return {"phase": "distributed", "n_devices": n,
            "batch_shard_devices": shard_ids,
            "bytes_in_use": in_use,
            "whole_mesh_all_reduces": len(whole_mesh),
            "collectives_in_step": len(schedule),
            "replicas_equal": True,
            "syncbn_vs_one_device_max_abs_diff": worst,
            "dryrun_multichip": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend, to debug this script; "
                         "never a pass")
    args = ap.parse_args(argv)

    import jax
    if args.rehearse:
        # so the four-device phase is rehearsed too (no effect on a TPU)
        jax.config.update("jax_num_cpu_devices", 4)

    from apex_tpu.arena import native_available
    from apex_tpu.prof import compile_watch, device_peak_flops
    from apex_tpu.utils import enable_compile_cache

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: needs a TPU, JAX found {device} — the "
              f"library would interpret every kernel here "
              f"(--rehearse runs toy sizes to debug this script)",
              file=sys.stderr)
        return 1
    if on_tpu:
        device_peak_flops(dev)      # a chip not in the peak table raises

    cfg = TOY if args.rehearse else FULL
    cache_dir = enable_compile_cache()
    compile_watch.install()
    counters0 = compile_watch.global_counters()
    print(json.dumps({"device": device, "rehearsal": args.rehearse,
                      "compile_cache": cache_dir,
                      "arena_native_available": native_available()}),
          flush=True)

    t0 = time.perf_counter()
    info, run = resnet_leg(cfg["resnet"], on_tpu)
    print(json.dumps(info), flush=True)
    print(json.dumps(bert_leg(cfg["bert"], on_tpu)), flush=True)
    if device["count"] >= 4:
        print(json.dumps(distributed_phase(run, cfg["syncbn"], on_tpu)),
              flush=True)
    print(json.dumps({"wall_s": round(time.perf_counter() - t0, 1),
                      **_compile_delta(counters0)}), flush=True)

    if args.rehearse:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
