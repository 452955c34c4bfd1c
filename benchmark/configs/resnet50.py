"""ResNet-50 ImageNet training step: amp O2 + FusedSGD, SyncBN, data-parallel.

The step ``examples/imagenet/main_amp.py --opt-level O2 --sync_bn``
builds, from the library's public API: ``models.ResNet`` at the sizes of
``resnet50.json`` (what ``models.ResNet50(num_classes=1000)`` gives) in the
policy's compute dtype with fp32 master weights, the fused softmax
cross-entropy, ``amp.Amp`` round ``FusedSGD``, gradients synced by
``DistributedDataParallel`` inside ``jax.shard_map`` over every local
device, state and BN statistics donated.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, models, ops, parallel
from apex_tpu.optim import FusedSGD


def build(sizes, key, mesh, batch):
    images, _labels = batch
    policy = amp.Policy.from_opt_level("O2")
    model = models.ResNet(
        stage_sizes=sizes["stage_sizes"], block=models.BottleneckBlock,
        width=sizes["width"], num_classes=sizes["num_classes"],
        dtype=policy.compute_dtype, bn_axis_name=parallel.DATA_AXIS)
    amp_opt = amp.Amp(policy, FusedSGD(lr=0.1, momentum=0.9))
    ddp = parallel.DistributedDataParallel(mesh)

    def init(key):
        variables = model.init(
            key, jnp.zeros((2, *images.shape[1:]), images.dtype), train=True)
        return amp_opt.init(variables["params"]), variables["batch_stats"]

    carry = jax.jit(init, out_shardings=parallel.replicated(mesh))(key)

    def step(carry, images, labels):
        state, batch_stats = carry

        def loss_fn(params):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            loss = jnp.mean(ops.softmax_cross_entropy_loss(logits, labels))
            return loss, mutated["batch_stats"]

        (loss, batch_stats), grads, state, finite = amp_opt.backward(
            state, loss_fn, has_aux=True)
        grads = ddp.sync(grads)
        loss = ddp.pmean(loss)
        state = amp_opt.apply_gradients(state, grads, finite)
        return (state, batch_stats), loss, jnp.asarray(finite)

    axis = ddp.axis_name
    return {
        "step": jax.jit(
            jax.shard_map(step, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
                          out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0,)),
        "carry": carry,
        "classes": sizes["num_classes"],
        # the count is for the published 224x224 input
        "flops_per_sample": sizes["flops_per_sample"]
        * (images.shape[1] * images.shape[2]) / (224 * 224),
        "steps_taken": lambda carry: int(carry[0].step),
        "params": lambda carry: carry[0].params,
    }
