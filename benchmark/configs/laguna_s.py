"""Laguna-S-2.1 next-token pre-training step on one expert-parallel rank's
share: amp O1 + FusedAdam (AdamW), data-parallel.

Built from the library's public API the way ``lfm2_moe.py`` wraps LFM2:
``models.laguna_from_config`` at the sizes of ``laguna_s.json`` (each
layer's attention kind, head count and FFN read from its ``layer_types``,
``num_attention_heads_per_layer`` and ``mlp_layer_types``),
``models.lm_loss`` under ``amp.auto_cast``, ``amp.Amp`` round
``FusedAdam``, gradients synced by ``DistributedDataParallel`` inside
``jax.shard_map`` over every local device, state donated. One chip and four
run this same code.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, models, parallel
from apex_tpu.optim import FusedAdam


def flops_per_sequence(sizes, seq):
    """Operations the forward and backward of one sequence *require* at this
    share. 6 a token for each matmul parameter the token touches (a routed
    expert's by the expected share of rows that reach the held ones; the
    head's matmul, not the embedding's lookup) and attention's two matmuls
    over the (query, key) pairs its mask keeps (``sum_t min(t + 1, w)``,
    ``w`` the window in a sliding layer and ``seq`` in a global one; a k/v
    head is shared, its products are not), forward once and backward twice.
    The norms, the rotation, the gates' sigmoid and anything computed again
    count nothing."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    kv = sizes["num_key_value_heads"]
    routed = sizes.get("router_experts", sizes["num_experts"])
    moe = (d * routed + 3 * d * sizes["shared_expert_intermediate_size"]
           + sizes["num_experts_per_tok"] * sizes["num_experts"] / routed
           * 3 * d * sizes["moe_intermediate_size"])
    dense = 3 * d * sizes["intermediate_size"]
    params = d * sizes["vocab_size"]
    attention = 0.0
    for kind, mlp, heads in zip(sizes["layer_types"],
                                sizes["mlp_layer_types"],
                                sizes["num_attention_heads_per_layer"]):
        params += d * (heads + 2 * kv) * hd + heads * hd * d + d * heads
        params += dense if mlp == "dense" else moe
        w = (min(sizes["sliding_window"], seq)
             if kind == "sliding_attention" else seq)
        pairs = w * (w + 1) // 2 + (seq - w) * w
        attention += 3 * 2 * 2 * hd * heads * pairs
    return 6.0 * params * seq + attention


def build(sizes, key, mesh, batch):
    tokens, = batch
    seq = tokens.shape[1]
    policy = amp.Policy.from_opt_level("O1")
    model = models.laguna_from_config(sizes, remat=True)
    amp_opt = amp.Amp(policy, FusedAdam(lr=3e-4, weight_decay=0.1))
    ddp = parallel.DistributedDataParallel(mesh)

    def init(key):
        variables = model.init(key, jnp.zeros((1, seq), jnp.int32))
        return amp_opt.init(variables["params"])

    # weights and optimizer state made on the devices, in one program
    state = jax.jit(init, out_shardings=parallel.replicated(mesh))(key)

    def loss_fn(params, tokens):
        with amp.auto_cast(policy):
            return models.lm_loss(model, {"params": params}, tokens)

    def logits_fn(params, tokens):
        with amp.auto_cast(policy):
            return model.apply({"params": params}, tokens)[0]

    def step(state, tokens):
        (loss, _routing), grads, state, finite = amp_opt.backward(
            state, loss_fn, tokens, has_aux=True)
        grads = ddp.sync(grads)
        loss = ddp.pmean(loss)
        state = amp_opt.apply_gradients(state, grads, finite)
        return state, loss, jnp.asarray(finite)

    axis = ddp.axis_name
    return {
        "step": jax.jit(
            jax.shard_map(step, mesh=mesh, in_specs=(P(), P(axis)),
                          out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0,)),
        "carry": state,
        "classes": sizes["vocab_size"],
        # what forward and backward require at this share; nothing that is
        # computed again is counted
        "flops_per_sample": flops_per_sequence(sizes, seq),
        "steps_taken": lambda state: int(state.step),
        "params": lambda state: state.params,
        "loss_fn": loss_fn,
        "logits_fn": logits_fn,
    }
