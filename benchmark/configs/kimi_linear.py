"""Kimi-Linear next-token pre-training step on one expert-parallel rank's
share: amp O1 + FusedAdam (AdamW), data-parallel.

Built from the library's public API the way ``bert_large.py`` wraps BERT:
``models.kimi_linear_from_config`` at the sizes of ``kimi_linear.json`` (each
layer's kind read from it), ``models.lm_loss`` under ``amp.auto_cast``,
``amp.Amp`` round ``FusedAdam``, gradients synced by
``DistributedDataParallel`` inside ``jax.shard_map`` over every local
device, state donated. One chip and four run this same code.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, models, parallel
from apex_tpu.ops.delta_rule import CHUNK as SCAN_CHUNK
from apex_tpu.optim import FusedAdam


def flops_per_sequence(sizes, seq):
    """Operations the forward and backward of one sequence *require* at this
    share. 6 a token for each matmul parameter the token touches (a routed
    expert's by the expected share of rows that reach the held ones), causal
    attention's two matmuls over half the square, and the chunked delta
    rule's own matmuls (two (C, C, d) score products, the triangular solve,
    three state products and the intra-chunk output), forward once and
    backward twice. The embedding lookup, the norms, the convolution and
    anything computed again count nothing."""
    d = sizes["hidden_size"]
    lin = sizes["linear_attn_config"]
    hd = lin["num_heads"] * lin["head_dim"]
    low = lin["head_dim"]
    kda = d * (4 * hd + 2 * low + lin["num_heads"]) + 2 * low * hd
    heads = sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    dv = sizes["v_head_dim"]
    mla = (d * heads * qk + d * (sizes["kv_lora_rank"] + sizes["qk_rope_head_dim"])
           + sizes["kv_lora_rank"] * heads * (sizes["qk_nope_head_dim"] + dv)
           + heads * dv * d)
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes.get("router_experts", sizes["num_experts"])
    moe = (expert * sizes["num_shared_experts"] + d * routed
           + sizes["num_experts_per_token"] * sizes["num_experts"] / routed
           * expert)
    params = d * sizes["vocab_size"]
    attention = scan = 0.0
    c, dk = SCAN_CHUNK, lin["head_dim"]
    for i in range(1, sizes["num_hidden_layers"] + 1):
        if i in lin["kda_layers"]:
            params += kda
            chunks = -(-seq // c)
            scan += 3 * chunks * lin["num_heads"] * (
                2 * 2 * c * c * dk + c * c * 2 * dk + 2 * c * c * dk
                + 3 * 2 * c * dk * dk)
        else:
            params += mla
            attention += 3 * 2 * heads * (qk + dv) * seq * seq / 2
        params += (3 * d * sizes["intermediate_size"]
                   if i <= sizes["first_k_dense_replace"] else moe)
    return 6.0 * params * seq + attention + scan


def build(sizes, key, mesh, batch):
    tokens, = batch
    seq = tokens.shape[1]
    policy = amp.Policy.from_opt_level("O1")
    model = models.kimi_linear_from_config(sizes, remat=True)
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
