"""kanana-2-30b-a3b next-token pre-training step on one expert-parallel
rank's share: amp O1 + FusedAdam (AdamW), data-parallel.

Built from the library's public API the way ``kimi_linear.py`` wraps
Kimi-Linear: ``models.deepseek_v3_from_config`` at the sizes of
``kanana2.json`` (each layer's FFN read from its ``first_k_dense_replace``),
``models.lm_loss`` under ``amp.auto_cast``, ``amp.Amp`` round ``FusedAdam``,
gradients synced by ``DistributedDataParallel`` inside ``jax.shard_map``
over every local device, state donated. One chip and four run this same
code.

The optimizer is DeepSeek-V3's published pre-training recipe (AdamW, betas
0.9 / 0.95, weight decay 0.1, the learning rate rising linearly from 0 to
2.2e-4 over the first 2000 steps): the cell's steps are the first of that
warm-up. At a constant 3e-4 from initialisation, as the sibling decoders
train, the expert layers' routing collapses within a window: the held
experts' rows fall from an even share to none, or one of them takes every
token, at steps the seed sets, and so does the work of a step (PERF.md
section 6).
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
    head's matmul, not the embedding's lookup) and causal attention's two
    matmuls over half the square at the q/k head (nope + rope) and the v
    head, forward once and backward twice. The norms, the rotation, the
    padding of v and anything computed again count nothing."""
    d, heads = sizes["hidden_size"], sizes["num_attention_heads"]
    nope, rope = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dv, rank = sizes["v_head_dim"], sizes["kv_lora_rank"]
    mla = (d * heads * (nope + rope) + d * (rank + rope)
           + rank * heads * (nope + dv) + heads * dv * d)
    expert = 3 * d * sizes["moe_intermediate_size"]
    routed = sizes.get("router_experts", sizes["n_routed_experts"])
    moe = (d * routed + sizes["n_shared_experts"] * expert
           + sizes["num_experts_per_tok"] * sizes["n_routed_experts"] / routed
           * expert)
    layers = sizes["num_hidden_layers"]
    dense = min(sizes["first_k_dense_replace"], layers)
    params = (d * sizes["vocab_size"] + layers * mla
              + dense * 3 * d * sizes["intermediate_size"]
              + (layers - dense) * moe)
    attention = layers * 3 * 2 * heads * (nope + rope + dv) * seq * seq / 2
    return 6.0 * params * seq + attention


#: DeepSeek-V3's peak learning rate and the steps its warm-up takes
PEAK_LR, WARMUP_STEPS = 2.2e-4, 2000


def learning_rate(count):
    """The rate of the optimizer's ``count``-th step (from 1)."""
    return PEAK_LR * jnp.minimum(count, WARMUP_STEPS) / WARMUP_STEPS


def build(sizes, key, mesh, batch):
    tokens, = batch
    seq = tokens.shape[1]
    policy = amp.Policy.from_opt_level("O1")
    model = models.deepseek_v3_from_config(sizes, remat=True)
    amp_opt = amp.Amp(policy, FusedAdam(lr=learning_rate, betas=(0.9, 0.95),
                                        weight_decay=0.1))
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
