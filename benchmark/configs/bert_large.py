"""BERT-Large masked-LM pre-training step: amp O1 + FusedLAMB, data-parallel.

Built from the library's public API the way a user wraps a flax model:
``models.BertEncoder`` at the sizes of ``bert_large.json`` (what
``models.BertLarge(30522)`` gives), ``models.mlm_loss`` under
``amp.auto_cast``, ``amp.Amp`` round ``FusedLAMB``, gradients synced by
``DistributedDataParallel`` inside ``jax.shard_map`` over every local
device, state donated. One chip and four run this same code.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, models, parallel
from apex_tpu.optim import FusedLAMB


def build(sizes, key, mesh, batch):
    tokens, _labels = batch
    seq = tokens.shape[1]
    policy = amp.Policy.from_opt_level("O1")
    enc = models.BertEncoder(
        vocab_size=sizes["vocab_size"], hidden=sizes["hidden_size"],
        layers=sizes["num_hidden_layers"],
        heads=sizes["num_attention_heads"],
        ffn_hidden=sizes["intermediate_size"],
        max_len=sizes["max_position_embeddings"],
        dropout=sizes["hidden_dropout_prob"])
    amp_opt = amp.Amp(policy, FusedLAMB(lr=1e-3))
    ddp = parallel.DistributedDataParallel(mesh)

    def init(key):
        variables = enc.init(key, jnp.zeros((1, seq), jnp.int32))
        return amp_opt.init(variables["params"])

    # weights and optimizer state made on the devices, in one program
    state = jax.jit(init, out_shardings=parallel.replicated(mesh))(key)

    def loss_fn(params, tokens, labels):
        with amp.auto_cast(policy):
            return models.mlm_loss(enc, {"params": params}, tokens, labels)

    def step(state, tokens, labels):
        loss, grads, state, finite = amp_opt.backward(
            state, loss_fn, tokens, labels)
        grads = ddp.sync(grads)
        loss = ddp.pmean(loss)
        state = amp_opt.apply_gradients(state, grads, finite)
        return state, loss, jnp.asarray(finite)

    axis = ddp.axis_name
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(state.params))
    return {
        "step": jax.jit(
            jax.shard_map(step, mesh=mesh, in_specs=(P(), P(axis), P(axis)),
                          out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0,)),
        "carry": state,
        "classes": sizes["vocab_size"],
        # forward + backward of a transformer: 6 FLOPs a parameter a token
        "flops_per_sample": 6.0 * n_params * seq,
        "steps_taken": lambda state: int(state.step),
        "params": lambda state: state.params,
        "loss_fn": loss_fn,
    }
