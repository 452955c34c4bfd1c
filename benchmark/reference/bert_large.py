"""BERT masked-LM loss in plain ``jax.numpy``, float32, no kernels.

The encoder as the configuration runs it (``configs/bert_large.json``,
``assumed``): token + position embeddings, LayerNorm (eps 1e-12), then per
layer {packed q/k/v projection, softmax(q k^T / sqrt(d)) v, output
projection, residual + LayerNorm, Dense - GELU(tanh) - Dense, residual +
LayerNorm} (post-LN, eps 1e-5), logits against the tied embedding, mean
cross-entropy over the labelled positions. It reads the model's own
parameter tree and shares no code with ``apex_tpu``.

``compare`` holds the system's own loss (``models.mlm_loss`` under
``auto_cast``, Pallas kernels compiled) against it on the first ``ROWS``
sequences of a batch, during set-up.

Tolerance. The system computes in bfloat16 (8 bits of mantissa) with
float32 accumulation and float32 softmax/LayerNorm statistics; the loss is
a mean over ``ROWS x 77`` labelled positions of values near ln(30522) =
10.3, so rounding errors of about 2**-9 relative in each logit largely
average out. On the chip, at the published widths, the two differed by
1.8e-5 to 1.9e-4 relative over the 8 seeds run (PERF.md, PR 24). ``REL_TOL``
= 2e-3 is ten times the largest. What it catches, probed in this
reference at the published width with 4 layers (PR 24): attention without
its 1/sqrt(d) moves the loss by 4.7e-2 relative, a dropped layer by
2.4e-1, LayerNorm with eps 1e-1 by 7.1e-2, labels shifted by one position
by 9.6e-3. What it cannot see is an error that leaves the mean loss alone;
a comparison of logits would (PERF.md, Open questions).
"""

import functools
import math

import jax
import jax.numpy as jnp

ROWS = 2
REL_TOL = 2e-3


def layer_norm(x, p, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def mlm_loss(params, tokens, labels, heads):
    params = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)
    emb = params["tok_emb"]["embedding"]
    rows, seq = tokens.shape
    x = emb[tokens] + params["pos_emb"][None, :seq]
    x = layer_norm(x, params["FusedLayerNormModule_0"], 1e-12)
    layers = sum(k.startswith("TransformerLayer_") for k in params)
    for i in range(layers):
        p = params[f"TransformerLayer_{i}"]
        attn = p["MultiheadAttention_0"]["SelfMultiheadAttn_0"]
        q, k, v = (t.reshape(rows, seq, heads, -1) for t in
                   jnp.split(dense(x, attn["qkv_proj"]), 3, axis=-1))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
        ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        out = dense(ctx.reshape(rows, seq, -1), attn["out_proj"])
        x = layer_norm(x + out, p["FusedLayerNormModule_0"], 1e-5)
        y = dense(jax.nn.gelu(dense(x, p["Dense_0"]), approximate=True),
                  p["Dense_1"])
        x = layer_norm(x + y, p["FusedLayerNormModule_1"], 1e-5)
    logp = jax.nn.log_softmax(x @ emb.T, -1)
    labelled = labels >= 0
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return -jnp.sum(jnp.where(labelled, picked, 0.0)) / jnp.maximum(
        jnp.sum(labelled), 1)


def compare(sizes, built, carry, batch):
    params = built["params"](carry)
    everywhere = jax.tree_util.tree_leaves(params)[0].sharding
    tokens, labels = (jax.device_put(x[:ROWS], everywhere) for x in batch)
    system = float(jax.jit(built["loss_fn"])(params, tokens, labels))
    with jax.default_matmul_precision("highest"):
        plain = float(jax.jit(functools.partial(
            mlm_loss, heads=sizes["num_attention_heads"]))(
                params, tokens, labels))
    rel = abs(system - plain) / abs(plain)
    return {"ok": rel <= REL_TOL, "system_loss": system,
            "reference_loss": plain, "rel_diff": rel, "rel_tol": REL_TOL,
            "rows": ROWS}
