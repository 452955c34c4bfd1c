"""A Kimi-Linear decoder: delta-rule linear attention (KDA) three layers in
four, latent attention without positions (MLA, NoPE) the fourth, a dense
SwiGLU in the first layer and routed experts beside a shared one after it.

Built from a configuration in the keys of the model's own ``config.json``
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; arXiv:2510.26692):
:func:`kimi_linear_from_config` reads each layer's kind from it. Pre-norm
residual blocks over a float32 residual stream, RMSNorm, an untied head,
a next-token loss (:func:`lm_loss`). Written for ``amp.auto_cast``: the
projections are ``nn.Dense`` (half under O1); the decay, the delta-rule
state, the router and the norms are float32 (``amp/lists.py``).

**One expert-parallel rank's share.** ``held`` lists the ids of the experts
this rank holds, and the expert weights are ``(len(held), ...)``. The layer
routes every token over all ``n_routed`` experts, normalises the weights
over all the chosen ones, and returns the shared expert plus the chosen
experts *that are in* ``held``: what this rank adds to the all-reduced sum
of a deployment (the shared expert is every rank's alike, counted once).
With ``held = range(n_routed)`` it is the whole layer. No token is dropped
for any routing (``ops/moe.py``).

The loss returns, beside itself, what a monitor wants of the routing:
``rows_routed_here`` and ``expert_load``, a row for each expert layer.

Every part runs under a ``jax.named_scope`` a device trace can be cut by:
``kda/{proj,conv,gate,scan,out}``, ``mla/{proj,attn,out}``,
``moe/{route,dispatch,experts,combine,shared,overflow}``, ``lm/head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import ops
from apex_tpu.ops import moe
from apex_tpu.ops.delta_rule import gated_delta_rule

_init = nn.initializers.normal(0.02)
#: what a recomputed block keeps beside its input: whatever a forward kernel
#: of ``ops`` wrote and its backward reads, so the rerun holds no kernel
_KEEP_KERNEL_OUTPUTS = jax.checkpoint_policies.save_only_these_names(
    *ops.KEPT_NAMES)
#: (block_q, block_k) of MLA's attention. The kernels' VMEM ledger was
#: fitted at a head size of 64: at 192 the v5e's compiler refuses their
#: default 1024 x 1024 (17.7 MiB of the 16 MiB scoped VMEM in the forward)
#: and 512 x 512 (19.6 MiB in the dk/dv backward), and takes this
_ATTN_TILES = (1024, 256)


def _dense(features, name):
    return nn.Dense(features, use_bias=False, kernel_init=_init, name=name)


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, in float32."""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


def _conv_init(key, shape, dtype=jnp.float32):
    bound = shape[0] ** -0.5            # a depthwise Conv1d's default
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _short_conv(x, taps):
    """Causal depthwise convolution over the ``len(taps)`` newest tokens,
    then SiLU. ``x`` ``(B, T, C)``, ``taps`` ``(K, C)``, newest last."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j:j + t] * taps[j] for j in range(k)))


def _l2_normalised(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


class KimiDeltaAttention(nn.Module):
    hidden: int
    heads: int
    head_dim: int
    conv_size: int = 4
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, d = self.heads, self.head_dim
        heads = lambda y: y.reshape(b, t, h, -1)
        with jax.named_scope("kda/proj"):
            qkv = [_dense(h * d, n)(x) for n in ("q_proj", "k_proj", "v_proj")]
        with jax.named_scope("kda/conv"):
            q, k, v = (
                heads(_short_conv(y, self.param(n, _conv_init,
                                                (self.conv_size, h * d))))
                for y, n in zip(qkv, ("q_conv", "k_conv", "v_conv")))
            q = _l2_normalised(q) * d ** -0.5
            k = _l2_normalised(k)
        with jax.named_scope("kda/gate"):
            # the decay, a value for each key channel, in float32
            f_up = self.param("f_b", _init, (d, h * d), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (h * d,),
                                 jnp.float32)
            low = _dense(d, "f_a")(x).astype(jnp.float32)
            g = -jnp.exp(a_log)[:, None] * heads(
                jax.nn.softplus(low @ f_up + dt_bias))
            beta = jax.nn.sigmoid(_dense(h, "b_proj")(x).astype(jnp.float32))
            gate = _dense(h * d, "g_b")(_dense(d, "g_a")(x))
        o = gated_delta_rule(q, k, v, g, beta)
        with jax.named_scope("kda/out"):
            o = RMSNorm(self.eps, name="o_norm")(o) * jax.nn.sigmoid(
                heads(gate).astype(jnp.float32))
            return _dense(self.hidden, "o_proj")(o.reshape(b, t, h * d))


class LatentAttention(nn.Module):
    """Multi-head latent attention, no position encoding: the shared part of
    the key (``rope_dim`` wide) goes in unrotated."""
    hidden: int
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, dq = self.heads, self.nope_dim + self.rope_dim
        with jax.named_scope("mla/proj"):
            q = _dense(h * dq, "q_proj")(x).reshape(b, t, h, dq)
            c = _dense(self.kv_rank + self.rope_dim, "kv_a")(x)
            c_kv = RMSNorm(self.eps, name="kv_norm")(c[..., :self.kv_rank])
            kv = _dense(h * (self.nope_dim + self.v_dim), "kv_b")(c_kv)
            kv = kv.reshape(b, t, h, self.nope_dim + self.v_dim)
            k_pe = jnp.broadcast_to(c[:, :, None, self.kv_rank:].astype(
                kv.dtype), (b, t, h, self.rope_dim))
            k = jnp.concatenate([kv[..., :self.nope_dim], k_pe], -1)
            # the kernels take one head size: v's tail is zeros, dropped below
            v = jnp.pad(kv[..., self.nope_dim:],
                        [(0, 0)] * 3 + [(0, dq - self.v_dim)])
        with jax.named_scope("mla/attn"):
            o = ops.flash_attention(q, k, v, None, dq ** -0.5, True,
                                    *_ATTN_TILES)
            o = o[..., :self.v_dim].reshape(b, t, h * self.v_dim)
        with jax.named_scope("mla/out"):
            return _dense(self.hidden, "o_proj")(o)


class SwiGLU(nn.Module):
    hidden: int
    width: int

    @nn.compact
    def __call__(self, x):
        gate = _dense(self.width, "gate_proj")(x)
        up = _dense(self.width, "up_proj")(x)
        return _dense(self.hidden, "down_proj")(jax.nn.silu(gate) * up)


class ExpertFFN(nn.Module):
    """The shared expert plus this rank's share of the routed ones. Returns
    ``(y, expert_load)``."""
    hidden: int
    width: int
    n_routed: int
    top_k: int
    held: Sequence[int]
    scale: float

    @nn.compact
    def __call__(self, x):
        n = len(self.held)
        router = self.param("router", _init, (self.hidden, self.n_routed),
                            jnp.float32)
        # the selection bias balances load outside the gradient: no cotangent
        bias = self.param("e_bias", nn.initializers.zeros, (self.n_routed,),
                          jnp.float32)
        w_gate, w_up = (self.param(name, _init, (n, self.hidden, self.width),
                                   jnp.float32)
                        for name in ("experts_gate", "experts_up"))
        w_down = self.param("experts_down", _init,
                            (n, self.width, self.hidden), jnp.float32)
        rows = x.reshape(-1, self.hidden)
        chosen, weights = moe.route(rows, router, bias, self.top_k, self.scale)
        y = moe.held_experts(rows, weights, chosen, w_gate, w_up, w_down,
                             tuple(self.held), self.n_routed)
        with jax.named_scope("moe/shared"):
            shared = SwiGLU(self.hidden, self.width, name="shared")(x)
        return (shared.astype(jnp.float32) + y.reshape(x.shape),
                moe.expert_load(chosen, self.held))


@dataclasses.dataclass(frozen=True)
class KimiLinearDims:
    vocab_size: int
    hidden: int
    kda_heads: int
    kda_head_dim: int
    conv_size: int
    mla_heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    dense_width: int
    expert_width: int
    n_routed: int
    top_k: int
    held: Tuple[int, ...]
    route_scale: float
    eps: float = 1e-5


class Block(nn.Module):
    """``h = x + Mix(norm(x))``, ``y = h + FFN(norm(h))``, with ``Mix`` KDA or
    MLA and ``FFN`` dense or experts, as ``kinds`` says. Returns ``(y,
    expert_load or None)``."""
    dims: KimiLinearDims
    kinds: Sequence[str]

    @nn.compact
    def __call__(self, x):
        d = self.dims
        mix = (KimiDeltaAttention(d.hidden, d.kda_heads, d.kda_head_dim,
                                  d.conv_size, d.eps, name="kda")
               if self.kinds[0] == "kda" else
               LatentAttention(d.hidden, d.mla_heads, d.kv_rank, d.nope_dim,
                               d.rope_dim, d.v_dim, d.eps, name="mla"))
        x = x + mix(RMSNorm(d.eps, name="attn_norm")(x))
        normed = RMSNorm(d.eps, name="ffn_norm")(x)
        if self.kinds[1] == "dense":
            return x + SwiGLU(d.hidden, d.dense_width, name="mlp")(normed), None
        y, load = ExpertFFN(d.hidden, d.expert_width, d.n_routed, d.top_k,
                            d.held, d.route_scale, name="moe")(normed)
        return x + y, load


class KimiLinear(nn.Module):
    """Token ids ``(B, T)`` to logits ``(B, T, V)`` and the expert layers'
    loads ``(n_moe, len(held))``.

    ``layer_kinds``: a ``("kda" | "mla", "dense" | "moe")`` pair a layer.
    ``remat``: run each block's forward again in the backward instead of
    keeping its activations. The rerun keeps a block's input and what the
    forward kernels of ``ops`` wrote (``ops.KEPT_NAMES``: the delta rule's
    output, chunk-start states and ``(I + A)^-1``; attention's ``o`` and
    ``lse``), so it holds no kernel: projections, convolution, gates, norms
    and the experts run again, a forward kernel runs once a step.
    """
    dims: KimiLinearDims
    layer_kinds: Sequence[Any]
    remat: bool = False

    @nn.compact
    def __call__(self, tokens):
        d = self.dims
        x = nn.Embed(d.vocab_size, d.hidden, embedding_init=_init,
                     name="embed")(tokens).astype(jnp.float32)
        block = (nn.remat(Block, policy=_KEEP_KERNEL_OUTPUTS)
                 if self.remat else Block)
        loads = []
        for i, kinds in enumerate(self.layer_kinds):
            x, load = block(d, tuple(kinds), name=f"layers_{i}")(x)
            if load is not None:
                loads.append(load)
        x = RMSNorm(d.eps, name="final_norm")(x)
        head = self.param("lm_head", _init, (d.hidden, d.vocab_size),
                          jnp.float32)
        with jax.named_scope("lm/head"):
            from apex_tpu.amp.policy import current_policy
            dtype = current_policy().op_dtype("matmul", x.dtype)
            logits = x.astype(dtype) @ head.astype(dtype)
        return logits, (jnp.stack(loads) if loads else
                        jnp.zeros((0, len(d.held)), jnp.int32))


def kimi_linear_from_config(config, remat=False):
    """The model of a configuration in the keys of the source's
    ``config.json``. Layers are numbered from 1 there: layer ``i`` is KDA or
    MLA as ``linear_attn_config`` lists it, dense while ``i <=
    first_k_dense_replace`` and routed experts after. ``num_experts`` is the
    number *held* (ids ``held_experts``, default the first ones) of the
    ``router_experts`` the router scores (default: all are held)."""
    lin = config["linear_attn_config"]
    kinds = []
    for i in range(1, config["num_hidden_layers"] + 1):
        if i not in lin["kda_layers"] and i not in lin["full_attn_layers"]:
            raise ValueError(f"layer {i} is neither a KDA nor a full layer")
        kinds.append(("kda" if i in lin["kda_layers"] else "mla",
                      "dense" if i <= config["first_k_dense_replace"]
                      else "moe"))
    dims = KimiLinearDims(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        mla_heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"], nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        n_routed=config.get("router_experts", config["num_experts"]),
        top_k=config["num_experts_per_token"],
        held=tuple(config.get("held_experts",
                              range(config["num_experts"]))),
        route_scale=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"])
    return KimiLinear(dims, tuple(kinds), remat)


def lm_loss(model, variables, tokens):
    """Mean cross-entropy of token ``t + 1`` at position ``t`` (the last
    position of each sequence has no label), over the fused softmax-CE.
    Returns ``(loss, {"rows_routed_here", "expert_load"})``, one row for each
    expert layer."""
    logits, load = model.apply(variables, tokens)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], 1)
    with jax.named_scope("lm/head"):
        total = jnp.sum(ops.softmax_cross_entropy_loss(logits, labels))
    loss = total / max(labels.shape[0] * (labels.shape[1] - 1), 1)
    return loss, {"rows_routed_here": jnp.sum(load, -1), "expert_load": load}
