"""A Kimi-Linear decoder: delta-rule linear attention (KDA) three layers in
four, latent attention without positions (MLA, NoPE) the fourth, a dense
SwiGLU in the first layer and routed experts beside a shared one after it.

Built from a configuration in the keys of the model's own ``config.json``
(huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct; arXiv:2510.26692):
:func:`kimi_linear_from_config` reads each layer's kind from it. The block
shell, the norms, the expert layer of one expert-parallel rank's share, the
untied head and the next-token loss are ``models/decoder.py``'s, which
``models/qwen3_next.py`` shares; MLA is ``models/mla.py``'s (without
positions here), shared with ``models/deepseek_v3.py``; KDA is here.
Written for ``amp.auto_cast``: the projections are ``nn.Dense`` (half under
O1); the decay, the delta-rule state, the router and the norms are float32
(``amp/lists.py``).

Every part runs under a ``jax.named_scope`` a device trace can be cut by:
``kda/{proj,conv,gate,scan,out}``, ``mla/{proj,attn,out}``,
``moe/{route,dispatch,experts,combine,shared}``, ``lm/head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import (
    Decoder, ExpertFFN, RMSNorm, _conv_init, _dense, _init, lm_loss)
from apex_tpu.models.mla import LatentAttention
from apex_tpu.ops.delta_rule import gated_delta_rule
from apex_tpu.ops.short_conv import short_conv


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


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
            # heads side by side, (B, T, H d), as the scan reads them:
            # q's heads l2-normalised and scaled, k's normalised, v's plain
            q, k, v = (
                short_conv(y, self.param(n, _conv_init,
                                         (self.conv_size, h * d)), norm, d)
                for y, n, norm in zip(
                    qkv, ("q_conv", "k_conv", "v_conv"),
                    (((0, h * d, d ** -0.5),), ((0, h * d, 1.0),), ())))
        with jax.named_scope("kda/gate"):
            # the decay, a value for each key channel, in float32
            f_up = self.param("f_b", _init, (d, h * d), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", _dt_bias_init, (h * d,),
                                 jnp.float32)
            low = _dense(d, "f_a")(x).astype(jnp.float32)
            g = -jnp.repeat(jnp.exp(a_log), d) * jax.nn.softplus(
                low @ f_up + dt_bias)
            beta = jax.nn.sigmoid(_dense(h, "b_proj")(x).astype(jnp.float32))
            gate = _dense(h * d, "g_b")(_dense(d, "g_a")(x))
        o = gated_delta_rule(q, k, v, g, beta, head_dim=d)
        with jax.named_scope("kda/out"):
            o = RMSNorm(self.eps, name="o_norm")(o) * jax.nn.sigmoid(
                heads(gate).astype(jnp.float32))
            return _dense(self.hidden, "o_proj")(o.reshape(b, t, h * d))


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

    def mixer(self, kind):
        if kind == "kda":
            return KimiDeltaAttention(self.hidden, self.kda_heads,
                                      self.kda_head_dim, self.conv_size,
                                      self.eps, name="kda")
        return LatentAttention(self.hidden, self.mla_heads, self.kv_rank,
                               self.nope_dim, self.rope_dim, self.v_dim,
                               self.eps, name="mla")

    def norm(self, name):
        return RMSNorm(self.eps, name=name)

    def experts(self):
        return ExpertFFN(self.hidden, self.expert_width, self.n_routed,
                         self.top_k, self.held, self.route_scale, name="moe")


class KimiLinear(Decoder):
    """:class:`~apex_tpu.models.decoder.Decoder` over a
    :class:`KimiLinearDims`; ``layer_kinds``: a ``("kda" | "mla", "dense" |
    "moe")`` pair a layer."""


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
