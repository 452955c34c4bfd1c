"""A Qwen3-Next decoder: gated DeltaNet (delta-rule linear attention with one
decay a head and key heads shared by groups of value heads) three layers in
four, gated grouped-query softmax attention with rotary positions on a part
of each head the fourth, and in every layer softmax-routed experts beside a
gated shared one.

Built from a configuration in the keys of the model's own ``config.json``
(huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct): :func:`qwen3_next_from_config`
reads each layer's kind from it. The block shell, the expert layer of one
expert-parallel rank's share, the untied head and the next-token loss are
``models/decoder.py``'s, shared with ``models/kimi_linear.py``; the two
mixers are here. Every norm is zero-centred (``scale = 1 + w``, ``w`` zero
at init) but the one on the delta rule's output. The multi-token-prediction
module of the release lies outside the language model's forward and is not
built. Written for ``amp.auto_cast``: the projections are ``nn.Dense`` (half
under O1); the decay, the delta-rule state, the convolution, the rotation,
the router and the norms are float32 (``amp/lists.py``).

Every part runs under a ``jax.named_scope`` a device trace can be cut by:
``gdn/{proj,conv,gate,scan,out}``, ``gattn/{proj,rope,attn,out}``,
``moe/{route,dispatch,experts,combine,shared}``, ``lm/head``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import ops
from apex_tpu.models.decoder import (
    Decoder, ExpertFFN, RMSNorm, _conv_init, _dense, partial_rotary)
from apex_tpu.ops.delta_rule import gated_delta_rule
from apex_tpu.ops.short_conv import short_conv


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)``; the draw is kept off 0, whose log no update moves."""
    return jnp.log(jnp.maximum(
        jax.random.uniform(key, shape, dtype, 0.0, 16.0), 1e-6))


class GatedDeltaNet(nn.Module):
    """``o_t = S_t^T q_t`` of the gated delta rule with a scalar decay a
    value head; ``key_heads`` divides ``value_heads`` and key head ``j``
    serves the value heads ``j r ... (j + 1) r - 1``, ``r`` their ratio."""
    hidden: int
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        hk, hv, dk, dv = (self.key_heads, self.value_heads, self.key_dim,
                          self.value_dim)
        n_qk, n_v = hk * dk, hv * dv
        with jax.named_scope("gdn/proj"):
            qkvz = _dense(2 * n_qk + 2 * n_v, "qkvz_proj")(x)
            ba = _dense(2 * hv, "ba_proj")(x).astype(jnp.float32)
        with jax.named_scope("gdn/conv"):
            # one convolution over q, k and v together (the projection's
            # leading channels: z's are not read), heads side by side as
            # the scan reads them: q's heads l2-normalised and scaled, k's
            # normalised, v's plain
            qkv = short_conv(
                qkvz, self.param("conv", _conv_init,
                                 (self.conv_size, 2 * n_qk + n_v)),
                ((0, n_qk, dk ** -0.5), (n_qk, 2 * n_qk, 1.0)), dk)
            q, k, v = (qkv[..., :n_qk], qkv[..., n_qk:2 * n_qk],
                       qkv[..., 2 * n_qk:])
        with jax.named_scope("gdn/gate"):
            # the decay, one value a value head, in float32
            a_log = self.param("A_log", _a_log_init, (hv,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.ones, (hv,),
                                 jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(a_log) * jax.nn.softplus(ba[..., hv:] + dt_bias)
        with jax.named_scope("gdn/scan"):
            o = gated_delta_rule(q, k, v, g, beta, head_dim=dk)
        with jax.named_scope("gdn/out"):
            z = qkvz[..., 2 * n_qk + n_v:].reshape(b, t, hv, dv)
            o = RMSNorm(self.eps, name="o_norm")(o) * jax.nn.silu(
                z.astype(jnp.float32))
            return _dense(self.hidden, "o_proj")(o.reshape(b, t, n_v))


class GatedAttention(nn.Module):
    """Causal grouped-query softmax attention: q and k normalised a head
    (zero-centred RMSNorm), rotary on the first ``rotary_dim`` channels, and
    the output times the sigmoid of a gate that the q projection makes
    beside the query."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, hkv, d = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("gattn/proj"):
            # a head's query, then its gate
            qg = _dense(h * 2 * d, "q_proj")(x).reshape(b, t, h, 2 * d)
            k = _dense(hkv * d, "k_proj")(x).reshape(b, t, hkv, d)
            v = _dense(hkv * d, "v_proj")(x).reshape(b, t, hkv, d)
            q = RMSNorm(self.eps, True, name="q_norm")(qg[..., :d])
            k = RMSNorm(self.eps, True, name="k_norm")(k)
        with jax.named_scope("gattn/rope"):
            q, k = (partial_rotary(y, self.rotary_dim, self.rope_theta)
                    .astype(v.dtype) for y in (q, k))
        with jax.named_scope("gattn/attn"):
            o = ops.flash_attention(q, k, v, None, d ** -0.5, True)
        with jax.named_scope("gattn/out"):
            o = o.astype(jnp.float32) * jax.nn.sigmoid(
                qg[..., d:].astype(jnp.float32))
            return _dense(self.hidden, "o_proj")(o.reshape(b, t, h * d))


@dataclasses.dataclass(frozen=True)
class Qwen3NextDims:
    vocab_size: int
    hidden: int
    gdn_key_heads: int
    gdn_value_heads: int
    gdn_key_dim: int
    gdn_value_dim: int
    conv_size: int
    attn_heads: int
    kv_heads: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    dense_width: int
    expert_width: int
    shared_width: int
    n_routed: int
    top_k: int
    held: Tuple[int, ...]
    eps: float = 1e-6

    def mixer(self, kind):
        if kind == "gdn":
            return GatedDeltaNet(self.hidden, self.gdn_key_heads,
                                 self.gdn_value_heads, self.gdn_key_dim,
                                 self.gdn_value_dim, self.conv_size,
                                 self.eps, name="gdn")
        return GatedAttention(self.hidden, self.attn_heads, self.kv_heads,
                              self.head_dim, self.rotary_dim,
                              self.rope_theta, self.eps, name="gattn")

    def norm(self, name):
        return RMSNorm(self.eps, True, name=name)

    def experts(self):
        return ExpertFFN(self.hidden, self.expert_width, self.n_routed,
                         self.top_k, self.held, scale=1.0, scoring="softmax",
                         shared_gate=True, shared_width=self.shared_width,
                         name="moe")


class Qwen3Next(Decoder):
    """:class:`~apex_tpu.models.decoder.Decoder` over a
    :class:`Qwen3NextDims`; ``layer_kinds``: a ``("gdn" | "gattn", "dense" |
    "moe")`` pair a layer."""


def qwen3_next_from_config(config, remat=False):
    """The model of a configuration in the keys of the source's
    ``config.json``. Layers are numbered from 1 there: layer ``i`` is gated
    attention where ``i % full_attention_interval == 0`` and gated DeltaNet
    otherwise; its FFN is routed experts unless ``mlp_only_layers`` lists
    ``i - 1`` or ``i % decoder_sparse_step != 0`` (then a dense SwiGLU of
    ``intermediate_size``). ``num_experts`` is the number *held* (ids
    ``held_experts``, default the first ones) of the ``router_experts`` the
    router scores (default: all are held)."""
    if not config.get("norm_topk_prob", True):
        raise ValueError("only norm_topk_prob = true: the weights of the "
                         "chosen experts are normalised to sum to one")
    kinds = [
        ("gattn" if i % config["full_attention_interval"] == 0 else "gdn",
         "dense" if (i - 1 in config.get("mlp_only_layers", ())
                     or i % config.get("decoder_sparse_step", 1)) else "moe")
        for i in range(1, config["num_hidden_layers"] + 1)]
    head_dim = config["head_dim"]
    dims = Qwen3NextDims(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        gdn_key_heads=config["linear_num_key_heads"],
        gdn_value_heads=config["linear_num_value_heads"],
        gdn_key_dim=config["linear_key_head_dim"],
        gdn_value_dim=config["linear_value_head_dim"],
        conv_size=config["linear_conv_kernel_dim"],
        attn_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=head_dim,
        rotary_dim=int(head_dim * config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        n_routed=config.get("router_experts", config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        held=tuple(config.get("held_experts",
                              range(config["num_experts"]))),
        eps=config["rms_norm_eps"])
    return Qwen3Next(dims, tuple(kinds), remat)
