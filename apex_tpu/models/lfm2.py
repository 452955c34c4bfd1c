"""An LFM2-MoE decoder: a gated short convolution three layers in four,
grouped-query softmax attention with rotary positions over the whole of each
head the fourth, a dense SwiGLU in the leading layers and sigmoid-routed
experts with no shared expert in the others, the head tied to the embedding.

Built from a configuration in the keys of the model's own ``config.json``
(huggingface.co/LiquidAI/LFM2-24B-A2B, ``model_type`` ``lfm2_moe``):
:func:`lfm2_moe_from_config` reads each layer's kind from its
``layer_types``. The block shell, the expert layer of one expert-parallel
rank's share, the head and the next-token loss are ``models/decoder.py``'s,
shared with ``models/kimi_linear.py`` and ``models/qwen3_next.py``; the two
mixers are here, each the wiring of an op of ``ops``. Written for
``amp.auto_cast``: the projections are ``nn.Dense`` (half under O1); the
convolution with its two gates, the rotation, the router and the norms are
float32 (``amp/lists.py``).

Every part runs under a ``jax.named_scope`` a device trace can be cut by:
``lconv/{proj,conv,out}``, ``gqa/{proj,rope,attn,out}``,
``moe/{route,dispatch,experts,combine}``, ``lm/head``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax

from apex_tpu import ops
from apex_tpu.models.decoder import (
    Decoder, ExpertFFN, RMSNorm, _conv_init, _dense, partial_rotary)
from apex_tpu.ops.short_conv import short_conv


class GatedShortConv(nn.Module):
    """``[B; C; z] = x W_in``; ``y = (C * conv(B * z)) W_out`` with a causal
    depthwise convolution over the ``conv_size`` newest tokens, no bias and
    no activation."""
    hidden: int
    conv_size: int = 3

    @nn.compact
    def __call__(self, x):
        c = self.hidden
        with jax.named_scope("lconv/proj"):
            bcz = _dense(3 * c, "in_proj")(x)
        with jax.named_scope("lconv/conv"):
            # the convolution reads B times z (a product: which of the two
            # is the gate cannot be told) and C gates its result, all three
            # lane ranges of the projection as the GEMM wrote it; the op
            # takes its kernels where the channels are whole 128-lane tiles
            y = short_conv(
                bcz, self.param("conv", _conv_init, (self.conv_size, c)),
                (), math.gcd(c, 128), gates=(2 * c, c))
        with jax.named_scope("lconv/out"):
            return _dense(c, "out_proj")(y)


class GroupedQueryAttention(nn.Module):
    """Causal grouped-query softmax attention: q and k normalised a head
    (RMSNorm, one learned scale of ``head_dim``), then rotary on every
    channel; q head ``h`` reads k/v head ``h // (heads / kv_heads)``."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, hkv, d = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope("gqa/proj"):
            q = _dense(h * d, "q_proj")(x).reshape(b, t, h, d)
            k = _dense(hkv * d, "k_proj")(x).reshape(b, t, hkv, d)
            v = _dense(hkv * d, "v_proj")(x).reshape(b, t, hkv, d)
            q = RMSNorm(self.eps, name="q_norm")(q)
            k = RMSNorm(self.eps, name="k_norm")(k)
        with jax.named_scope("gqa/rope"):
            q, k = (partial_rotary(y, d, self.rope_theta).astype(v.dtype)
                    for y in (q, k))
        with jax.named_scope("gqa/attn"):
            o = ops.flash_attention(q, k, v, None, d ** -0.5, True)
        with jax.named_scope("gqa/out"):
            return _dense(self.hidden, "o_proj")(o.reshape(b, t, h * d))


@dataclasses.dataclass(frozen=True)
class Lfm2Dims:
    vocab_size: int
    hidden: int
    conv_size: int
    attn_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    dense_width: int
    expert_width: int
    n_routed: int
    top_k: int
    held: Tuple[int, ...]
    routed_scale: float = 1.0
    eps: float = 1e-5
    tied_head: bool = True
    #: no shared expert beside the routed ones
    shared_width: int = 0

    def mixer(self, kind):
        if kind == "lconv":
            return GatedShortConv(self.hidden, self.conv_size, name="lconv")
        return GroupedQueryAttention(self.hidden, self.attn_heads,
                                     self.kv_heads, self.head_dim,
                                     self.rope_theta, self.eps, name="gqa")

    def norm(self, name):
        return RMSNorm(self.eps, name=name)

    def experts(self):
        return ExpertFFN(self.hidden, self.expert_width, self.n_routed,
                         self.top_k, self.held, scale=self.routed_scale,
                         scoring="sigmoid", shared_width=self.shared_width,
                         name="moe")


class Lfm2Moe(Decoder):
    """:class:`~apex_tpu.models.decoder.Decoder` over an :class:`Lfm2Dims`;
    ``layer_kinds``: a ``("lconv" | "gqa", "dense" | "moe")`` pair a
    layer."""


def lfm2_moe_from_config(config, remat=False):
    """The model of a configuration in the keys of the source's
    ``config.json``. Layer ``i`` (from 0) is grouped-query attention where
    ``layer_types[i]`` is ``"full_attention"`` and a gated short convolution
    where it is ``"conv"``; its FFN is a dense SwiGLU of
    ``intermediate_size`` for ``i < num_dense_layers`` and routed experts
    after. ``num_experts`` is the number *held* (ids ``held_experts``,
    default the first ones) of the ``router_experts`` the router scores
    (default: all are held)."""
    for key, needed in (("norm_topk_prob", True), ("use_expert_bias", True),
                        ("conv_bias", False)):
        if config.get(key, needed) != needed:
            raise ValueError(f"only {key} = {needed}")
    types = config["layer_types"]
    if len(types) != config["num_hidden_layers"] or set(types) - {
            "conv", "full_attention"}:
        raise ValueError(f"layer_types {types}: one of 'conv', "
                         "'full_attention' a layer")
    kinds = [("gqa" if kind == "full_attention" else "lconv",
              "dense" if i < config["num_dense_layers"] else "moe")
             for i, kind in enumerate(types)]
    heads = config["num_attention_heads"]
    dims = Lfm2Dims(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        conv_size=config["conv_L_cache"], attn_heads=heads,
        kv_heads=config["num_key_value_heads"],
        head_dim=config.get("head_dim") or config["hidden_size"] // heads,
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        n_routed=config.get("router_experts", config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        held=tuple(config.get("held_experts",
                              range(config["num_experts"]))),
        routed_scale=float(config.get("routed_scaling_factor", 1.0)),
        eps=config["norm_eps"],
        tied_head=config.get("tie_word_embeddings", True))
    return Lfm2Moe(dims, tuple(kinds), remat)
