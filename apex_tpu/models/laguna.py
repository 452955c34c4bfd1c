"""A Laguna decoder: sliding-window grouped-query attention three layers in
four and global attention the fourth, each head's output scaled by a
sigmoid gate of its own, a dense SwiGLU in the leading layer and
softmax-routed experts beside an ungated shared one in the others.

Built from a configuration in the keys of the model's own ``config.json``
(huggingface.co/poolside/Laguna-S-2.1, ``model_type`` ``laguna``):
:func:`laguna_from_config` reads each layer's attention kind from
``layer_types``, its head count from ``num_attention_heads_per_layer`` and
its FFN from ``mlp_layer_types``. The two kinds differ in their heads (72
and 48 q heads on the same 8 k/v heads at Laguna-S-2.1's widths), their
rotation (``rope_parameters`` has one entry a kind: the window layers turn
every channel by the plain frequencies, the global ones half of each head by
YaRN's, times its attention factor) and their mask (the window's 512 newest
keys, or every earlier one). The block shell, the expert layer of one
expert-parallel rank's share, the untied head and the next-token loss are
``models/decoder.py``'s, shared with ``models/kimi_linear.py``,
``models/qwen3_next.py`` and ``models/lfm2.py``. Written for
``amp.auto_cast``: the projections are ``nn.Dense`` (half under O1); the
rotation, the gates, the router and the norms are float32
(``amp/lists.py``).

Every part runs under a ``jax.named_scope`` a device trace can be cut by:
``swa/{proj,rope,attn,out}`` in a window layer,
``fullattn/{proj,rope,attn,out}`` in a global one (``out`` is the gate and
``W_o``), ``moe/{route,dispatch,experts,combine,shared}``, ``lm/head``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu import ops
from apex_tpu.models.decoder import (
    Decoder, ExpertFFN, RMSNorm, _dense, partial_rotary, yarn_frequencies)

#: ``layer_types`` entry -> the mixer's kind (its module's and scopes' name)
KINDS = {"sliding_attention": "swa", "full_attention": "fullattn"}


@dataclasses.dataclass(frozen=True)
class Rotary:
    """How a kind of layer turns q and k: the first ``channels`` of each
    head at ``inv_freq`` (None: ``theta``'s plain frequencies), ``cos`` and
    ``sin`` times ``scale``."""
    channels: int
    theta: float
    inv_freq: Optional[Tuple[float, ...]] = None
    scale: float = 1.0

    def __call__(self, x):
        return partial_rotary(x, self.channels, self.theta,
                              inv_freq=self.inv_freq, scale=self.scale)


class HeadGatedAttention(nn.Module):
    """Causal grouped-query softmax attention over the newest ``window``
    keys (None: all of them), q head ``h`` reading k/v head ``h // (heads /
    kv_heads)``, rotary as ``rotary`` says, and each head's output times
    ``sigmoid(u w_h)`` of the layer's input ``u`` before ``W_o``. No q/k
    norm, no biases."""
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    rotary: Rotary
    window: Optional[int] = None

    @nn.compact
    def __call__(self, x):
        b, t, _ = x.shape
        h, hkv, d = self.heads, self.kv_heads, self.head_dim
        with jax.named_scope(f"{self.name}/proj"):
            q = _dense(h * d, "q_proj")(x).reshape(b, t, h, d)
            k = _dense(hkv * d, "k_proj")(x).reshape(b, t, hkv, d)
            v = _dense(hkv * d, "v_proj")(x).reshape(b, t, hkv, d)
        with jax.named_scope(f"{self.name}/rope"):
            q, k = (self.rotary(y).astype(v.dtype) for y in (q, k))
        with jax.named_scope(f"{self.name}/attn"):
            o = ops.flash_attention(q, k, v, None, d ** -0.5, True,
                                    window=self.window)
        with jax.named_scope(f"{self.name}/out"):
            gate = jax.nn.sigmoid(
                _dense(h, "g_proj")(x).astype(jnp.float32))
            o = o.astype(jnp.float32) * gate[..., None]
            return _dense(self.hidden, "o_proj")(o.reshape(b, t, h * d))


@dataclasses.dataclass(frozen=True)
class LagunaDims:
    """A layer's mixer kind is a ``(kind, q heads)`` pair: ``("swa", 72)``
    or ``("fullattn", 48)``; ``rotary`` holds one entry a kind."""
    vocab_size: int
    hidden: int
    kv_heads: int
    head_dim: int
    window: int
    rotary: Tuple[Tuple[str, Rotary], ...]
    dense_width: int
    expert_width: int
    shared_width: int
    n_routed: int
    top_k: int
    held: Tuple[int, ...]
    routed_scale: float = 1.0
    eps: float = 1e-6

    def mixer(self, kind):
        name, heads = kind
        return HeadGatedAttention(
            self.hidden, heads, self.kv_heads, self.head_dim,
            dict(self.rotary)[name],
            self.window if name == "swa" else None, name=name)

    def norm(self, name):
        return RMSNorm(self.eps, name=name)

    def experts(self):
        return ExpertFFN(self.hidden, self.expert_width, self.n_routed,
                         self.top_k, self.held, scale=self.routed_scale,
                         scoring="softmax", shared_width=self.shared_width,
                         name="moe")


class Laguna(Decoder):
    """:class:`~apex_tpu.models.decoder.Decoder` over a :class:`LagunaDims`;
    ``layer_kinds``: a ``(("swa" | "fullattn", q heads), "dense" | "moe")``
    pair a layer."""


def _rotary(rope, head_dim):
    channels = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") == "default":
        return Rotary(channels, theta)
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}: 'default' or "
                         "'yarn'")
    inv_freq, scale = yarn_frequencies(rope, channels)
    return Rotary(channels, theta, tuple(float(f) for f in inv_freq), scale)


def laguna_from_config(config, remat=False):
    """The model of a configuration in the keys of the source's
    ``config.json``. Layer ``i`` (from 0) is window attention where
    ``layer_types[i]`` is ``"sliding_attention"`` and global attention where
    it is ``"full_attention"``, with ``num_attention_heads_per_layer[i]`` q
    heads; its FFN is a dense SwiGLU of ``intermediate_size`` where
    ``mlp_layer_types[i]`` is ``"dense"`` and routed experts where it is
    ``"sparse"``. ``num_experts`` is the number *held* (ids
    ``held_experts``, default the first ones) of the ``router_experts`` the
    router scores (default: all are held)."""
    for key, needed in (("norm_topk_prob", True), ("attention_bias", False),
                        ("moe_apply_router_weight_on_input", False),
                        ("moe_router_logit_softcapping", 0),
                        ("tie_word_embeddings", False)):
        if config.get(key, needed) != needed:
            raise ValueError(f"only {key} = {needed}")
    n = config["num_hidden_layers"]
    types, mlps = config["layer_types"], config["mlp_layer_types"]
    heads = config["num_attention_heads_per_layer"]
    if (len(types) != n or len(mlps) != n or len(heads) != n
            or set(types) - set(KINDS) or set(mlps) - {"dense", "sparse"}
            or set(config.get("gating_types", ["per_head"])) != {"per_head"}):
        raise ValueError("layer_types, mlp_layer_types and "
                         "num_attention_heads_per_layer: one entry a layer "
                         "(of 'sliding_attention' | 'full_attention', 'dense' "
                         "| 'sparse'), per-head gates")
    kinds = [((KINDS[kind], h), "dense" if mlp == "dense" else "moe")
             for kind, mlp, h in zip(types, mlps, heads)]
    head_dim = config["head_dim"]
    dims = LagunaDims(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        kv_heads=config["num_key_value_heads"], head_dim=head_dim,
        window=config["sliding_window"],
        rotary=tuple((KINDS[kind], _rotary(rope, head_dim))
                     for kind, rope in config["rope_parameters"].items()),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=config["shared_expert_intermediate_size"],
        n_routed=config.get("router_experts", config["num_experts"]),
        top_k=config["num_experts_per_tok"],
        held=tuple(config.get("held_experts",
                              range(config["num_experts"]))),
        routed_scale=float(config.get("moe_routed_scaling_factor", 1.0)),
        eps=config["rms_norm_eps"])
    return Laguna(dims, tuple(kinds), remat)
