"""A DeepSeek-V3 decoder: latent attention with a rotary part in every layer,
a dense SwiGLU in the leading layers and sigmoid-routed experts beside a
shared one after them.

Built from a configuration in the keys of the model's own ``config.json``
(``model_type`` ``deepseek_v3``; the public implementation is
``transformers``' ``modeling_deepseek_v3.py``): :func:`deepseek_v3_from_config`
reads each layer's FFN from ``first_k_dense_replace``. Attention is
``models/mla.py``'s with positions: each head's last ``qk_rope_head_dim``
query channels and the shared ``k_pe`` turned in interleaved pairs. The
block shell, the expert layer of one expert-parallel rank's share, the
untied head and the next-token loss are ``models/decoder.py``'s. Written
for ``amp.auto_cast``: the projections are ``nn.Dense`` (half under O1); the
rotation, the router and the norms are float32 (``amp/lists.py``).

Every part runs under a ``jax.named_scope`` a device trace can be cut by:
``mla/{proj,rope,attn,out}``, ``moe/{route,dispatch,experts,combine,shared}``,
``lm/head``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

from apex_tpu.models.decoder import Decoder, ExpertFFN, RMSNorm
from apex_tpu.models.mla import LatentAttention

#: keys of the source's config that the model holds to one value: a form of
#: the architecture this module does not build is an error, not ignored
_FIXED = (("q_lora_rank", None), ("rope_scaling", None),
          ("rope_interleave", True), ("scoring_func", "sigmoid"),
          ("topk_method", "noaux_tc"), ("n_group", 1), ("topk_group", 1),
          ("norm_topk_prob", True), ("moe_layer_freq", 1),
          ("attention_bias", False), ("tie_word_embeddings", False),
          ("hidden_act", "silu"))


@dataclasses.dataclass(frozen=True)
class DeepseekV3Dims:
    vocab_size: int
    hidden: int
    heads: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    dense_width: int
    expert_width: int
    shared_width: int
    n_routed: int
    top_k: int
    held: Tuple[int, ...]
    route_scale: float
    eps: float = 1e-6

    def mixer(self, kind):
        return LatentAttention(self.hidden, self.heads, self.kv_rank,
                               self.nope_dim, self.rope_dim, self.v_dim,
                               self.eps, self.rope_theta, name=kind)

    def norm(self, name):
        return RMSNorm(self.eps, name=name)

    def experts(self):
        return ExpertFFN(self.hidden, self.expert_width, self.n_routed,
                         self.top_k, self.held, self.route_scale,
                         shared_width=self.shared_width, name="moe")


class DeepseekV3(Decoder):
    """:class:`~apex_tpu.models.decoder.Decoder` over a
    :class:`DeepseekV3Dims`; ``layer_kinds``: a ``("mla", "dense" | "moe")``
    pair a layer."""


def deepseek_v3_from_config(config, remat=False):
    """The model of a configuration in the keys of the source's
    ``config.json``. Layer ``i`` (from 0) is a dense SwiGLU of
    ``intermediate_size`` while ``i < first_k_dense_replace`` and routed
    experts after: ``num_experts_per_tok`` of ``n_routed_experts`` by sigmoid
    score plus a selection bias, weights renormalised and times
    ``routed_scaling_factor``, beside one shared SwiGLU of ``n_shared_experts
    * moe_intermediate_size``. ``n_routed_experts`` is the number *held* (ids
    ``held_experts``, default the first ones) of the ``router_experts`` the
    router scores (default: all are held). Raises where the configuration
    asks for a form this module does not build (``_FIXED``, or k/v heads
    other than the q heads, a ``head_dim`` other than the rotated width or a
    ``qk_head_dim`` other than the two parts')."""
    for key, needed in _FIXED:
        if config.get(key, needed) != needed:
            raise ValueError(f"{key}: only {needed!r}")
    heads = config["num_attention_heads"]
    rope = config["qk_rope_head_dim"]
    nope = config["qk_nope_head_dim"]
    if (config.get("num_key_value_heads", heads) != heads
            or config.get("head_dim", rope) != rope
            or config.get("qk_head_dim", nope + rope) != nope + rope):
        raise ValueError("num_key_value_heads = num_attention_heads, "
                         "head_dim = qk_rope_head_dim and qk_head_dim = "
                         "qk_nope_head_dim + qk_rope_head_dim")
    dense = config["first_k_dense_replace"]
    kinds = tuple(("mla", "dense" if i < dense else "moe")
                  for i in range(config["num_hidden_layers"]))
    dims = DeepseekV3Dims(
        vocab_size=config["vocab_size"], hidden=config["hidden_size"],
        heads=heads, kv_rank=config["kv_lora_rank"],
        nope_dim=nope, rope_dim=rope,
        v_dim=config["v_head_dim"], rope_theta=float(config["rope_theta"]),
        dense_width=config["intermediate_size"],
        expert_width=config["moe_intermediate_size"],
        shared_width=(config["n_shared_experts"]
                      * config["moe_intermediate_size"]),
        n_routed=config.get("router_experts", config["n_routed_experts"]),
        top_k=config["num_experts_per_tok"],
        held=tuple(config.get("held_experts",
                              range(config["n_routed_experts"]))),
        route_scale=config["routed_scaling_factor"],
        eps=config["rms_norm_eps"])
    return DeepseekV3(dims, kinds, remat)
