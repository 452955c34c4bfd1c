"""What the decoders share: the pre-norm block shell over a float32 residual
stream, RMSNorm, SwiGLU, the expert layer of one expert-parallel rank, the
short convolution's taps, the rotary embedding (half-split or interleaved
pairs), the head (its own matrix or the embedding's) and the next-token
loss.

A decoder is :class:`Decoder` over a ``dims`` of its own (a frozen
dataclass: ``models/kimi_linear.py``, ``models/qwen3_next.py``,
``models/lfm2.py``, ``models/laguna.py``, ``models/deepseek_v3.py``). The
shell asks ``dims`` for what differs between them and holds no model's
name:

``dims.mixer(kind)``   the token mixer of a layer of that kind, a module
                       named by its kind (``kda``, ``mla``, ``gdn``,
                       ``gattn``, ``lconv``, ``gqa``); a kind is whatever
                       the layer's entry of ``layer_kinds`` holds, so a
                       model whose layers differ in more than the kind
                       carries it there (Laguna's ``("swa", 72)``: a
                       window layer of 72 q heads);
``dims.norm(name)``    the norm of the blocks and the final one;
``dims.experts()``     the :class:`ExpertFFN` of an expert layer;
``dims.tied_head``     where it is there and true, the logits are ``h E^T``
                       with the embedding ``E`` and there is no ``lm_head``;
``dims.hidden``, ``dims.vocab_size``, ``dims.dense_width``, ``dims.held``,
``dims.top_k``, ``dims.n_routed``.

Written for ``amp.auto_cast``: the projections are ``nn.Dense`` (half under
O1); the router, the convolution and the norms are float32
(``amp/lists.py``).

**One expert-parallel rank's share.** ``held`` lists the ids of the experts
this rank holds, and the expert weights are ``(len(held), ...)``. The layer
routes every token over all ``n_routed`` experts, normalises the weights
over all the chosen ones, and returns the shared expert plus the chosen
experts *that are in* ``held``: what this rank adds to the all-reduced sum
of a deployment (the shared expert, where the model has one, is every
rank's alike, counted once).
With ``held = range(n_routed)`` it is the whole layer. No token is dropped
for any routing, and there is one path: the assignments to held experts
become rows sorted by expert, the experts' matmuls are grouped matmuls over
the live rows alone, and gathers take the rows there and back
(``ops/moe.py``). A step's cost follows the rows its routing sent here.

The loss returns, beside itself, what a monitor wants of the routing:
``rows_routed_here``, ``expert_load`` and ``expert_rows_run`` (the rows the
grouped matmul visited: the live ones and the tiles' rounding), a row for
each expert layer.

The expert layer and the head run under ``jax.named_scope``s a device trace
can be cut by: ``moe/{route,dispatch,experts,combine,shared}``,
``lm/head``; each mixer names its own.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import ops
from apex_tpu.ops import moe

_init = nn.initializers.normal(0.02)
#: what a recomputed block keeps beside its input: whatever a forward kernel
#: of ``ops`` wrote and its backward reads, so the rerun holds no kernel
_KEEP_KERNEL_OUTPUTS = jax.checkpoint_policies.save_only_these_names(
    *ops.KEPT_NAMES)


def _dense(features, name):
    return nn.Dense(features, use_bias=False, kernel_init=_init, name=name)


class RMSNorm(nn.Module):
    """``x / rms(x) * scale`` over the last axis, in float32.
    ``zero_centred``: the learned ``scale`` starts at zero and the norm
    multiplies by ``1 + scale``."""
    eps: float = 1e-5
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.initializers.zeros if self.zero_centred
            else nn.initializers.ones, (x.shape[-1],), jnp.float32)
        if self.zero_centred:
            scale = 1.0 + scale
        x = x.astype(jnp.float32)
        return x * jax.lax.rsqrt(
            jnp.mean(jnp.square(x), -1, keepdims=True) + self.eps) * scale


def partial_rotary(x, rotary_dim, theta, positions=None, inv_freq=None,
                   scale=1.0):
    """Rotary position embedding on the first ``rotary_dim`` channels of each
    head, the rest left as they are. ``x`` ``(B, T, H, D)``; channel ``m <
    rotary_dim / 2`` pairs with ``m + rotary_dim / 2`` (half-split) and turns
    by ``p theta^(-2m / rotary_dim)`` at position ``p`` (``positions``
    ``(T,)``, default ``0 ... T - 1``). ``inv_freq`` (``rotary_dim / 2``
    numbers) replaces ``theta^(-2m / rotary_dim)``, and ``cos`` and ``sin``
    are multiplied by ``scale``: YaRN's frequencies and attention factor
    (:func:`yarn_frequencies`). In the policy's dtype for ``rotary`` (a FLOAT
    op): float32."""
    from apex_tpu.amp.policy import current_policy
    x = x.astype(current_policy().op_dtype("rotary", x.dtype))
    half = rotary_dim // 2
    if positions is None:
        positions = jnp.arange(x.shape[1])
    freq = (theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / rotary_dim)
            if inv_freq is None else jnp.asarray(inv_freq, jnp.float32))
    angle = positions.astype(jnp.float32)[:, None] * freq         # (T, half)
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    if scale != 1.0:            # no multiply by one in the lowered text
        cos, sin = cos * scale, sin * scale
    cos, sin = cos.astype(x.dtype), sin.astype(x.dtype)
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rotary_dim:]], -1)


def interleaved_rotary(x, theta):
    """Rotary position embedding over every channel of each head in
    interleaved pairs ``(2i, 2i + 1)``, as ``transformers``'
    ``apply_rotary_pos_emb_interleave``: pair ``i`` turns by ``p
    theta^(-2i / D)`` at position ``p``, and the result is laid out
    de-interleaved (the pairs' first channels, then their second). q and k
    both taken through it, their scores equal those of pairs turned in
    place. ``x`` ``(B, T, H, D)``; float32, as :func:`partial_rotary`."""
    return partial_rotary(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                          x.shape[-1], theta)


def yarn_frequencies(rope, rotary_dim):
    """``(inv_freq, attention_factor)`` of a ``rope_type`` ``"yarn"`` entry
    of a ``rope_parameters`` (``rope_theta``, ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    ``attention_factor``) for ``rotary_dim`` rotated channels, as numpy
    float32. Pair ``m`` turns at ``theta^(-2m / rotary_dim)`` below the
    correction range, at that over ``factor`` from its top on, and on the
    linear ramp between; the range's ends are the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context,
    ``floor`` and ``ceil`` of ``rotary_dim ln(L / (2 pi beta)) / (2 ln
    theta)``. Without an ``attention_factor`` it is ``0.1 ln(factor) + 1``."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    length = rope["original_max_position_embeddings"]

    def pair(turns):
        return rotary_dim * math.log(length / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair(rope["beta_fast"])), 0)
    high = min(math.ceil(pair(rope["beta_slow"])), rotary_dim - 1)
    kept = base ** -(np.arange(0, rotary_dim, 2, dtype=np.float32)
                     / rotary_dim)
    ramp = np.clip((np.arange(rotary_dim // 2, dtype=np.float32) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = kept / factor * ramp + kept * (1.0 - ramp)
    scale = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(scale)


def _conv_init(key, shape, dtype=jnp.float32):
    bound = shape[0] ** -0.5            # a depthwise Conv1d's default
    return jax.random.uniform(key, shape, dtype, -bound, bound)


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
    ``(y, expert_load)``.

    ``scoring``: ``"sigmoid"`` scores with a selection bias ``e_bias`` that
    no gradient moves, or ``"softmax"`` over all the experts and no bias
    (``ops.moe.route``). ``shared_gate``: the shared expert's output times
    ``sigmoid(w_s^T x)``, one learned gate a token. ``shared_width`` 0: no
    shared expert."""
    hidden: int
    width: int
    n_routed: int
    top_k: int
    held: Sequence[int]
    scale: float
    scoring: str = "sigmoid"
    shared_gate: bool = False
    shared_width: Optional[int] = None      # default: ``width``

    @nn.compact
    def __call__(self, x):
        n = len(self.held)
        router = self.param("router", _init, (self.hidden, self.n_routed),
                            jnp.float32)
        # the selection bias balances load outside the gradient: no cotangent
        bias = (self.param("e_bias", nn.initializers.zeros, (self.n_routed,),
                           jnp.float32)
                if self.scoring == "sigmoid" else None)
        w_gate, w_up = (self.param(name, _init, (n, self.hidden, self.width),
                                   jnp.float32)
                        for name in ("experts_gate", "experts_up"))
        w_down = self.param("experts_down", _init,
                            (n, self.width, self.hidden), jnp.float32)
        rows = x.reshape(-1, self.hidden)
        chosen, weights = moe.route(rows, router, bias, self.top_k,
                                    self.scale, self.scoring)
        y = moe.held_experts(rows, weights, chosen, w_gate, w_up, w_down,
                             tuple(self.held), self.n_routed)
        if self.shared_width == 0:
            return y.reshape(x.shape), moe.expert_load(chosen, self.held)
        with jax.named_scope("moe/shared"):
            shared = SwiGLU(self.hidden, self.shared_width or self.width,
                            name="shared")(x)
            if self.shared_gate:
                shared = shared * jax.nn.sigmoid(_dense(1, "shared_gate")(x))
        return (shared.astype(jnp.float32) + y.reshape(x.shape),
                moe.expert_load(chosen, self.held))


class Block(nn.Module):
    """``h = x + Mix(norm(x))``, ``y = h + FFN(norm(h))``, with ``Mix`` the
    ``dims``' mixer of kind ``kinds[0]`` and ``FFN`` dense or experts, as
    ``kinds[1]`` says. Returns ``(y, expert_load or None)``."""
    dims: Any
    kinds: Sequence[str]

    @nn.compact
    def __call__(self, x):
        d = self.dims
        x = x + d.mixer(self.kinds[0])(d.norm("attn_norm")(x))
        normed = d.norm("ffn_norm")(x)
        if self.kinds[1] == "dense":
            return x + SwiGLU(d.hidden, d.dense_width, name="mlp")(normed), None
        y, load = d.experts()(normed)
        return x + y, load


class Decoder(nn.Module):
    """Token ids ``(B, T)`` to logits ``(B, T, V)`` and the expert layers'
    loads ``(n_moe, len(held))``.

    ``layer_kinds``: a ``(mixer kind, "dense" | "moe")`` pair a layer.
    ``remat``: run each block's forward again in the backward instead of
    keeping its activations. The rerun keeps a block's input and what the
    forward kernels of ``ops`` wrote (``ops.KEPT_NAMES``: the delta rule's
    output, chunk-start states and ``(I + A)^-1``; attention's ``o`` and
    ``lse``), so it holds no kernel: projections, convolution, gates, norms
    and the experts run again, a forward kernel runs once a step.

    The head is a matrix of its own, ``lm_head``, unless ``dims.tied_head``:
    then the logits are ``h E^T`` with the embedding's ``E``, one parameter
    whose gradient is the sum of both uses.
    """
    dims: Any
    layer_kinds: Sequence[Any]
    remat: bool = False

    @nn.compact
    def __call__(self, tokens):
        d = self.dims
        embed = nn.Embed(d.vocab_size, d.hidden, embedding_init=_init,
                         name="embed")
        x = embed(tokens).astype(jnp.float32)
        block = (nn.remat(Block, policy=_KEEP_KERNEL_OUTPUTS)
                 if self.remat else Block)
        loads = []
        for i, kinds in enumerate(self.layer_kinds):
            x, load = block(d, tuple(kinds), name=f"layers_{i}")(x)
            if load is not None:
                loads.append(load)
        x = d.norm("final_norm")(x)
        tied = getattr(d, "tied_head", False)
        head = (embed.embedding if tied else
                self.param("lm_head", _init, (d.hidden, d.vocab_size),
                           jnp.float32))
        with jax.named_scope("lm/head"):
            from apex_tpu.amp.policy import current_policy
            dtype = current_policy().op_dtype("matmul", x.dtype)
            logits = (jnp.einsum("btd,vd->btv", x.astype(dtype),
                                 head.astype(dtype)) if tied
                      else x.astype(dtype) @ head.astype(dtype))
        return logits, (jnp.stack(loads) if loads else
                        jnp.zeros((0, len(d.held)), jnp.int32))


def lm_loss(model, variables, tokens):
    """Mean cross-entropy of token ``t + 1`` at position ``t`` (the last
    position of each sequence has no label), over the fused softmax-CE.
    Returns ``(loss, {"rows_routed_here", "expert_load",
    "expert_rows_run"})``, one row for each expert layer; the last is the
    rows the experts' grouped matmul visited in this step (visited tiles
    times a tile's rows): over ``rows_routed_here`` by what the tiles'
    edges round up, and by nothing else."""
    logits, load = model.apply(variables, tokens)
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full_like(tokens[:, :1], -1)], 1)
    with jax.named_scope("lm/head"):
        total = jnp.sum(ops.softmax_cross_entropy_loss(logits, labels))
    loss = total / max(labels.shape[0] * (labels.shape[1] - 1), 1)
    return loss, {"rows_routed_here": jnp.sum(load, -1), "expert_load": load,
                  "expert_rows_run": moe.expert_rows_run(
                      load, tokens.size * model.dims.top_k)}
