"""BERT-style transformer encoder built on the fused ops.

The reference's transformer story is its kernel set — fused MHA
(`apex/contrib/multihead_attn`), FusedLayerNorm, fused softmax-CE, and the
"BERT-Large pretraining with FusedLAMB" config in BASELINE.json. This
module assembles those pieces into the encoder those configs describe:
pre/post-LN blocks over :func:`apex_tpu.ops.fused_layer_norm_affine`,
attention through :mod:`apex_tpu.ops.attention` (fused blockwise softmax
when available), and an MLM head matching
:func:`apex_tpu.ops.softmax_cross_entropy_loss`.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

from apex_tpu import ops


class FusedLayerNormModule(nn.Module):
    features: int
    epsilon: float = 1e-5

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (self.features,),
                       jnp.float32)
        b = self.param("bias", nn.initializers.zeros, (self.features,),
                       jnp.float32)
        return ops.fused_layer_norm_affine(x, w, b, self.epsilon)


class MultiheadAttention(nn.Module):
    """Thin wrapper over :class:`apex_tpu.ops.SelfMultiheadAttn` taking a
    boolean mask (True = attend) instead of an additive bias — one
    attention implementation for the whole framework."""
    hidden: int
    heads: int
    dropout: float = 0.0

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        from apex_tpu.ops.multihead_attn import SelfMultiheadAttn

        bias = None
        if mask is not None:
            bias = jnp.where(mask, 0.0, -1e9).astype(jnp.float32)
        attn = SelfMultiheadAttn(self.hidden, self.heads,
                                 dropout=self.dropout)
        return attn(x, attn_bias=bias, deterministic=deterministic)


class TransformerLayer(nn.Module):
    hidden: int
    heads: int
    ffn_hidden: int
    dropout: float = 0.0
    pre_ln: bool = False

    @nn.compact
    def __call__(self, x, mask=None, deterministic: bool = True):
        attn = MultiheadAttention(self.hidden, self.heads, self.dropout)
        ln1 = FusedLayerNormModule(self.hidden)
        ln2 = FusedLayerNormModule(self.hidden)
        if self.pre_ln:
            x = x + attn(ln1(x), mask, deterministic)
            y = ln2(x)
            y = nn.Dense(self.ffn_hidden)(y)
            y = jax.nn.gelu(y)
            y = nn.Dense(self.hidden)(y)
            return x + y
        x = ln1(x + attn(x, mask, deterministic))
        y = nn.Dense(self.ffn_hidden)(x)
        y = jax.nn.gelu(y)
        y = nn.Dense(self.hidden)(y)
        return ln2(x + y)


class BertEncoder(nn.Module):
    """BERT-style encoder: embeddings + N layers + optional MLM head."""
    vocab_size: int
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    ffn_hidden: Optional[int] = None
    max_len: int = 512
    dropout: float = 0.0

    @nn.compact
    def __call__(self, tokens, attn_mask=None, deterministic: bool = True):
        ffn = self.ffn_hidden or 4 * self.hidden
        emb = nn.Embed(self.vocab_size, self.hidden, name="tok_emb")(tokens)
        pos = self.param("pos_emb", nn.initializers.normal(0.02),
                         (self.max_len, self.hidden), jnp.float32)
        x = emb + pos[None, :tokens.shape[1]].astype(emb.dtype)
        x = FusedLayerNormModule(self.hidden, epsilon=1e-12)(x)
        mask = None
        if attn_mask is not None:
            mask = attn_mask[:, None, None, :].astype(bool)
        for _ in range(self.layers):
            x = TransformerLayer(self.hidden, self.heads, ffn,
                                 self.dropout)(x, mask, deterministic)
        return x


def BertLarge(vocab_size: int = 30522, **kw):
    return BertEncoder(vocab_size, hidden=1024, layers=24, heads=16, **kw)


#: rows of the local batch the gathered MLM head holds: one in this many,
#: rounded down to whole row blocks (and never under one)
_HEAD_ROWS_SHARE = 4
_HEAD_ROW_BLOCK = 128


def _head_capacity(rows: int) -> int:
    block = _HEAD_ROW_BLOCK
    return max(rows // _HEAD_ROWS_SHARE // block * block, block)


def _compact(hidden, labels, cap):
    """The labelled rows of ``hidden`` (rows, H) in order, in ``cap`` rows,
    and their labels; the rows of padding take label -1. Needs
    ``sum(labels >= 0) <= cap``."""
    labelled = labels >= 0
    idx, = jnp.nonzero(labelled, size=cap, fill_value=0)
    kept = jnp.where(jnp.arange(cap) < jnp.sum(labelled), labels[idx], -1)
    return hidden[idx], kept


def _head_sum(hidden, emb, labels, smoothing, cap):
    """Summed loss of the rows of ``hidden``: the vocabulary GEMM, then the
    fused softmax-CE, which zeroes a row labelled < 0. With a ``cap`` the
    GEMM runs on the compacted rows."""
    with jax.named_scope("mlm/head_full" if cap is None
                         else "mlm/head_gathered"):
        if cap is not None:
            hidden, labels = _compact(hidden, labels, cap)
        logits = hidden @ emb.T.astype(hidden.dtype)
        return jnp.sum(
            ops.softmax_cross_entropy_loss(logits, labels, smoothing))


def _head_grads(hidden, emb, labels, g, smoothing, cap):
    """The cotangents of ``hidden`` and ``emb`` for ``g`` on the sum: the
    head's forward once more, then its backward."""
    _, vjp = jax.vjp(lambda h, e: _head_sum(h, e, labels, smoothing, cap),
                     hidden, emb)
    return vjp(g)


def _either_head(fn, labels, *operands):
    """``fn(*operands, cap=...)`` over the gathered head where the labelled
    rows fit its capacity, over the full head where they do not: decided
    on the device, each step."""
    rows = labels.shape[0]
    cap = _head_capacity(rows)
    if cap >= rows:
        return fn(*operands, cap=None)
    return jax.lax.cond(jnp.sum(labels >= 0) <= cap,
                        functools.partial(fn, cap=cap),
                        functools.partial(fn, cap=None), *operands)


def _head_primal(hidden, emb, labels, smoothing):
    return _either_head(functools.partial(_head_sum, smoothing=smoothing),
                        labels, hidden, emb, labels)


def _head_fwd(hidden, emb, labels, smoothing):
    return _head_primal(hidden, emb, labels, smoothing), (hidden, emb, labels)


def _head_bwd(smoothing, res, g):
    hidden, emb, labels = res
    d_hidden, d_emb = _either_head(
        functools.partial(_head_grads, smoothing=smoothing),
        labels, hidden, emb, labels, g)
    return d_hidden, d_emb, None


# One vjp over both heads, so that what the backward keeps is the head's
# inputs. Differentiated through, the conditional would keep each branch's
# logits and have the branch not taken fill the other's with zeros: the
# full head's are 0.5 GB at BERT-Large's b16 x s512. The backward runs
# the chosen head's forward again instead.
_mlm_head = jax.custom_vjp(_head_primal, nondiff_argnums=(3,))
_mlm_head.defvjp(_head_fwd, _head_bwd)


def mlm_loss(encoder, variables, tokens, labels, smoothing=0.0):
    """Masked-LM loss over the fused softmax-CE (labels < 0 = unmasked).

    The head runs on the labelled rows only, as google-research/bert's
    ``gather_indexes`` has it: where the local batch's labelled rows fit a
    capacity of a quarter of its rows (whole 128-row blocks), they are
    compacted into that many rows ahead of the vocabulary GEMM; a batch
    with more takes the head over every row. Both give the same loss and
    gradients (up to the order of a sum), so the choice is the program's,
    made each step from the labels it sees, and not an argument: no batch
    is truncated, and BERT's and RoBERTa's 15% and ELECTRA's 25% fit. The
    two run under the scopes ``mlm/head_gathered`` and ``mlm/head_full``;
    the device time under each (``python -m apex_tpu.prof <trace>``) says
    which the steps took.
    """
    hidden = encoder.apply(variables, tokens)
    emb = variables["params"]["tok_emb"]["embedding"]
    total = _mlm_head(hidden.reshape(-1, hidden.shape[-1]), emb,
                      labels.reshape(-1), smoothing)
    return total / jnp.maximum(jnp.sum(labels >= 0), 1)
