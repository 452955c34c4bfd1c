"""LFM2-MoE's next-token loss in plain ``jax.numpy``, float32, no kernels.

The decoder as the configuration runs it (``configs/lfm2_moe.json``),
written from the source's ``config.json`` and the public implementation
(``transformers``, ``modeling_lfm2_moe.py``), reading the model's own
parameter tree and sharing no code with ``apex_tpu``:

- norm: ``x / sqrt(mean(x^2) + eps) * w``, eps 1e-5, for the two norms of a
  block, the final one (the source's ``embedding_norm``) and the q/k norms;
  block: ``h = x + Mix(N(x))``, ``y = h + FFN(N(h))``; after the last block
  ``N``, then the head, which is the embedding: ``logits = h E^T``; the loss
  is the mean cross-entropy of token ``t+1`` at position ``t``, the last of
  each sequence unlabelled;
- gated short convolution: ``[B; C; z] = u W_in``; ``v = B * z``; ``c_t =
  sum_j w_j * v_(t-2+j)``, ``j = 0, 1, 2``, depthwise and causal with zeros
  before the sequence, written token by token as that sum; ``(C * c)
  W_out``; no bias, no activation;
- grouped-query attention: 32 q heads, 8 k/v heads of 64; q and k
  RMS-normalised a head with one learned scale of 64, *then* rotary on all
  64 channels in half-split pairs ``(m, m + 32)`` at ``theta = 1e6``; a
  dense causal softmax over ``q k^T / 8`` in blocks of ``ATTN_BLOCK``
  queries, q head ``h`` reading k/v head ``h // 4``;
- FFN: a SwiGLU of 11 776 in the leading ``num_dense_layers`` layers; after
  them ``s = sigmoid(u W_r)`` over all 64 experts, the 4 largest of ``s + b``
  chosen (``b`` is the selection bias, zero here), weights ``s / (sum of the
  4 chosen s + 1e-6)`` (the source's 1e-6; the system's ``ops.moe.route``
  adds 1e-20 instead: the chosen scores sum to ~2, so a weight departs by
  5e-7 of itself, under every limit below), times
  ``routed_scaling_factor`` = 1; a loop over the
  ``held`` ids with a 0/1 mask, every token; no shared expert.

Departures from the source, each the configuration's (its ``assumed``): no
auxiliary loss, a selection bias no update moves.

``compare`` decides ``correct``. On the timed batch (2 x 8192 tokens) it
holds the system's own loss function (``auto_cast`` on, kernels compiled)
against this file for

(a) the loss of the whole batch, the reference a sequence at a time: ``|sys
    - ref| / ref <= LOSS_TOL``;
(b) the logits at ``LOGIT_ROWS`` positions, spread evenly over each sequence
    with its last among them, twice: the whole, ``|sys - ref|_2 / |ref|_2 <=
    LOGIT_TOL``, and the median over the rows of each row's own ``|sys -
    ref|_2 / |ref|_2``, at most ``LOGIT_ROW_TOL``. Rounding moves every row
    alike; a row whose fourth choice of experts changed sides under it moves
    by a whole expert and sets the first number, not the second;
(c) the gradients of the leaves of ``GRAD_TOLS`` (each module of each kind
    of layer once, and the embedding, which is the head too) on the first
    ``GRAD_PREFIX`` = 2048 tokens of every sequence of the batch, the
    system in one program over them all, the reference a sequence at a time
    and averaged: ``|sys - ref|_2 / |ref|_2`` of each leaf at most its
    tolerance. 2048 tokens are eight of the convolution kernels' token
    blocks and two of the attention backward's 1024-token tiles, the plan
    the timed step's 8192 take (one tile takes another kernel), so what the
    backward kernels carry across a block's border (the halo of the gated
    input before it, the gated cotangent after it, ``d taps`` summed over
    blocks and sequences, ``dk``/``dv`` summed over query tiles) is inside
    ``correct``, on the chip, in every run.

``rel_diff`` is the largest of the fourteen ratios to their tolerances,
against ``rel_tol`` = 1.

Tolerances, each between two readings on the v5e at the published widths
and 2 x 8192 tokens (PR 34: loss and logits over 30 runs of the system on 28
seeds, the cell's own; the gradients over the 14 of them that took 2 x 2048
tokens, ``chiprun_out/D34`` and ``E34``; ``scripts/lfm2_moe_probes.py`` on
seeds 7, 11,
13 for the wrong programs, their gradients on 2048 tokens on seeds 7 and 11;
PERF.md has the table). The
system computes its matmuls in bfloat16 with float32 accumulation; the
residual stream, the convolution with its gates, the rotation, the router
and the norms are float32.

- logits, the rows' median: the system read 0.01386 to 0.01400 (27 runs,
  to the third digit the same: it is the matmuls' rounding and
  nothing else). This reference with *everything* in bfloat16 (weights,
  residual stream, convolution, scores), the precision below the stated one,
  read 0.0170, 0.0170, 0.0173 against itself: ``LOGIT_ROW_TOL`` = 0.0154,
  their geometric mean, is the limit that tells the two apart.
- logits, the whole: the system read 0.0211 to 0.0307 (30 runs), set by how
  many of the 256 rows changed an expert; everything in bfloat16 reads the
  same (0.0272, 0.0314, 0.0333) and cannot be told by it. ``LOGIT_TOL`` =
  0.038 is there for what moves rows by more than rounding and less than a
  wrong layer: rotary on half of each head reads 0.0475, 0.0493.
- loss: 3.6e-6 to 5.6e-5 (30 runs): a mean over 16 382 positions averages
  rounding out (everything in bfloat16: 5.6e-6 to 3.4e-5, no different).
  ``LOSS_TOL`` = 2e-4 is there for what shifts every position: four taps
  read 7.5e-4 and 1.9e-3, a shared expert left in 9.1e-4 and 1.2e-3, ``B``
  and ``C`` swapped 1.5e-3, an untied head 1.7e-3 and 2.7e-3.
- gradients, on 2 x 2048 tokens (14 runs on 14 seeds), of the embedding
  0.0328 to 0.0356, of ``in_proj`` 0.0335 to 0.0364, the taps 0.0337 to
  0.0367, the dense ``up_proj`` 0.0335 to 0.0363, ``out_proj`` 0.0363 to
  0.0389 and the final norm 0.0143 to 0.0158 (everything in bfloat16: 0.0415 to 0.0467,
  0.0211 and 0.0220 on the last: above the system, not by enough to stand a
  limit between). Their limits (0.07; 0.03 for the final norm) sit between
  the system and rotary on half of each head, the mildest of the wrong
  programs, which reads 0.115 to 0.133 on them (0.0525, 0.0554 on the final
  norm). On 256 tokens of one sequence, as the first limits were read (0.09
  and 0.06), the system read 0.028 to 0.044 (0.019 to 0.028) and that probe
  0.20 to 0.24 (0.12): more tokens do not average the matmuls' rounding out,
  they do bring a wrong rotation's share down.
- gradient of ``k_norm``'s scale 0.0297 to 0.0488: limit 0.13, under the q/k
  norm applied *after* the rotation, which reads 0.58, 0.60 on it and
  **nothing elsewhere** (0 on the loss, 2e-7 on the logits, 4e-7 to 6e-7 on
  the other gradients: float32's last bit): at the published initialisation
  the scales are all one, a rotation keeps a head's mean square, and the two
  orders give the same q and k; only the scale's own gradient tells them
  apart (channel ``m``'s cotangent before or after it was turned).
- gradients of ``q_proj`` 0.0376 to 0.0437 and ``v_proj`` 0.0328 to 0.0404:
  limits 0.2 and 0.17, under what moves the scores: rotary on half the head
  1.05 and 0.70, the k/v head by ``h % 8`` 1.32, 1.33 and 1.32.
- gradients of the router 0.18 to 0.26 and of the held experts 0.127 to
  0.169: set by routing, not by rounding: where bfloat16 moves a row's fourth
  choice across a held expert's boundary a whole row changes sides. The
  share of such rows is the same at any length, so 4096 tokens read no lower
  than 256 did (0.08 to 0.36, 0.03 to 0.23), only closer together
  (everything in bfloat16 reads the same: 0.17, 0.31 and 0.17, 0.23). Limits
  0.7 and 0.6: weights normalised over the held chosen experts only read
  1.08, 1.09 and 2.64, 2.67.

The probes (``scripts/lfm2_moe_probes.py``), this reference against itself
with one thing wrong, seeds 7 and 11 on the v5e with the gradients on 2048
tokens, as (loss, logits whole, rows' median, the gradient that shows it
most ÷ its limit); **bold** fails its limit, and each fails one at least:
everything in bfloat16 (3.4e-5, 0.0314, **0.0171**, 0.73 on the final norm;
seed 11: 0.0272, **0.0170**, 0.70; it fails that one limit and no other);
four taps (**1.9e-3**, **0.86**, **0.86**, **21** on the final norm); the
taps reversed (**5.1e-4**, **1.34**, **1.34**, **32**); ``B`` and ``C``
swapped (**1.5e-3**, **1.34**, **1.34**, **33**); the q/k norm after the
rotary (0, 2e-7, 2e-7, **4.5** on ``k_norm``, seed 11 **4.6**: that one
limit); rotary on half of each head (8.9e-5, **0.0475**, **0.0314**, **7.3**
on ``k_norm``, **5.2** on ``q_proj``); k/v head ``h % 8`` (1.2e-4, **0.078**,
**0.057**, **10.5** on ``k_norm``, **7.8** on ``v_proj``); weights normalised
over the held chosen experts only (**2.4e-4**, **0.33**, **0.33**, **7.7** on
the final norm, **4.4** on the experts; seed 11's loss 1.5e-4 passes, its
other thirteen fail); a shared expert left in (**1.2e-3**, **0.87**,
**0.87**, **21**); an untied head (**1.7e-3**, **1.41**, **1.41**, **34**).

At any other width than the published one (the rehearsal's toy size) every
tolerance is ``OTHER_WIDTH_FACTOR`` times wider: sums are 32 times shorter
there and a row is a larger share of an expert's.
"""

import functools
import math

import jax
import jax.numpy as jnp

LOSS_TOL = 2e-4
LOGIT_TOL = 3.8e-2
LOGIT_ROW_TOL = 1.54e-2
LOGIT_ROWS = 256
GRAD_PREFIX = 2048
ATTN_BLOCK = 512
#: the leaves whose gradients are compared, each with its tolerance
GRAD_TOLS = {
    ("embed", "embedding"): 7e-2,
    ("layers_0", "lconv", "in_proj", "kernel"): 7e-2,
    ("layers_0", "lconv", "conv"): 7e-2,
    ("layers_0", "mlp", "up_proj", "kernel"): 7e-2,
    ("layers_1", "gqa", "q_proj", "kernel"): 2e-1,
    ("layers_1", "gqa", "k_norm", "scale"): 1.3e-1,
    ("layers_1", "gqa", "v_proj", "kernel"): 1.7e-1,
    ("layers_2", "lconv", "out_proj", "kernel"): 7e-2,
    ("layers_2", "moe", "router"): 7e-1,
    ("layers_2", "moe", "experts_up"): 6e-1,
    ("final_norm", "scale"): 3e-2,
}
GRAD_LEAVES = tuple(GRAD_TOLS)
#: the tolerances were read at the published widths. Anywhere else (the
#: rehearsal's toy size, where a sum is 32 times shorter and one row a larger
#: share of an expert's) they are this much wider; a rehearsal is never correct
OTHER_WIDTH_FACTOR = 2.0
PUBLISHED_HIDDEN = 2048


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, p):
    return (silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
            ) @ p["down_proj"]["kernel"]


def _drawn(like, seed, bound=None):
    """What a probe adds that the model has no parameter for: normal(0.02),
    or uniform in ``(-bound, bound)``, from a fixed key."""
    key = jax.random.PRNGKey(seed)
    if bound is None:
        return 0.02 * jax.random.normal(key, like.shape, like.dtype)
    return jax.random.uniform(key, like.shape, like.dtype, -bound, bound)


def gated_short_conv(x, p, four_taps=False, taps_reversed=False,
                     swap_b_c=False):
    """``x`` ``(T, D)``. ``c_t = sum_j w_j v_(t - K + 1 + j)``, zeros before
    the sequence."""
    d = x.shape[1]
    bcz = x @ p["in_proj"]["kernel"]
    b, c, z = bcz[:, :d], bcz[:, d:2 * d], bcz[:, 2 * d:]
    if swap_b_c:            # probe only
        b, c = c, b
    taps = p["conv"]
    if four_taps:           # probe only: one more tap, on the token before
        taps = jnp.concatenate(
            [_drawn(taps[:1], 4, bound=taps.shape[0] ** -0.5), taps])
    if taps_reversed:       # probe only: the newest token's tap first
        taps = taps[::-1]
    k, t = taps.shape[0], x.shape[0]
    v = jnp.concatenate([jnp.zeros((k - 1, d), x.dtype), b * z])
    conv = sum(taps[j] * v[j:j + t] for j in range(k))
    return (c * conv) @ p["out_proj"]["kernel"]


def rotary(x, theta, channels):
    """``x`` ``(T, H, D)``: channels ``[0, channels)`` turned by position,
    pairs ``(m, m + channels / 2)``; the rest unrotated."""
    r = channels
    freq = float(theta) ** (-jnp.arange(0, r, 2) / r)
    angle = jnp.arange(x.shape[0])[:, None, None] * freq         # (T, 1, R/2)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           -1)


def attention(x, p, sizes, norm_after_rotary=False, half_rotary=False,
              kv_head_mod=False):
    h, hkv = sizes["num_attention_heads"], sizes["num_key_value_heads"]
    d = sizes.get("head_dim") or sizes["hidden_size"] // h
    eps, theta = sizes["norm_eps"], sizes["rope_parameters"]["rope_theta"]
    t = x.shape[0]
    q = (x @ p["q_proj"]["kernel"]).reshape(t, h, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(t, hkv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(t, hkv, d)
    turn = lambda y: rotary(y, theta, d // 2 if half_rotary else d)
    if norm_after_rotary:   # probe only
        q = rms(turn(q), p["q_norm"]["scale"], eps)
        k = rms(turn(k), p["k_norm"]["scale"], eps)
    else:
        q = turn(rms(q, p["q_norm"]["scale"], eps))
        k = turn(rms(k, p["k_norm"]["scale"], eps))
    # q head i reads k/v head i // (h / hkv)
    kv_of = jnp.arange(h) // (h // hkv)
    if kv_head_mod:         # probe only
        kv_of = jnp.arange(h) % hkv
    k, v = k[:, kv_of], v[:, kv_of]
    out = []
    for lo in range(0, t, ATTN_BLOCK):      # a block of queries at a time
        hi = min(lo + ATTN_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(d)
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", w, v[:hi]))
    return jnp.concatenate(out).reshape(t, h * d) @ p["o_proj"]["kernel"]


def experts(x, p, sizes, held, over_held_only=False, shared_left_in=False):
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["e_bias"],
                              sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    norm = jnp.sum(picked, -1, keepdims=True) + 1e-6
    if over_held_only:      # probe only; a row with none of its choices here
        here = jnp.isin(chosen, jnp.asarray(held))
        norm = jnp.sum(jnp.where(here, picked, 0.0), -1, keepdims=True) + 1e-6
    weights = picked / norm * sizes.get("routed_scaling_factor", 1)
    y = jnp.zeros_like(x)
    if shared_left_in:      # probe only: one more expert, every token
        gate, up, down = (_drawn(p[name][0], i) for i, name in enumerate(
            ("experts_gate", "experts_up", "experts_down")))
        y = (silu(x @ gate) * (x @ up)) @ down

    def one(y, e):          # a held expert over every row, 0 where not chosen
        i, gate, up, down = e
        w = jnp.sum(jnp.where(chosen == i, weights, 0.0), -1)
        return y + w[:, None] * ((silu(x @ gate) * (x @ up)) @ down), None

    return jax.lax.scan(one, y, (jnp.asarray(held), p["experts_gate"],
                                 p["experts_up"], p["experts_down"]))[0]


def held_ids(sizes):
    return tuple(sizes.get("held_experts", range(sizes["num_experts"])))


def hidden_states(params, tokens, sizes, dtype=jnp.float32, **probe):
    """One sequence ``(T,)`` to the final normed hidden states ``(T, D)``.
    ``dtype`` and ``probe`` are for the probes of the docstring: another
    precision for everything, or one departure from the equations. A
    gradient runs each block again (``jax.checkpoint``: the same values) so
    that 2048 tokens' worth fits beside the training state, under the timed
    step's own peak."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = sizes["norm_eps"]
    pick = lambda *keys: {k: probe[k] for k in keys if k in probe}

    def block(x, p, i, kind):
        normed = rms(x, p["attn_norm"]["scale"], eps)
        if kind == "conv":
            y = gated_short_conv(normed, p["lconv"], **pick(
                "four_taps", "taps_reversed", "swap_b_c"))
        else:
            y = attention(normed, p["gqa"], sizes, **pick(
                "norm_after_rotary", "half_rotary", "kv_head_mod"))
        x = x + y.astype(dtype)
        normed = rms(x, p["ffn_norm"]["scale"], eps)
        if i < sizes["num_dense_layers"]:
            y = swiglu(normed, p["mlp"])
        else:
            y = experts(normed, p["moe"], sizes, held_ids(sizes), **pick(
                "over_held_only", "shared_left_in"))
        return x + y.astype(dtype)

    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(sizes["layer_types"]):
        x = jax.checkpoint(block, static_argnums=(2, 3))(
            x, params[f"layers_{i}"], i, kind)
    return rms(x, params["final_norm"]["scale"], eps)


def loss_and_logits(params, tokens, sizes, rows=None, untied_head=False,
                    **probe):
    """Mean next-token loss of one sequence, and the logits at ``rows`` (all
    positions when None)."""
    hidden = hidden_states(params, tokens, sizes, **probe)
    head = params["embed"]["embedding"].astype(hidden.dtype).T
    if untied_head:         # probe only: a matrix of its own
        head = _drawn(head, 7)
    logp = jax.nn.log_softmax((hidden[:-1] @ head).astype(jnp.float32), -1)
    loss = -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))
    return loss, (hidden if rows is None else hidden[rows]) @ head


def lm_loss(params, tokens, sizes, **probe):
    return loss_and_logits(params, tokens, sizes, **probe)[0]


def logit_rows(length, n=LOGIT_ROWS):
    """``n`` positions spread evenly, the last among them."""
    n = min(n, length)
    return jnp.asarray([(i + 1) * length // n - 1 for i in range(n)])


def _leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with_leaves(params, paths, leaves):
    """``params`` with the leaves at ``paths`` replaced (a copy of the dicts
    on the way, not of the arrays)."""
    for path, leaf in zip(paths, leaves):
        node = params = dict(params)
        for key in path[:-1]:
            node[key] = dict(node[key])
            node = node[key]
        node[path[-1]] = leaf
    return params


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _rows_rel(a, b):
    """The median over rows of each row's own ``|a - b|_2 / |b|_2``: what
    rounding does to every row, whatever a changed choice of experts does to
    a few."""
    a, b = (x.astype(jnp.float32).reshape(-1, x.shape[-1]) for x in (a, b))
    return float(jnp.median(jnp.linalg.norm(a - b, axis=-1)
                            / jnp.linalg.norm(b, axis=-1)))


def compare(sizes, built, carry, batch):
    params = built["params"](carry)
    everywhere = jax.tree_util.tree_leaves(params)[0].sharding
    tokens = jax.device_put(batch[0], everywhere)       # the timed batch
    n, length = tokens.shape
    rows = logit_rows(length, LOGIT_ROWS // n)
    paths = GRAD_LEAVES
    prefix = tokens[:, :min(GRAD_PREFIX, length)]
    leaves = [_leaf(params, p) for p in paths]

    sys_loss, sys_logits = jax.jit(lambda p, t: (
        built["loss_fn"](p, t)[0], built["logits_fn"](p, t)[:, rows]))(
            params, tokens)
    sys_grads = jax.jit(jax.grad(lambda leaves, p, t: built["loss_fn"](
        _with_leaves(p, paths, leaves), t)[0]))(leaves, params, prefix)
    with jax.default_matmul_precision("highest"):
        # a sequence at a time; equal lengths: the mean of the means
        ref_loss, ref_logits = jax.jit(lambda p, t: jax.lax.map(
            functools.partial(loss_and_logits, p, sizes=sizes, rows=rows),
            t))(params, tokens)
        ref_loss = jnp.mean(ref_loss)
        ref_grad = jax.jit(jax.grad(lambda leaves, p, t: lm_loss(
            _with_leaves(p, paths, leaves), t, sizes)))
        ref_grads = [sum(of_leaf) / n for of_leaf in zip(
            *(ref_grad(leaves, params, sequence) for sequence in prefix))]

    loss_rel = abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss))
    logit_rel = _rel(sys_logits, ref_logits)
    row_rel = _rows_rel(sys_logits, ref_logits)
    grad_rel = {"/".join(p): _rel(s, r)
                for p, s, r in zip(paths, sys_grads, ref_grads)}
    wider = (1.0 if sizes["hidden_size"] == PUBLISHED_HIDDEN
             else OTHER_WIDTH_FACTOR)
    worst = max(loss_rel / LOSS_TOL, logit_rel / LOGIT_TOL,
                row_rel / LOGIT_ROW_TOL,
                *(grad_rel["/".join(p)] / tol
                  for p, tol in GRAD_TOLS.items())) / wider
    return {"ok": worst <= 1.0, "rel_diff": worst, "rel_tol": 1.0,
            "system_loss": float(sys_loss), "reference_loss": float(ref_loss),
            "loss_rel_diff": loss_rel, "loss_rel_tol": LOSS_TOL,
            "logit_rel_diff": logit_rel, "logit_rel_tol": LOGIT_TOL,
            "logit_row_rel_diff": row_rel, "logit_row_rel_tol": LOGIT_ROW_TOL,
            "logit_rows": int(n * rows.shape[0]),
            "grad_rel_diff": grad_rel,
            "grad_rel_tol": {"/".join(p): t for p, t in GRAD_TOLS.items()},
            "grad_prefix": int(prefix.shape[1]), "length": int(length),
            "sequences": int(n), "tolerances_times": wider}
