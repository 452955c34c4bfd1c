"""Qwen3-Next's next-token loss in plain ``jax.numpy``, float32, no kernels.

The decoder as the configuration runs it (``configs/qwen3_next.json``),
written from the source's ``config.json`` and the public implementation
(``transformers``, ``modeling_qwen3_next.py``), reading the model's own
parameter tree and sharing no code with ``apex_tpu``:

- norm: ``x / sqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred), for the two
  norms of a block, the final one and the q/k norms; block: ``h = x +
  Mix(N(x))``, ``y = h + MoE(N(h))``; after the last block ``N``, then the
  untied head; the loss is the mean cross-entropy of token ``t+1`` at
  position ``t``, the last unlabelled;
- gated DeltaNet in its **recurrent** form, one ``lax.scan`` step a token:
  ``S_t = (I - b_t k_t k_t^T) a_t S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T
  q_t`` a value head, ``a_t = exp(g_t)`` a scalar, ``g = -exp(A) *
  softplus(a + dt)``, ``b = sigmoid(.)``; ``[q; k; v; z] = W x``, ``[q; k;
  v] <- silu(conv4(.))`` as one depthwise convolution, ``q`` and ``k``
  L2-normalised a head (``q`` times ``d_k^-1/2``), key head ``j`` serving
  value heads ``2j, 2j + 1``; the output RMS-normalised a head with a plain
  scale, times ``silu(z)``;
- gated attention: a head's query and gate from one projection, q and k
  normed a head, rotary on channels ``[0, 64)`` in half-split pairs ``(m, m
  + 32)`` at ``theta = 1e7``, a dense causal softmax over ``q k^T / 16`` with
  q head ``h`` reading k/v head ``h // 8``, the output times
  ``sigmoid(gate)``;
- experts: softmax over all 512 router outputs, the 10 largest, weights
  normalised over the 10 chosen; a loop over the ``held`` ids with a 0/1
  mask, every token, plus ``sigmoid(w_s^T x)`` times the shared expert.

Departures from the source, each the configuration's (its ``assumed``): the
fused projections are plain concatenations (a permutation of the public
layout), no multi-token-prediction module, no auxiliary loss.

``compare`` decides ``correct``. At the timed length, on one sequence of the
batch, it holds the system's own loss function (``auto_cast`` on, kernels
compiled) against this file for

(a) the loss: ``|sys - ref| / ref <= LOSS_TOL``;
(b) the logits at ``LOGIT_ROWS`` positions spread evenly over the sequence,
    the last among them: ``|sys - ref|_2 / |ref|_2 <= LOGIT_TOL``. The
    reference's logits are computed for those rows only; the layers run
    over the whole sequence, attention in blocks of ``ATTN_BLOCK`` queries,
    so that float32 fits beside the state;
(c) the logits at the same rows again with every DeltaNet head's decay
    ``exp(SLOW_DECAY)`` times slower (``A_log - 12``: nothing is forgotten
    inside 8192 tokens), at most ``SLOW_LOGIT_TOL``. At the published
    initialisation (``A = log U(0, 16)``, ``dt = 1``: ``g`` near -10 a
    token) a state forgets within a token or two, and neither what carries
    it from chunk to chunk nor its precision shows in (b);
(d) the gradients of the nine leaves of ``GRAD_TOLS`` (the fused DeltaNet
    projection, ``A_log``, ``dt_bias``, the q projection with its gate,
    ``k_norm``'s scale, the router, the held experts, the shared expert's
    gate, the head) on the first ``GRAD_PREFIX`` = 256 tokens, as long a
    prefix as the recurrent form's backward fits beside 7.5 GB of training
    state (it keeps a state a token, 2 MB a layer-token): ``|sys - ref|_2 /
    |ref|_2`` of each leaf at most its tolerance.

``rel_diff`` is the largest of the twelve ratios to their tolerances,
against ``rel_tol`` = 1.

Tolerances, each between two readings on the v5e at the published widths
and 8192 tokens (PR 32: five seeds of the system from the cell's own runs,
``scripts/qwen3_next_probes.py`` on seeds 7, 11, 13 for the wrong programs;
PERF.md has the table). The system computes its matmuls in bfloat16 with
float32 accumulation; the residual stream, the delta rule's state, decay and
solve, the rotation, the router and the norms are float32.

- logits: the system read 0.0258 to 0.0272. This reference with
  *everything* in bfloat16 (weights, residual stream, the delta rule's
  state rounded a token), the precision below the stated one, read 0.0325,
  0.0328, 0.0339 against itself: ``LOGIT_TOL`` = 0.030 is the limit that
  tells the two apart (the slowed logits do too: 0.093, 0.098).
- slowed logits: the system read 0.0264, 0.0267; a bfloat16 delta-rule state
  alone read 0.0667, 0.0668 (0.0076 to 0.0091 on the logits of (b), where it
  passes unseen): ``SLOW_LOGIT_TOL`` = 0.042, their geometric mean.
- loss: 2.8e-6 to 9.7e-6; a mean over 8191 positions averages rounding out
  (all-bfloat16: 2.4e-6 to 3.2e-5, no different). ``LOSS_TOL`` = 1e-4 is
  there for what shifts every position: weights normalised over the held
  experts only read 2.3e-4 and 6.4e-4, an ungated shared expert 1.2e-4 and
  3.8e-4.
- gradients of ``qkvz_proj`` 0.042 to 0.052, ``A_log`` 0.043 to 0.065,
  ``dt_bias`` 0.043 to 0.061, the shared expert's gate 0.046 to 0.050, the
  head 0.025 to 0.030 (all-bfloat16: 0.060, 0.040 to 0.086, 0.043 to 0.091,
  0.054 to 0.081, 0.034 to 0.035: above the system, not by enough to stand
  a limit between). Their limits (0.08, 0.085, 0.085, 0.09, 0.04) sit between
  the system and rotary in interleaved pairs, which reads 0.110 to 0.114,
  0.096 to 0.129, 0.100 to 0.126, 0.106 to 0.123 and 0.053 to 0.056 on them
  while its logits (0.019) pass unseen.
- gradients of ``q_proj`` 0.050 to 0.062 and of ``k_norm``'s scale 0.052 to
  0.061: limits 0.2, under what moves the scores: interleaved pairs 0.48 and
  0.53, rotary over all 256 channels 0.78 and 0.82 (its logits read 0.0295
  to 0.0300, on the limit), the k/v head by ``h % 2`` 1.0 and 1.0.
- gradients of the router 0.099 to 0.189 and of the held experts 0.088 to
  0.155: set by routing, not by rounding: where bfloat16 moves a row's
  tenth choice across a held expert's boundary a whole row changes sides,
  and on 256 tokens a held expert has about 5 rows (all-bfloat16 reads the
  same: 0.096 to 0.168, 0.132 to 0.173). Limits 0.5 and 0.45: sigmoid scores
  in place of the softmax read 0.906 and 0.917 on the router (0.322, 0.343
  on the experts, 0.036 to 0.038 on the logits), weights normalised over the
  held experts only 1.9, 2.2 and 7.0.

The probes, this reference against itself with one thing wrong, seeds 7 and
11, as (loss, logits, slowed logits of seed 11, the gradient that shows it
most ÷ its limit); **bold** fails its limit, and each fails one at least:
a bfloat16 delta-rule state (1.2e-6, 0.0091, **0.0667**, 0.35); everything
in bfloat16 (1.8e-5, **0.0328**, **0.0976**, 1.07 on ``dt_bias`` in one seed
of three); a dropped 1/16 in attention (1.4e-5, **0.606**, **0.419**,
**75** on ``k_norm``); rotary over all 256 channels (6.9e-5, 0.0295, 0.0188, **3.9** on
``q_proj``); rotary in interleaved pairs (2.9e-5, 0.0192, 0.0121, **2.4** on
``q_proj``, **1.4** on ``qkvz_proj``); key heads tiled ``j -> j, j + 16``
(**2.1e-3**, **1.34**, **1.36**, **34** on the head); q head ``h`` reading k/v head ``h
% 2`` (3.2e-5, **0.052**, **0.069**, **5.0**); sigmoid scores (2.1e-6,
**0.036**, 0.025, **1.8** on the router); weights normalised over the held
experts only (**2.3e-4**, **0.649**, **0.481**, **16**); the shared expert
ungated (**3.8e-4**, **0.599**, **0.448**, **16** on the head).

At any other width than the published one (the rehearsal's toy size) every
tolerance is ``OTHER_WIDTH_FACTOR`` times wider: sums are 32 times shorter
there and a row is a larger share of an expert's.
"""

import functools
import math

import jax
import jax.numpy as jnp

LOSS_TOL = 1e-4
LOGIT_TOL = 3.0e-2
LOGIT_ROWS = 256
SLOW_DECAY = 12.0
SLOW_LOGIT_TOL = 4.2e-2
GRAD_PREFIX = 256
ATTN_BLOCK = 1024
#: the leaves whose gradients are compared, each with its tolerance
GRAD_TOLS = {
    ("layers_0", "gdn", "qkvz_proj", "kernel"): 8e-2,
    ("layers_0", "gdn", "A_log"): 8.5e-2,
    ("layers_0", "gdn", "dt_bias"): 8.5e-2,
    ("layers_3", "gattn", "q_proj", "kernel"): 2e-1,
    ("layers_3", "gattn", "k_norm", "scale"): 2e-1,
    ("layers_1", "moe", "router"): 5e-1,
    ("layers_1", "moe", "experts_up"): 4.5e-1,
    ("layers_1", "moe", "shared_gate", "kernel"): 9e-2,
    ("lm_head",): 4e-2,
}
GRAD_LEAVES = tuple(GRAD_TOLS)
#: the tolerances were read at the published widths. Anywhere else (the
#: rehearsal's toy size, where a sum is 32 times shorter and one row a larger
#: share of an expert's) they are this much wider; a rehearsal is never correct
OTHER_WIDTH_FACTOR = 2.0
PUBLISHED_HIDDEN = 2048


def rms(x, w, eps):
    """Zero-centred: the learned ``w`` starts at 0."""
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, p):
    return (silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
            ) @ p["down_proj"]["kernel"]


def conv_silu(x, taps):
    """``y_t = sum_j taps[j] x_{t-K+1+j}`` a channel, then SiLU. ``(T, C)``."""
    k, t = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return silu(sum(padded[j:j + t] * taps[j] for j in range(k)))


def gated_deltanet(x, p, sizes, state_dtype=jnp.float32, tiled_keys=False):
    hk, hv = sizes["linear_num_key_heads"], sizes["linear_num_value_heads"]
    dk, dv = sizes["linear_key_head_dim"], sizes["linear_value_head_dim"]
    t = x.shape[0]
    n_qk, n_v = hk * dk, hv * dv
    qkvz = x @ p["qkvz_proj"]["kernel"]
    qkv = conv_silu(qkvz[:, :2 * n_qk + n_v], p["conv"])
    q = qkv[:, :n_qk].reshape(t, hk, dk)
    k = qkv[:, n_qk:2 * n_qk].reshape(t, hk, dk)
    v = qkv[:, 2 * n_qk:].reshape(t, hv, dv)
    z = qkvz[:, 2 * n_qk + n_v:].reshape(t, hv, dv)
    unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(dk), unit(k)
    # value head i reads key head i // (hv / hk)
    key_of = jnp.arange(hv) // (hv // hk)
    if tiled_keys:      # probe only: j -> j, j + hk
        key_of = jnp.arange(hv) % hk
    q, k = q[:, key_of], k[:, key_of]
    ba = x @ p["ba_proj"]["kernel"]
    beta = jax.nn.sigmoid(ba[:, :hv])                           # (T, Hv)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, None, None] * state
        state = state - beta[:, None, None] * k[:, :, None] * jnp.einsum(
            "hk,hkv->hv", k, state)[:, None, :]
        state = state + beta[:, None, None] * k[:, :, None] * v[:, None, :]
        if state_dtype != jnp.float32:  # probe only; a convert pair is elided
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        return state, jnp.einsum("hkv,hk->hv", state, q)

    # the backward keeps the state a token and computes the rest again
    o = jax.lax.scan(jax.checkpoint(token), jnp.zeros((hv, dk, dv), x.dtype),
                     (q, k, v, g, beta))[1]
    o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                     + sizes["rms_norm_eps"]) * p["o_norm"]["scale"]
    return (o * silu(z)).reshape(t, n_v) @ p["o_proj"]["kernel"]


def rotary(x, sizes, over_all=False, interleaved=False):
    """``x`` ``(T, H, D)``: channels ``[0, R)`` turned by position, pairs
    ``(m, m + R / 2)``; the rest unrotated."""
    d = x.shape[-1]
    r = d if over_all else int(d * sizes["partial_rotary_factor"])
    freq = float(sizes["rope_theta"]) ** (-jnp.arange(0, r, 2) / r)
    angle = jnp.arange(x.shape[0])[:, None, None] * freq         # (T, 1, R/2)
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)
    if interleaved:     # probe only: pairs (2m, 2m + 1)
        a, b = x[..., 0:r:2], x[..., 1:r:2]
        turned = jnp.stack([a * cos - b * sin, b * cos + a * sin], -1)
        return jnp.concatenate([turned.reshape(*x.shape[:-1], r), x[..., r:]],
                               -1)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           -1)


def gated_attention(x, p, sizes, scaled=True, kv_head_mod=False, **rope):
    h, hkv, d = (sizes["num_attention_heads"], sizes["num_key_value_heads"],
                 sizes["head_dim"])
    eps = sizes["rms_norm_eps"]
    t = x.shape[0]
    qg = (x @ p["q_proj"]["kernel"]).reshape(t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["k_proj"]["kernel"]).reshape(t, hkv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(t, hkv, d)
    q = rotary(rms(q, p["q_norm"]["scale"], eps), sizes, **rope)
    k = rotary(rms(k, p["k_norm"]["scale"], eps), sizes, **rope)
    # q head i reads k/v head i // (h / hkv)
    kv_of = jnp.arange(h) // (h // hkv)
    if kv_head_mod:     # probe only
        kv_of = jnp.arange(h) % hkv
    k, v = k[:, kv_of], v[:, kv_of]
    scale = 1 / math.sqrt(d) if scaled else 1.0
    out = []
    for lo in range(0, t, ATTN_BLOCK):      # a block of queries at a time
        hi = min(lo + ATTN_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", w, v[:hi]))
    o = jnp.concatenate(out) * jax.nn.sigmoid(gate)
    return o.reshape(t, h * d) @ p["o_proj"]["kernel"]


def experts(x, p, sizes, held, sigmoid_scores=False, over_held_only=False,
            shared_ungated=False):
    logits = x @ p["router"]
    scores = (jax.nn.sigmoid(logits) if sigmoid_scores      # probe only
              else jax.nn.softmax(logits, -1))
    _, chosen = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    here = jnp.isin(chosen, jnp.asarray(held))
    norm = jnp.sum(picked, -1, keepdims=True)
    if over_held_only:  # probe only; a row with none of its choices here: 1
        norm = jnp.sum(jnp.where(here, picked, 0.0), -1, keepdims=True)
        norm = jnp.where(norm > 0, norm, 1.0)
    weights = picked / norm
    y = swiglu(x, p["shared"])
    if not shared_ungated:
        y = y * jax.nn.sigmoid(x @ p["shared_gate"]["kernel"])

    def one(y, e):      # a held expert over every row, 0 where not chosen
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
    precision for everything, or one departure from the equations."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    if dtype != jnp.float32:
        probe = {"state_dtype": dtype, **probe}
    eps = sizes["rms_norm_eps"]
    pick = lambda *keys: {k: probe[k] for k in keys if k in probe}
    x = params["embed"]["embedding"][tokens]
    for i in range(sizes["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        normed = rms(x, p["attn_norm"]["scale"], eps)
        if (i + 1) % sizes["full_attention_interval"]:
            y = gated_deltanet(normed, p["gdn"], sizes,
                               **pick("state_dtype", "tiled_keys"))
        else:
            y = gated_attention(normed, p["gattn"], sizes, **pick(
                "scaled", "kv_head_mod", "over_all", "interleaved"))
        x = x + y.astype(dtype)
        normed = rms(x, p["ffn_norm"]["scale"], eps)
        y = experts(normed, p["moe"], sizes, held_ids(sizes), **pick(
            "sigmoid_scores", "over_held_only", "shared_ungated"))
        x = x + y.astype(dtype)
    return rms(x, params["final_norm"]["scale"], eps)


def loss_and_logits(params, tokens, sizes, rows=None, **probe):
    """Mean next-token loss of one sequence, and the logits at ``rows`` (all
    positions when None)."""
    hidden = hidden_states(params, tokens, sizes, **probe)
    head = params["lm_head"].astype(hidden.dtype)
    logp = jax.nn.log_softmax((hidden[:-1] @ head).astype(jnp.float32), -1)
    loss = -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], -1))
    return loss, (hidden if rows is None else hidden[rows]) @ head


def lm_loss(params, tokens, sizes, **probe):
    return loss_and_logits(params, tokens, sizes, **probe)[0]


def logit_rows(length):
    """``LOGIT_ROWS`` positions spread evenly, the last among them."""
    n = min(LOGIT_ROWS, length)
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


def slowed(params, sizes):
    """``params`` with every DeltaNet head's decay ``exp(SLOW_DECAY)`` times
    slower (``A_log - SLOW_DECAY``): a state that holds what it was told."""
    paths = [(f"layers_{i}", "gdn", "A_log")
             for i in range(sizes["num_hidden_layers"])
             if (i + 1) % sizes["full_attention_interval"]]
    return _with_leaves(params, paths,
                        [_leaf(params, p) - SLOW_DECAY for p in paths])


def _rel(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def compare(sizes, built, carry, batch):
    params = built["params"](carry)
    everywhere = jax.tree_util.tree_leaves(params)[0].sharding
    tokens = jax.device_put(batch[0][:1], everywhere)       # one sequence
    length = tokens.shape[1]
    rows = logit_rows(length)
    paths = GRAD_LEAVES
    prefix = tokens[:, :min(GRAD_PREFIX, length)]
    leaves = [_leaf(params, p) for p in paths]

    slow = slowed(params, sizes)
    run = jax.jit(lambda p, t: (built["loss_fn"](p, t)[0],
                                built["logits_fn"](p, t)[0, rows]))
    (sys_loss, sys_logits), (_, sys_slow) = (run(params, tokens),
                                             run(slow, tokens))
    sys_grads = jax.jit(jax.grad(lambda leaves, p, t: built["loss_fn"](
        _with_leaves(p, paths, leaves), t)[0]))(leaves, params, prefix)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(loss_and_logits, sizes=sizes,
                                        rows=rows))
        (ref_loss, ref_logits), (_, ref_slow) = (ref(params, tokens[0]),
                                                 ref(slow, tokens[0]))
        ref_grads = jax.jit(jax.grad(lambda leaves, p, t: lm_loss(
            _with_leaves(p, paths, leaves), t, sizes)))(
                leaves, params, prefix[0])

    loss_rel = abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss))
    logit_rel = _rel(sys_logits, ref_logits)
    slow_rel = _rel(sys_slow, ref_slow)
    grad_rel = {"/".join(p): _rel(s, r)
                for p, s, r in zip(paths, sys_grads, ref_grads)}
    wider = (1.0 if sizes["hidden_size"] == PUBLISHED_HIDDEN
             else OTHER_WIDTH_FACTOR)
    worst = max(loss_rel / LOSS_TOL, logit_rel / LOGIT_TOL,
                slow_rel / SLOW_LOGIT_TOL,
                *(grad_rel["/".join(p)] / tol
                  for p, tol in GRAD_TOLS.items())) / wider
    return {"ok": worst <= 1.0, "rel_diff": worst, "rel_tol": 1.0,
            "system_loss": float(sys_loss), "reference_loss": float(ref_loss),
            "loss_rel_diff": loss_rel, "loss_rel_tol": LOSS_TOL,
            "logit_rel_diff": logit_rel, "logit_rel_tol": LOGIT_TOL,
            "logit_rows": int(rows.shape[0]),
            "slow_logit_rel_diff": slow_rel,
            "slow_logit_rel_tol": SLOW_LOGIT_TOL,
            "grad_rel_diff": grad_rel,
            "grad_rel_tol": {"/".join(p): t for p, t in GRAD_TOLS.items()},
            "grad_prefix": int(prefix.shape[1]), "length": int(length),
            "tolerances_times": wider}
