"""Kimi-Linear's next-token loss in plain ``jax.numpy``, float32, no kernels.

The decoder as the configuration runs it (``configs/kimi_linear.json``),
written from the source's ``config.json`` and arXiv:2510.26692, reading the
model's own parameter tree and sharing no code with ``apex_tpu``:

- block: ``h = x + Mix(rms(x))``, ``y = h + FFN(rms(h))``, eps from the file;
  after the last block ``rms``, then the untied head; the loss is the mean
  cross-entropy of token ``t+1`` at position ``t``, the last unlabelled;
- KDA in its **recurrent** form, one ``lax.scan`` step a token:
  ``S_t = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T``,
  ``o_t = S_t^T q_t``, with ``q, k, v = silu(conv4(W x))``, ``q`` and ``k``
  L2-normalised a head (``q`` times ``d_k^-1/2``), ``g = -exp(A) *
  softplus(W_up W_down x + dt_bias)``, ``b = sigmoid(W_b x)``; the output
  normalised a head and gated by ``sigmoid(W_gb W_ga x)``;
- MLA without positions: a dense causal softmax over ``q k^T / sqrt(192)``,
  ``k = [k_nope ; k_pe]`` with the shared ``k_pe`` **not rotated**;
- experts: a loop over the ``held`` ids with a 0/1 mask, weights normalised
  over all the chosen experts, every token, plus the shared expert.

Departures from the source, each the configuration's (its ``assumed``): no
bias on the output gate, ``1e-6`` inside the L2 norm, no auxiliary loss, the
selection bias added to the scores for the choice only.

``compare`` decides ``correct``. At the timed length, on one sequence of the
batch, it holds the system's own loss function (``auto_cast`` on, kernels
compiled) against this file for

(a) the loss: ``|sys - ref| / ref <= LOSS_TOL``;
(b) the logits at ``LOGIT_ROWS`` positions spread evenly over the sequence,
    the last among them: ``|sys - ref|_2 / |ref|_2 <= LOGIT_TOL``. The
    reference's logits are computed for those rows only (the head is the
    largest matmul); the layers run over the whole sequence, attention in
    blocks of ``ATTN_BLOCK`` queries, so that float32 fits beside the state;
(c) the gradients of the six leaves of ``GRAD_TOLS`` (a KDA projection,
    ``A_log``, MLA's ``kv_b``, the router, the held experts, the head) on the
    first ``GRAD_PREFIX`` = 256 tokens, as long a prefix as the recurrent
    form's backward fits beside 6.7 GiB of training state (it keeps a state
    a token, 2 MB a layer-token): ``|sys - ref|_2 / |ref|_2`` of each leaf
    at most its tolerance.

``rel_diff`` is the largest of the eight ratios to their tolerances, against
``rel_tol`` = 1.

Tolerances, each between two readings on the v5e at the published widths
(PR 28; twelve seeds of the system, ``scripts/kimi_linear_probes.py`` for the
rest; PERF.md has the table). The system computes its matmuls in bfloat16
with float32 accumulation; the residual stream, the delta rule's state, decay
and solve, the router and the norms are float32.

- logits: the system read 0.0195 to 0.0223. This reference with *everything*
  in bfloat16 (weights, residual stream, the delta rule's state rounded a
  token) read 0.0344 and 0.0356 against itself: ``LOGIT_TOL`` = 0.028 is the
  one limit that tells the stated precision from the next one down. A
  bfloat16 state alone reads 0.0222, as much as all of the system's other
  rounding together, so a system with such a state would read ~0.031.
- loss: 1.8e-6 to 4.4e-5; a mean over 8191 positions averages rounding out
  (all-bfloat16: 3e-6 to 4e-5, no different). ``LOSS_TOL`` = 2e-4 is there
  for what shifts every position: weights normalised over the held experts
  only read 6.3e-4.
- gradients of ``k_proj`` 0.027 to 0.035, ``A_log`` 0.026 to 0.048, ``kv_b``
  0.019 to 0.035, ``lm_head`` 0.018 to 0.022 (all-bfloat16: 0.045 to 0.052,
  0.040 to 0.087, 0.031 to 0.036, 0.029 to 0.034: above the system but not
  by enough to stand a limit between). Their limits (0.055, 0.08, 0.09,
  0.035) sit between the system and a rotated ``k_pe``, which reads 0.094,
  0.098, 0.295, 0.052 on them while its logits (0.017) pass unseen.
- gradients of the router 0.07 to 0.37 and of the held experts 0.03 to 0.22:
  set by routing, not by rounding. Where bfloat16 moves a row's eighth
  choice across a held expert's boundary, a whole row changes sides, and on
  256 tokens an expert has about 8 rows (all-bfloat16 reads the same: 0.11
  to 0.28, 0.17 to 0.24). Limits 0.8 and 0.6: weights normalised over the
  held experts only read 0.997 and 6.2.

The five probes, this reference against itself with one thing wrong, as
(loss, logits, worst gradient ratio to its limit): a bfloat16 delta-rule
state (5.8e-5, 0.0222, 0.55: seen only through the system's own total, see
above); a dropped ``1/sqrt(192)`` (8.9e-5, **0.311**, **37**); a rotated
``k_pe`` (1.4e-5, 0.017, **3.3** on ``kv_b``); weights normalised over the
held experts only (**6.3e-4**, **0.45**, **10**); each held expert keeping
only its first 128 rows, half an even share (4.4e-5, **0.064**, 0: the
256-token prefix ends before the first dropped row). Bold fails its limit.

At any other width than the published one (the rehearsal's toy size) every
tolerance is ``OTHER_WIDTH_FACTOR`` times wider: sums are 36 times shorter
there and a row is a larger share of an expert's.
"""

import functools
import math

import jax
import jax.numpy as jnp

LOSS_TOL = 2e-4
LOGIT_TOL = 2.8e-2
LOGIT_ROWS = 256
GRAD_PREFIX = 256
ATTN_BLOCK = 1024
#: the leaves whose gradients are compared, each with its tolerance
GRAD_TOLS = {
    ("layers_0", "kda", "k_proj", "kernel"): 5.5e-2,
    ("layers_0", "kda", "A_log"): 8e-2,
    ("layers_3", "mla", "kv_b", "kernel"): 9e-2,
    ("layers_1", "moe", "router"): 8e-1,
    ("layers_1", "moe", "experts_up"): 6e-1,
    ("lm_head",): 3.5e-2,
}
GRAD_LEAVES = tuple(GRAD_TOLS)
#: the tolerances were read at the published widths. Anywhere else (the
#: rehearsal's toy size, where a sum is 36 times shorter and one row a larger
#: share of an expert's) they are this much wider; a rehearsal is never correct
OTHER_WIDTH_FACTOR = 2.0
PUBLISHED_HIDDEN = 2304


def rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def swiglu(x, p):
    gate = x @ p["gate_proj"]["kernel"]
    return (gate * jax.nn.sigmoid(gate) * (x @ p["up_proj"]["kernel"])
            ) @ p["down_proj"]["kernel"]


def conv_silu(x, taps):
    """``y_t = sum_j taps[j] x_{t-K+1+j}`` a channel, then SiLU. ``(T, C)``."""
    k, t = taps.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    y = sum(padded[j:j + t] * taps[j] for j in range(k))
    return y * jax.nn.sigmoid(y)


def kda(x, p, sizes, state_dtype=jnp.float32):
    lin = sizes["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    t = x.shape[0]
    heads = lambda y: y.reshape(t, h, d)
    q, k, v = (heads(conv_silu(x @ p[n + "_proj"]["kernel"], p[n + "_conv"]))
               for n in "qkv")
    unit = lambda y: y / jnp.sqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(d), unit(k)
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus(
        x @ p["f_a"]["kernel"] @ p["f_b"] + p["dt_bias"]))
    beta = jax.nn.sigmoid(x @ p["b_proj"]["kernel"])            # (T, H)

    def token(state, xs):
        q, k, v, g, beta = xs
        state = jnp.exp(g)[:, :, None] * state
        state = state - beta[:, None, None] * k[:, :, None] * jnp.einsum(
            "hk,hkv->hv", k, state)[:, None, :]
        state = state + beta[:, None, None] * k[:, :, None] * v[:, None, :]
        if state_dtype != jnp.float32:  # probe only; a convert pair is elided
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        return state, jnp.einsum("hkv,hk->hv", state, q)

    # the backward keeps the state a token and computes the rest again
    o = jax.lax.scan(jax.checkpoint(token), jnp.zeros((h, d, d)),
                     (q, k, v, g, beta))[1]
    o = rms(o, p["o_norm"]["scale"], sizes["rms_norm_eps"])
    gate = jax.nn.sigmoid(x @ p["g_a"]["kernel"] @ p["g_b"]["kernel"])
    return (o * heads(gate)).reshape(t, h * d) @ p["o_proj"]["kernel"]


def mla(x, p, sizes, scaled=True, rotate=False):
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, pe, dv = (sizes[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                       "v_head_dim"))
    t = x.shape[0]
    q = (x @ p["q_proj"]["kernel"]).reshape(t, h, nope + pe)
    c = x @ p["kv_a"]["kernel"]
    kv = (rms(c[:, :rank], p["kv_norm"]["scale"], sizes["rms_norm_eps"])
          @ p["kv_b"]["kernel"]).reshape(t, h, nope + dv)
    k_pe = c[:, rank:]
    if rotate:          # probe only: the rotary the model does not apply
        freq = sizes["rope_theta"] ** (-jnp.arange(0, pe, 2) / pe)
        angle = jnp.arange(t)[:, None] * freq
        a, b = k_pe[:, 0::2], k_pe[:, 1::2]
        k_pe = jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                                a * jnp.sin(angle) + b * jnp.cos(angle)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (t, h, pe))], -1)
    v = kv[..., nope:]
    scale = 1 / math.sqrt(nope + pe) if scaled else 1.0
    out = []
    for lo in range(0, t, ATTN_BLOCK):      # a block of queries at a time
        hi = min(lo + ATTN_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) * scale
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", w, v[:hi]))
    return jnp.concatenate(out).reshape(t, h * dv) @ p["o_proj"]["kernel"]


def experts(x, p, sizes, held, over_held_only=False, drop_after=None):
    scores = jax.nn.sigmoid(x @ p["router"])
    k = sizes["num_experts_per_token"]
    _, chosen = jax.lax.top_k(scores + p["e_bias"], k)
    picked = jnp.take_along_axis(scores, chosen, -1)
    here = jnp.isin(chosen, jnp.asarray(held))
    norm = jnp.sum(jnp.where(here, picked, 0.0) if over_held_only else picked,
                   -1, keepdims=True)
    weights = sizes["routed_scaling_factor"] * picked / (norm + 1e-20)
    y = swiglu(x, p["shared"])
    for n, e in enumerate(held):
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), -1)   # 0 if not chosen
        if drop_after is not None:  # probe only: a capacity that drops rows
            w = jnp.where(jnp.cumsum(w > 0) <= drop_after, w, 0.0)
        gate = x @ p["experts_gate"][n]
        y = y + w[:, None] * ((gate * jax.nn.sigmoid(gate)
                               * (x @ p["experts_up"][n]))
                              @ p["experts_down"][n])
    return y


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
    lin = sizes["linear_attn_config"]
    pick = lambda *keys: {k: probe[k] for k in keys if k in probe}
    x = params["embed"]["embedding"][tokens]
    for i in range(sizes["num_hidden_layers"]):
        p = params[f"layers_{i}"]
        normed = rms(x, p["attn_norm"]["scale"], eps)
        if i + 1 in lin["kda_layers"]:
            y = kda(normed, p["kda"], sizes, **pick("state_dtype"))
        else:
            y = mla(normed, p["mla"], sizes, **pick("scaled", "rotate"))
        x = x + y.astype(dtype)
        normed = rms(x, p["ffn_norm"]["scale"], eps)
        if i + 1 <= sizes["first_k_dense_replace"]:
            y = swiglu(normed, p["mlp"])
        else:
            y = experts(normed, p["moe"], sizes, held_ids(sizes),
                        **pick("over_held_only", "drop_after"))
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

    sys_loss, sys_logits = jax.jit(
        lambda p, t: (built["loss_fn"](p, t)[0], built["logits_fn"](p, t)[0, rows])
    )(params, tokens)
    sys_grads = jax.jit(jax.grad(lambda leaves, p, t: built["loss_fn"](
        _with_leaves(p, paths, leaves), t)[0]))(leaves, params, prefix)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_logits = jax.jit(functools.partial(
            loss_and_logits, sizes=sizes, rows=rows))(params, tokens[0])
        ref_grads = jax.jit(jax.grad(lambda leaves, p, t: lm_loss(
            _with_leaves(p, paths, leaves), t, sizes)))(
                leaves, params, prefix[0])

    loss_rel = abs(float(sys_loss) - float(ref_loss)) / abs(float(ref_loss))
    logit_rel = _rel(sys_logits, ref_logits)
    grad_rel = {"/".join(p): _rel(s, r)
                for p, s, r in zip(paths, sys_grads, ref_grads)}
    wider = (1.0 if sizes["hidden_size"] == PUBLISHED_HIDDEN
             else OTHER_WIDTH_FACTOR)
    worst = max(loss_rel / LOSS_TOL, logit_rel / LOGIT_TOL,
                *(grad_rel["/".join(p)] / tol
                  for p, tol in GRAD_TOLS.items())) / wider
    return {"ok": worst <= 1.0, "rel_diff": worst, "rel_tol": 1.0,
            "system_loss": float(sys_loss), "reference_loss": float(ref_loss),
            "loss_rel_diff": loss_rel, "loss_rel_tol": LOSS_TOL,
            "logit_rel_diff": logit_rel, "logit_rel_tol": LOGIT_TOL,
            "logit_rows": int(rows.shape[0]),
            "grad_rel_diff": grad_rel,
            "grad_rel_tol": {"/".join(p): t for p, t in GRAD_TOLS.items()},
            "grad_prefix": int(prefix.shape[1]), "length": int(length),
            "tolerances_times": wider}
