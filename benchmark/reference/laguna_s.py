"""Laguna-S-2.1's next-token loss in plain ``jax.numpy``, float32, no kernels.

The decoder as the configuration runs it (``configs/laguna_s.json``),
written from the source's ``config.json`` and the equations its ``assumed``
states, reading the model's own parameter tree and sharing no code with
``apex_tpu``:

- norm: ``x / sqrt(mean(x^2) + eps) * w``, eps 1e-6; block: ``h = x +
  Attn(N(x))``, ``y = h + FFN(N(h))``; after the last block ``N``, then the
  untied head ``lm_head``; the loss is the mean cross-entropy of token
  ``t+1`` at position ``t``, the last unlabelled;
- attention, ``heads`` q heads (``num_attention_heads_per_layer``) on 8 k/v
  heads of 128, q head ``h`` reading k/v head ``h // (heads / 8)``, no q/k
  norm, no biases: rotary in half-split pairs ``(m, m + r/2)`` over the
  first ``r`` channels of q and k at positions ``0 ... T - 1``; a
  ``full_attention`` layer turns ``r = 64`` channels at YaRN's frequencies
  (this file's :func:`yarn`: theta 5e5, factor 128, original 8192, beta_fast
  32, beta_slow 1) with ``cos`` and ``sin`` times the attention factor
  1.4852, a ``sliding_attention`` layer all 128 at theta 1e4's plain ones;
  softmax of ``q k^T / sqrt(128)`` over the keys ``j <= t`` (global) or ``t
  - 511 <= j <= t`` (window), in blocks of ``ATTN_BLOCK`` queries that read
  only the keys their mask can keep; each head's output times ``sigmoid(u
  W_g)_h``, then ``W_o``;
- FFN: a SwiGLU of 12 288 in the ``dense`` layer; in a ``sparse`` one ``p =
  softmax(u W_r)`` over all 256 experts, the 10 largest chosen, weights the
  chosen ``p`` over their sum times 2.5; a loop over the ``held`` ids with a
  0/1 mask, every token; plus the shared SwiGLU of 1024, ungated.

``compare`` decides ``correct``. On the timed batch (one sequence of 4096
tokens) it holds the system's own loss function (``auto_cast`` on, kernels
compiled) against this file for

(a) the loss: ``|sys - ref| / ref <= LOSS_TOL``;
(b) the logits at ``LOGIT_ROWS`` positions spread evenly over the sequence,
    the last among them, twice: the whole, ``|sys - ref|_2 / |ref|_2 <=
    LOGIT_TOL``, and the median over the rows of each row's own relative
    difference, at most ``LOGIT_ROW_TOL``;
(c) the gradients of the leaves of ``GRAD_TOLS`` (each module of each kind
    of layer, the embedding and the head) on the first ``GRAD_PREFIX`` =
    2048 tokens: ``|sys - ref|_2 / |ref|_2`` of each leaf at most its
    tolerance. 2048 tokens are four of the window kernels' 512-token tiles,
    so the backward kernels skip tiles left of the band there as they do at
    4096 (q tiles 2 and 3 skip k tiles 0 and 1), and two of the global
    layers' 1024-token tiles.

``rel_diff`` is the largest of the ratios to their tolerances, against
``rel_tol`` = 1.

Each limit was set from two readings on one TPU v5e at the cell's size
(``scripts/laguna_s_limits.py``; three seeds of the system, two of the
control): the largest the system gives against this file, and what the
*control* gives, this file's own loss and logits computed wholly in
bfloat16 (:func:`control`, through ``compare`` itself), the precision below
the float32 it states. Where rounding sets a reading the two stay apart
over seeds, and the limit is their geometric mean, ~1.25 times above the
one and below the other:

- ``LOGIT_ROW_TOL`` 0.025, the rows' median: system 0.01817 to 0.01819,
  control 0.0334 to 0.0337;
- ``LOGIT_TOL`` 0.028, the whole: system 0.0204 to 0.0210, control 0.0361
  to 0.0369;
- each gradient but two in ``GRAD_TOLS``, system against control: the
  embedding 0.0315 / 0.0508, the attention projections, gates and
  ``o_proj`` 0.031 to 0.056 / 0.048 to 0.089, the dense and shared FFNs
  0.031 and 0.033 / 0.049 and 0.053, the final norm 0.0151 / 0.0251, the
  head 0.0204 / 0.0340.

Two readings do not separate the precisions, and their limits sit above
both, where a wrong program still shows:

- ``LOSS_TOL`` 1e-4: a mean over 4095 positions averages rounding out; the
  system read 1.7e-5 to 3.7e-5, the control 5.8e-6 and 1.25e-4. It is
  there for what shifts every position.
- the router and the held experts' ``up`` 0.3: which experts a row chooses
  sets them, not rounding (system 0.112 to 0.139 and 0.098 to 0.108,
  control 0.16 to 0.19 and 0.15 to 0.17); a wrong expert gradient reads
  near 1.

So the control comes out not correct by the logits and by every gradient
that rounding sets (``rel_diff`` 1.3 to 1.35 where the system reads 0.8).

At any other width than the published one (the rehearsal's toy size) every
tolerance is ``OTHER_WIDTH_FACTOR`` times wider.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOL = 1e-4         # what shifts every position (docstring)
LOGIT_TOL = 2.8e-2      # the whole: system 0.021, control 0.036
LOGIT_ROW_TOL = 2.5e-2  # the rows' median: system 0.0182, control 0.0334
LOGIT_ROWS = 256
GRAD_PREFIX = 2048
ATTN_BLOCK = 512
#: the leaves whose gradients are compared, each with its tolerance: the
#: geometric mean of the system's largest reading and the control's
#: smallest where rounding sets them, 0.3 where a row's choice of experts
#: does (docstring)
GRAD_TOLS = {
    ("embed", "embedding"): 4.0e-2,
    ("layers_0", "fullattn", "q_proj", "kernel"): 4.0e-2,
    ("layers_0", "fullattn", "g_proj", "kernel"): 3.9e-2,
    ("layers_0", "mlp", "up_proj", "kernel"): 3.9e-2,
    ("layers_1", "swa", "q_proj", "kernel"): 5.5e-2,
    ("layers_1", "swa", "k_proj", "kernel"): 5.5e-2,
    ("layers_1", "swa", "v_proj", "kernel"): 3.9e-2,
    ("layers_1", "swa", "g_proj", "kernel"): 4.7e-2,
    ("layers_1", "swa", "o_proj", "kernel"): 3.9e-2,
    ("layers_1", "moe", "router"): 3e-1,
    ("layers_1", "moe", "experts_up"): 3e-1,
    ("layers_1", "moe", "shared", "up_proj", "kernel"): 4.2e-2,
    ("layers_4", "fullattn", "k_proj", "kernel"): 7.1e-2,
    ("final_norm", "scale"): 1.9e-2,
    ("lm_head",): 2.6e-2,
}
GRAD_LEAVES = tuple(GRAD_TOLS)
#: the tolerances are for the published widths; anywhere else (the
#: rehearsal's toy size, where a sum is 48 times shorter) they are this much
#: wider; a rehearsal is never correct
OTHER_WIDTH_FACTOR = 2.0
PUBLISHED_HIDDEN = 3072


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, p):
    return (silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
            ) @ p["down_proj"]["kernel"]


def yarn(rope, channels):
    """YaRN's inverse frequencies for ``channels`` rotated channels and its
    attention factor: pair ``m``'s plain ``theta^(-2m / r)`` below the
    correction range, that over ``factor`` from its top on, the linear ramp
    between; the range's ends are ``floor`` and ``ceil`` of ``r ln(L / (2 pi
    beta)) / (2 ln theta)`` at ``beta_fast`` and ``beta_slow``."""
    theta, factor = rope["rope_theta"], rope["factor"]
    length = rope["original_max_position_embeddings"]
    end = lambda beta: channels * math.log(length / (2 * math.pi * beta)) / (
        2 * math.log(theta))
    low = max(math.floor(end(rope["beta_fast"])), 0)
    high = min(math.ceil(end(rope["beta_slow"])), channels - 1)
    plain = 1.0 / theta ** (np.arange(0, channels, 2) / channels)
    ramp = np.clip((np.arange(channels // 2) - low) / (high - low), 0, 1)
    return (plain * (1 - ramp) + plain / factor * ramp,
            rope["attention_factor"])


def rotary(x, rope, head_dim, plain=False, unscaled=False):
    """``x`` ``(T, H, D)``: the first ``r`` channels turned by position,
    pairs ``(m, m + r / 2)``; the rest unrotated. ``plain`` / ``unscaled``
    are probes: YaRN's frequencies replaced by theta's plain ones, its
    factor left off."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    if rope["rope_type"] == "yarn" and not plain:
        freq, scale = yarn(rope, r)
    else:
        freq = 1.0 / rope["rope_theta"] ** (np.arange(0, r, 2) / r)
        scale = rope.get("attention_factor", 1.0)
    if rope["rope_type"] != "yarn" or unscaled:
        scale = 1.0
    angle = jnp.arange(x.shape[0])[:, None, None] * jnp.asarray(
        freq, jnp.float32)                                       # (T, 1, R/2)
    cos = (jnp.cos(angle) * scale).astype(x.dtype)
    sin = (jnp.sin(angle) * scale).astype(x.dtype)
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]],
                           -1)


def attention(x, p, sizes, kind, heads, no_window=False, plain_yarn=False,
              unscaled_yarn=False, ungated=False):
    hkv, d = sizes["num_key_value_heads"], sizes["head_dim"]
    t = x.shape[0]
    rope = sizes["rope_parameters"][kind]
    window = (None if kind == "full_attention" or no_window
              else sizes["sliding_window"])
    q = (x @ p["q_proj"]["kernel"]).reshape(t, heads, d)
    k = (x @ p["k_proj"]["kernel"]).reshape(t, hkv, d)
    v = (x @ p["v_proj"]["kernel"]).reshape(t, hkv, d)
    q, k = (rotary(y, rope, d, plain_yarn, unscaled_yarn) for y in (q, k))
    # q head i reads k/v head i // (heads / hkv)
    kv_of = jnp.arange(heads) // (heads // hkv)
    k, v = k[:, kv_of], v[:, kv_of]
    out = []
    for lo in range(0, t, ATTN_BLOCK):      # a block of queries at a time
        hi = min(lo + ATTN_BLOCK, t)
        first = 0 if window is None else max(0, lo - window + 1)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[first:hi]) / math.sqrt(d)
        lag = jnp.arange(lo, hi)[:, None] - jnp.arange(first, hi)[None, :]
        seen = (lag >= 0) if window is None else (lag >= 0) & (lag < window)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", w, v[first:hi]))
    o = jnp.concatenate(out)
    if not ungated:         # probe only when off
        o = o * jax.nn.sigmoid(x @ p["g_proj"]["kernel"])[..., None]
    return o.reshape(t, heads * d) @ p["o_proj"]["kernel"]


def experts(x, p, sizes, held):
    scores = jax.nn.softmax(x @ p["router"], -1)
    _, chosen = jax.lax.top_k(scores, sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = (picked / jnp.sum(picked, -1, keepdims=True)
               * sizes["moe_routed_scaling_factor"])

    def one(y, e):          # a held expert over every row, 0 where not chosen
        i, gate, up, down = e
        w = jnp.sum(jnp.where(chosen == i, weights, 0.0), -1)
        return y + w[:, None] * ((silu(x @ gate) * (x @ up)) @ down), None

    y = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.asarray(held), p["experts_gate"], p["experts_up"],
        p["experts_down"]))[0]
    return y + swiglu(x, p["shared"])


def held_ids(sizes):
    return tuple(sizes.get("held_experts", range(sizes["num_experts"])))


def hidden_states(params, tokens, sizes, dtype=jnp.float32, **probe):
    """One sequence ``(T,)`` to the final normed hidden states ``(T, D)``.
    ``dtype`` and ``probe`` are for the probes: another precision for
    everything, or one departure from the equations. A gradient runs each
    block again (``jax.checkpoint``: the same values) so that 2048 tokens'
    worth fits beside the training state."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = sizes["rms_norm_eps"]

    def block(x, p, kind, heads, mlp):
        normed = rms(x, p["attn_norm"]["scale"], eps)
        name = "swa" if kind == "sliding_attention" else "fullattn"
        x = x + attention(normed, p[name], sizes, kind, heads,
                          **probe).astype(dtype)
        normed = rms(x, p["ffn_norm"]["scale"], eps)
        y = (swiglu(normed, p["mlp"]) if mlp == "dense"
             else experts(normed, p["moe"], sizes, held_ids(sizes)))
        return x + y.astype(dtype)

    x = params["embed"]["embedding"][tokens]
    for i, layer in enumerate(zip(sizes["layer_types"],
                                  sizes["num_attention_heads_per_layer"],
                                  sizes["mlp_layer_types"])):
        x = jax.checkpoint(block, static_argnums=(2, 3, 4))(
            x, params[f"layers_{i}"], *layer)
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


def control(built, sizes, dtype=jnp.bfloat16):
    """``built`` with its loss and logits replaced by this file's own in
    ``dtype``: the reference in the precision below the float32 it states,
    which :func:`compare` must find not correct."""
    def loss_fn(params, tokens):
        return jnp.mean(jax.lax.map(lambda t: lm_loss(
            params, t, sizes, dtype=dtype), tokens)), None

    def logits_fn(params, tokens):
        return jax.lax.map(lambda t: loss_and_logits(
            params, t, sizes, dtype=dtype)[1], tokens)
    return {**built, "loss_fn": loss_fn, "logits_fn": logits_fn}


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


def _host(x):
    return np.asarray(jax.device_get(x), np.float64)


def _rel(a, b):
    """``|a - b|_2 / |b|_2`` on the host: what is compared leaves the
    chip's memory as soon as it is made."""
    a, b = _host(a), _host(b)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _rows_rel(a, b):
    """The median over rows of each row's own ``|a - b|_2 / |b|_2``: what
    rounding does to every row, whatever a changed choice of experts does to
    a few."""
    a, b = (_host(x).reshape(-1, x.shape[-1]) for x in (a, b))
    return float(np.median(np.linalg.norm(a - b, axis=-1)
                           / np.linalg.norm(b, axis=-1)))


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
    sys_grads = jax.device_get(jax.jit(jax.grad(
        lambda leaves, p, t: built["loss_fn"](
            _with_leaves(p, paths, leaves), t)[0]))(leaves, params, prefix))
    with jax.default_matmul_precision("highest"):
        # a sequence at a time; equal lengths: the mean of the means
        ref_loss, ref_logits = jax.jit(lambda p, t: jax.lax.map(
            functools.partial(loss_and_logits, p, sizes=sizes, rows=rows),
            t))(params, tokens)
        ref_loss = jnp.mean(ref_loss)
        ref_grad = jax.jit(jax.grad(lambda leaves, p, t: lm_loss(
            _with_leaves(p, paths, leaves), t, sizes)))
        ref_grads = [sum(of_leaf) / n for of_leaf in zip(*(
            jax.device_get(ref_grad(leaves, params, sequence))
            for sequence in prefix))]

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
