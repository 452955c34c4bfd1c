"""kanana-2-30b-a3b's next-token loss in plain ``jax.numpy``, float32, no
kernels.

The decoder as the configuration runs it (``configs/kanana2.json``,
``model_type`` ``deepseek_v3``), written from the source's ``config.json``
and ``transformers``' ``modeling_deepseek_v3.py``, reading the model's own
parameter tree and sharing no code with ``apex_tpu``:

- norm: ``x / sqrt(mean(x^2) + eps) * w``, eps 1e-6; block: ``h = x +
  MLA(N(x))``, ``y = h + FFN(N(h))``; after the last block ``N``, then the
  untied head ``lm_head``; the loss is the mean cross-entropy of token
  ``t+1`` at position ``t``, the last unlabelled;
- MLA in every layer: ``q = x W_q`` (32 heads of 128 + 64, no compression),
  ``c = x W_kva`` (512 + 64), ``[k_nope | v] = N(c[:512]) W_kvb`` (32 heads
  of 128 + 128), ``k_pe = c[512:]`` (one head for all); ``q_pe`` and
  ``k_pe`` turned as ``apply_rotary_pos_emb_interleave`` does (:func:`rope`:
  a head's 64 channels viewed as (32, 2), transposed, then ``x cos +
  rotate_half(x) sin`` with ``cos``, ``sin`` of ``p theta^(-2i/64)`` twice
  over, theta 1e6); ``k = [k_nope | k_pe]``, ``q = [q_nope | q_pe]``;
  softmax of ``q k^T / sqrt(192)`` over the keys ``j <= t``, in blocks of
  ``ATTN_BLOCK`` queries that read only the keys their mask can keep; ``W_o``;
- FFN: a SwiGLU of 6144 in layer 0; in the others ``s = sigmoid(u W_r)``
  over all 128 experts, the top 6 of ``s + e_bias`` chosen, weights the
  chosen ``s`` over their sum times 2.448; a loop over the ``held`` ids with
  a 0/1 mask, every token; plus the shared SwiGLU of 2 x 768.

``compare`` decides ``correct``. On the timed batch (one sequence of 8192
tokens) it holds the system's own loss function (``auto_cast`` on, kernels
compiled) against this file for

(a) the loss: ``|sys - ref| / ref <= LOSS_TOL``;
(b) the logits at ``LOGIT_ROWS`` positions spread evenly over the sequence,
    the last among them, twice: the whole, ``|sys - ref|_2 / |ref|_2 <=
    LOGIT_TOL``, and the median over the rows of each row's own relative
    difference, at most ``LOGIT_ROW_TOL``;
(c) the gradients of the leaves of ``GRAD_TOLS`` (each projection of the
    attention, the dense, shared and routed FFNs, the router, the
    embedding, the final norm and the head) on the first ``GRAD_PREFIX`` =
    2048 tokens: ``|sys - ref|_2 / |ref|_2`` of each leaf at most its
    tolerance. 2048 tokens are two of the attention kernels' 1024-token
    query tiles and eight 256-token key tiles, so the backward kernels skip
    the tiles above the diagonal there as they do at 8192.

``rel_diff`` is the largest of the ratios to their tolerances, against
``rel_tol`` = 1.

Each limit was set from readings on one TPU v5e at the cell's size
(``scripts/laguna_s_limits.py --workload kanana2.lm_s8192_b1_v16k`` and
``benchmark/run.py``; fifteen seeds of the system, seven of the control):
what the system gives against this file, and what the *control* gives, this
file's own loss and logits computed wholly in bfloat16 (:func:`control`,
through ``compare`` itself), the precision below the float32 it states.

One reading separates the precisions on every seed, and its limit lies
between them:

- ``LOGIT_ROW_TOL`` 0.0131, the rows' median: system 0.0115 to 0.0118,
  control 0.0144 to 0.0150, 1.10 times above the limit. The control comes
  out not correct by it.

The gradients do not separate them. On any one seed the system reads below
the control on every leaf (0.73 to 0.92 of it on layer 5's ``kv_a``, 0.77
to 0.83 on layer 0's), but the seed moves both readings by more than the gap
between them: on layer 5's ``kv_a`` the system read up to 0.0319 and the
control down to 0.0300, and on the others the two ranges lie 1.13 to 1.17
times apart, so a limit between them fails one of them on some new seed. So
these limits sit between the system and a wrong program, as
``kimi_linear.py``'s do. The wrong programs are this file's two probes, a
half-split rotation and no rotation, against the float32 reference. At three
layers and 2048 tokens they read 0.33 to 1.24 on every leaf compared. Each
limit that rounding sets is 1.25 times the system's largest reading, 4.6 to
5.3 of its standard deviations over seeds above its mean. Largest reading /
limit / the control's range:

- the embedding 0.0551 / 0.069 / 0.0647 to 0.0707;
- layer 0's ``q_proj``, ``kv_a``, ``kv_b``, ``o_proj``: 0.0569 / 0.0711 /
  0.0657 to 0.0720, 0.0548 / 0.0685 / 0.0624 to 0.0706, 0.0547 / 0.0684 /
  0.0632 to 0.0693, 0.0547 / 0.0685 / 0.0625 to 0.0693;
- the dense and shared FFNs 0.0545 / 0.0682 / 0.0615 to 0.0709 and 0.0527
  / 0.0659 / 0.0593 to 0.0661;
- layer 5's ``kv_a`` 0.0319 / 0.0399 / 0.0300 to 0.0378;
- the final norm 0.0288 / 0.0361 / 0.0328 to 0.0366;
- the head 0.0395 / 0.0494 / 0.0451 to 0.0500.

Four more limits sit above the system's readings, where a wrong program still
shows:

- ``LOSS_TOL`` 2e-4: a mean over 8191 positions averages rounding out; the
  system read 1.9e-7 to 7.3e-5, the control 8.5e-6 to 1.48e-4. It is
  there for what shifts every position.
- ``LOGIT_TOL`` 0.07, the whole: a row whose choice of experts flips in one
  of the five expert layers changes as a whole (system 0.0236 to 0.0438,
  control 0.0384 to 0.0572); the rows' median is what rounding sets.
- the router and the held experts' ``up`` 0.3: which experts a row chooses
  sets them, not rounding (system 0.145 to 0.209 and 0.114 to 0.171,
  control 0.224 to 0.327 and 0.173 to 0.207); a wrong rotation reads 1.0
  or more on both.

At any other width than the published one (the rehearsal's toy size) every
tolerance is ``OTHER_WIDTH_FACTOR`` times wider.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LOSS_TOL = 2e-4         # what shifts every position (docstring)
LOGIT_TOL = 7e-2        # the whole, set by routing: above system and control
LOGIT_ROW_TOL = 1.31e-2  # the rows' median: system 0.0118, control 0.0144
LOGIT_ROWS = 256
GRAD_PREFIX = 2048
ATTN_BLOCK = 1024
#: the leaves whose gradients are compared, each with its tolerance: 1.25
#: times the system's largest reading where rounding sets it, 0.3 where a
#: row's choice of experts does; a wrong rotation reads 0.33 or more on
#: every one (docstring)
GRAD_TOLS = {
    ("embed", "embedding"): 6.90e-2,
    ("layers_0", "mla", "q_proj", "kernel"): 7.11e-2,
    ("layers_0", "mla", "kv_a", "kernel"): 6.85e-2,
    ("layers_0", "mla", "kv_b", "kernel"): 6.84e-2,
    ("layers_0", "mla", "o_proj", "kernel"): 6.85e-2,
    ("layers_0", "mlp", "up_proj", "kernel"): 6.82e-2,
    ("layers_1", "moe", "router"): 3e-1,
    ("layers_1", "moe", "experts_up"): 3e-1,
    ("layers_1", "moe", "shared", "up_proj", "kernel"): 6.59e-2,
    ("layers_5", "mla", "kv_a", "kernel"): 3.99e-2,
    ("final_norm", "scale"): 3.61e-2,
    ("lm_head",): 4.94e-2,
}
GRAD_LEAVES = tuple(GRAD_TOLS)
#: the tolerances are for the published widths; anywhere else (the
#: rehearsal's toy size, where a sum is 32 times shorter) they are this much
#: wider; a rehearsal is never correct
OTHER_WIDTH_FACTOR = 2.0
PUBLISHED_HIDDEN = 2048


def rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, p):
    return (silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
            ) @ p["down_proj"]["kernel"]


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rope(x, theta, form="interleaved"):
    """``x`` ``(T, H, D)`` turned by position as ``transformers``' DeepSeek-V3
    does with ``rope_interleave``: each head's channels viewed as ``(D/2,
    2)`` and transposed (the pairs' first channels, then their second), then
    ``x cos + rotate_half(x) sin``. ``form`` is for the probes: ``"half"``
    skips the view and transpose (``apply_rotary_pos_emb``'s half-split
    pairs), ``"none"`` leaves ``x`` unturned."""
    if form == "none":
        return x
    t, h, d = x.shape
    if form == "interleaved":
        x = x.reshape(t, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, d)
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * jnp.asarray(
        inv_freq, jnp.float32)                                   # (T, D/2)
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]       # (T, 1, D)
    return x * jnp.cos(emb).astype(x.dtype) + rotate_half(x) * jnp.sin(
        emb).astype(x.dtype)


def attention(x, p, sizes, form="interleaved"):
    h, rank = sizes["num_attention_heads"], sizes["kv_lora_rank"]
    nope, pe, dv = (sizes[k] for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                       "v_head_dim"))
    theta = float(sizes["rope_theta"])
    t = x.shape[0]
    q = (x @ p["q_proj"]["kernel"]).reshape(t, h, nope + pe)
    c = x @ p["kv_a"]["kernel"]
    kv = (rms(c[:, :rank], p["kv_norm"]["scale"], sizes["rms_norm_eps"])
          @ p["kv_b"]["kernel"]).reshape(t, h, nope + dv)
    k_pe = rope(c[:, None, rank:], theta, form)                 # (T, 1, pe)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta, form)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (t, h, pe))], -1)
    v = kv[..., nope:]
    out = []
    for lo in range(0, t, ATTN_BLOCK):      # a block of queries at a time
        hi = min(lo + ATTN_BLOCK, t)
        s = jnp.einsum("qhd,khd->hqk", q[lo:hi], k[:hi]) / math.sqrt(nope + pe)
        seen = jnp.arange(lo, hi)[:, None] >= jnp.arange(hi)[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", w, v[:hi]))
    return jnp.concatenate(out).reshape(t, h * dv) @ p["o_proj"]["kernel"]


def experts(x, p, sizes, held):
    scores = jax.nn.sigmoid(x @ p["router"])
    _, chosen = jax.lax.top_k(scores + p["e_bias"],
                              sizes["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    weights = (picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
               * sizes["routed_scaling_factor"])

    def one(y, e):          # a held expert over every row, 0 where not chosen
        i, gate, up, down = e
        w = jnp.sum(jnp.where(chosen == i, weights, 0.0), -1)
        return y + w[:, None] * ((silu(x @ gate) * (x @ up)) @ down), None

    y = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.asarray(held), p["experts_gate"], p["experts_up"],
        p["experts_down"]))[0]
    return y + swiglu(x, p["shared"])


def held_ids(sizes):
    return tuple(sizes.get("held_experts", range(sizes["n_routed_experts"])))


def hidden_states(params, tokens, sizes, dtype=jnp.float32, **probe):
    """One sequence ``(T,)`` to the final normed hidden states ``(T, D)``.
    ``dtype`` and ``probe`` are for the probes: another precision for
    everything, or another rotation (``form``). A gradient runs each block
    again (``jax.checkpoint``: the same values) so that 2048 tokens' worth
    fits beside the training state."""
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    eps = sizes["rms_norm_eps"]

    def block(x, p, dense):
        x = x + attention(rms(x, p["attn_norm"]["scale"], eps), p["mla"],
                          sizes, **probe).astype(dtype)
        normed = rms(x, p["ffn_norm"]["scale"], eps)
        y = (swiglu(normed, p["mlp"]) if dense
             else experts(normed, p["moe"], sizes, held_ids(sizes)))
        return x + y.astype(dtype)

    x = params["embed"]["embedding"][tokens]
    for i in range(sizes["num_hidden_layers"]):
        x = jax.checkpoint(block, static_argnums=(2,))(
            x, params[f"layers_{i}"], i < sizes["first_k_dense_replace"])
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
