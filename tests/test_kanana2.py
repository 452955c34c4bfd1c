"""``models/deepseek_v3.py`` against the plain reference the benchmark keeps
(``benchmark/reference/kanana2.py``: float32 ``jax.numpy``, latent attention
in blocks with the rotation written as ``transformers`` writes it, a loop
over the held experts beside the shared one, an untied head), at the
configuration's toy size; and the configuration's own numbers at the
published widths (``benchmark/configs/kanana2.json``)."""

import dataclasses
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp, models
from apex_tpu.models import deepseek_v3, kimi_linear, mla

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference_kanana2",
            ROOT / "benchmark" / "reference" / "kanana2.py")
BUILD = _load("configs_kanana2", ROOT / "benchmark" / "configs" /
              "kanana2.py")
FULL = json.loads((ROOT / "benchmark" / "configs" / "kanana2.json")
                  .read_text())
TOY = {**FULL, **FULL["toy"]}
#: the dense layer and one expert layer: every kind of layer, a third of the
#: toy's compile time
SMALL = {**TOY, "num_hidden_layers": 2}
LENGTH = 150            # no whole number of attention tiles


def stirred(params, seed=7, gain=3):
    """Seeded weights that no part of the model is blind to: the norm
    scales and the selection bias off their constants, the matrices
    ``gain`` times their initial spread."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) if x.ndim == 1 else gain * x
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def toy():
    model = models.deepseek_v3_from_config(SMALL)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0,
                                SMALL["vocab_size"])
    params = stirred(model.init(jax.random.PRNGKey(0), tokens)["params"])
    return model, params, tokens


def reference_loss(params, tokens, sizes=SMALL):
    return sum(REF.lm_loss(params, t, sizes) for t in tokens) / len(tokens)


def rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / jnp.linalg.norm(b))


def test_layer_kinds_and_sizes_are_read_from_the_file():
    """Layer 0 dense (``first_k_dense_replace`` 1), layers 1-5 experts, MLA
    in each, at the published 48 as at the cut's 6; the shared expert is
    ``n_shared_experts`` times an expert's width; a form the module does not
    build raises."""
    model = models.deepseek_v3_from_config(FULL)
    assert model.layer_kinds == (("mla", "dense"),) + (("mla", "moe"),) * 5
    published = models.deepseek_v3_from_config({
        **FULL, "num_hidden_layers": 48, "n_routed_experts": 128,
        "held_experts": list(range(128))})
    assert [f for _, f in published.layer_kinds] == ["dense"] + ["moe"] * 47
    d = model.dims
    assert (d.heads, d.kv_rank, d.nope_dim, d.rope_dim, d.v_dim,
            d.rope_theta) == (32, 512, 128, 64, 128, 1e6)
    assert (d.dense_width, d.expert_width, d.shared_width, d.n_routed,
            d.top_k, d.held, d.route_scale, d.eps) == (
        6144, 768, 1536, 128, 6, tuple(range(16)), 2.448, 1e-6)
    assert published.dims.n_routed == 128
    for wrong in ({"q_lora_rank": 1536}, {"rope_interleave": False},
                  {"rope_scaling": {"type": "yarn", "factor": 40}},
                  {"scoring_func": "softmax"}, {"n_group": 8},
                  {"norm_topk_prob": False}, {"num_key_value_heads": 8},
                  {"head_dim": 128}, {"qk_head_dim": 128},
                  {"tie_word_embeddings": True}, {"moe_layer_freq": 2}):
        with pytest.raises(ValueError):
            models.deepseek_v3_from_config({**FULL, **wrong})


def test_parameter_count_at_the_published_widths():
    """687.50 M parameters = 11.0 GB at 16 B: MLA 26.35 M a layer (q 12.58,
    kv_a 1.18, kv_b 4.19, o 8.39, the 512-wide norm), an expert layer's
    router 0.26 M and shared expert 9.44 M beside 16 held experts of 4.72 M,
    the dense layer's SwiGLU 37.75 M, an eighth of the embedding and of the
    untied head 65.67 M, thirteen norms."""
    model = models.deepseek_v3_from_config(FULL)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    d = 2048
    attn = (d * 32 * 192 + d * 576 + 512 * 32 * 256 + 32 * 128 * d + 512)
    expert, router, shared = 3 * d * 768, d * 128, 3 * d * 1536
    assert count(shapes["layers_0"]["mla"]) == attn == 26_345_984
    assert count(shapes["layers_0"]["mlp"]) == 3 * d * 6144 == 37_748_736
    assert count(shapes["layers_1"]["moe"]) == (router + 128 + shared
                                                + 16 * expert)
    assert count(shapes["layers_1"]["moe"]["shared"]) == shared == 9_437_184
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == d * 16032
    assert count(shapes) == (6 * attn + 3 * d * 6144 + 5 * (
        router + 128 + shared + 16 * expert) + 2 * d * 16032
        + 13 * d) == 687_502_976
    assert 16 * count(shapes) / 2 ** 30 == pytest.approx(10.24, abs=5e-3)
    # the leaves the chip's comparison holds are the model's
    for leaf in REF.GRAD_LEAVES:
        REF._leaf(shapes, leaf)


def test_the_flops_count_what_the_issue_reckons():
    """26.86 TFLOP a sequence of 8192: 6 a token for 294.9 M matmul
    parameters (the routed experts at 6 * 16 / 128 of one), 12.37 of causal
    attention at 192 + 128 a head over half the square."""
    attention = 6 * 3 * 2 * 32 * (192 + 128) * 8192 ** 2 / 2
    touched = (6 * 26_345_472 + 3 * 2048 * 6144 + 5 * (
        2048 * 128 + 3 * 2048 * 1536 + 6 * 16 / 128 * 3 * 2048 * 768)
        + 2048 * 16032)
    assert BUILD.flops_per_sequence(FULL, 8192) == pytest.approx(
        6 * touched * 8192 + attention)
    assert attention / BUILD.flops_per_sequence(FULL, 8192) == pytest.approx(
        0.46, abs=5e-3)
    assert BUILD.flops_per_sequence(FULL, 8192) == pytest.approx(26.86e12,
                                                                 rel=1e-3)


def _in_place(x, theta):
    """Pairs ``(2i, 2i + 1)`` of ``(..., T, H, D)`` turned where they stand."""
    d, t = x.shape[-1], x.shape[-3]
    angle = (np.arange(t)[:, None] * theta ** (-np.arange(0, d, 2) / d))
    cos, sin = np.cos(angle)[:, None, :], np.sin(angle)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    out = np.empty_like(x)
    out[..., 0::2], out[..., 1::2] = a * cos - b * sin, b * cos + a * sin
    return out


def test_interleaved_rotation_is_transformers_and_pairs_in_place():
    """``models.interleaved_rotary`` equals the reference's rotation written
    as ``transformers``' ``apply_rotary_pos_emb_interleave`` (view as pairs,
    transpose, ``x cos + rotate_half(x) sin``), channel for channel; its
    output is laid out de-interleaved, and the scores of a q and a k both
    taken through it equal those of their pairs turned in place. It is not
    the half-split form."""
    q, k = (jax.random.normal(jax.random.PRNGKey(i), (1, 40, 3, 64))
            for i in (0, 1))
    got = models.interleaved_rotary(q, 1e6)
    with jax.default_matmul_precision("highest"):
        want = REF.rope(q[0], 1e6)
        assert float(jnp.max(jnp.abs(got[0] - want))) <= 1e-5
        half = REF.rope(q[0], 1e6, form="half")
        assert float(jnp.max(jnp.abs(got[0] - half))) > 0.1
        placed = [_in_place(np.asarray(x, np.float64), 1e6) for x in (q, k)]
        scores = jnp.einsum("bqhd,bkhd->bhqk", got,
                            models.interleaved_rotary(k, 1e6))
    np.testing.assert_allclose(
        scores, np.einsum("bqhd,bkhd->bhqk", *placed), atol=1e-4)
    # de-interleaved: the turned first channels of the pairs lead
    np.testing.assert_allclose(got[..., :32], placed[0][..., 0::2],
                               atol=1e-5)
    np.testing.assert_allclose(got[..., 32:], placed[0][..., 1::2],
                               atol=1e-5)


def test_attention_against_the_reference(toy):
    """At the toy size (2 heads of 16 + 16, 150 tokens), forward and input
    gradient; the probes move it: half-split pairs, no rotation."""
    model, params, _ = toy
    p = params["layers_0"]["mla"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, LENGTH, 64))
    layer = model.dims.mixer("mla")
    got = layer.apply({"params": p}, x)[0]
    top = float(jnp.max(jnp.abs(got)))
    with jax.default_matmul_precision("highest"):
        want = REF.attention(x[0], p, TOY)
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * max(top, 1.0)
        for form in ("half", "none"):
            other = REF.attention(x[0], p, TOY, form=form)
            assert float(jnp.max(jnp.abs(got - other))) > 1e-3 * top, form
        grad = lambda fn: jax.grad(lambda x: jnp.sum(jnp.sin(fn(x))))(x)
        assert float(jnp.max(jnp.abs(
            grad(lambda x: layer.apply({"params": p}, x))
            - grad(lambda x: REF.attention(x[0], p, TOY)[None])))) <= 1e-4


def test_float32_model_equals_the_reference(toy):
    """No policy (O0): loss, logits and every gradient leaf, tightly."""
    model, params, tokens = toy
    logits, load = model.apply({"params": params}, tokens)
    for seq, got in zip(tokens, logits):
        want = REF.loss_and_logits(params, seq, SMALL)[1]
        assert rel(got, want) <= 1e-5
    loss_fn = lambda p: models.lm_loss(model, {"params": p}, tokens)
    (loss, routing), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(params, tokens)
    assert float(abs(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(got - want)) <= 2e-3 * max(
            float(jnp.linalg.norm(want)), 1e-3), jax.tree_util.keystr(path)
    # a row for the expert layer; the selection bias, which no gradient
    # moves, has none
    assert routing["expert_load"].shape == (1, 4)
    assert load.tolist() == routing["expert_load"].tolist()
    assert float(jnp.max(jnp.abs(grads["layers_1"]["moe"]["e_bias"]))) == 0


def test_the_eight_shares_of_an_expert_layer_add_up():
    """EP8 at a small size: the layer as the model builds it (sigmoid scores
    over 64, the top 6 of score + bias, weights renormalised and times
    2.448, a shared expert of twice an expert's width), eight shares of 8
    experts each over one router: the shares' routed parts and the shared
    expert, which every share computes alike, counted once, add up to the
    uncut reference's whole layer."""
    sizes = {**TOY, "num_experts_per_tok": 6, "n_routed_experts": 64,
             "router_experts": 64, "held_experts": list(range(64))}
    dims = models.deepseek_v3_from_config(sizes).dims
    assert dims.shared_width == 2 * 32
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    p = dims.experts().init(jax.random.PRNGKey(1), x)["params"]
    p = {**p, "router": p["router"] * 30,
         "e_bias": 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,)),
         **{n: p[n] * 5 for n in ("experts_gate", "experts_up",
                                  "experts_down")}}
    assert set(p) == {"router", "e_bias", "experts_gate", "experts_up",
                      "experts_down", "shared"}
    rows = x.reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        whole = REF.experts(rows, p, sizes, tuple(range(64)))
        shared = REF.swiglu(rows, p["shared"])
        top = float(jnp.max(jnp.abs(whole)))
        total, count = shared, 0
        for rank in range(8):
            held = tuple(range(8 * rank, 8 * rank + 8))
            mine = {**p, **{n: p[n][8 * rank:8 * rank + 8] for n in (
                "experts_gate", "experts_up", "experts_down")}}
            part, load = dataclasses.replace(dims, held=held).experts().apply(
                {"params": mine}, x)
            want = REF.experts(rows, mine, sizes, held)
            assert float(jnp.max(jnp.abs(part.reshape(-1, 64) - want))) <= (
                1e-5 * top)
            routed = part.reshape(-1, 64) - shared
            assert float(jnp.max(jnp.abs(routed))) > 1e-2 * top
            total, count = total + routed, count + int(load.sum())
    assert count == 2 * 40 * 6          # every assignment on one rank
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * top


def test_remat_changes_nothing(toy):
    model, params, tokens = toy
    again = models.deepseek_v3_from_config(SMALL, remat=True)
    run = lambda m: jax.value_and_grad(
        lambda p: models.lm_loss(m, {"params": p}, tokens)[0])(params)
    (loss, a), (loss_again, b) = run(model), run(again)
    assert float(abs(loss - loss_again)) <= 1e-6 * float(loss)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-6


def test_kernels_in_the_lowered_step(monkeypatch):
    """The dense layer and an expert layer, every block recomputed, lowered
    for the TPU: the differentiated loss holds attention's forward kernel
    once a layer (the rerun keeps its ``o`` and ``lse``) and its backward in
    each (150 tokens are one tile: the fused kernel; at 1024 tokens and the
    tiles ``models/mla.py`` passes, several: the two-kernel backward), and
    every scope a reader cuts by, ``mla/rope`` among them. Four heads of 32:
    the toy's two are too few to fill the kernels' 128 lanes and take the
    ``jax.numpy`` form."""
    from apex_tpu.ops import _dispatch, attention
    sizes = {**SMALL, "num_attention_heads": 4, "num_key_value_heads": 4}

    def lowered(length):
        model = models.deepseek_v3_from_config(sizes, remat=True)
        tokens = jnp.zeros((1, length), jnp.int32)
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(3), tokens))["params"]
        policy = amp.Policy.from_opt_level("O1")

        def loss(p):
            with amp.auto_cast(policy):
                return models.lm_loss(model, {"params": p}, tokens)[0]

        with monkeypatch.context() as m:
            for mod in (_dispatch, attention):
                m.setattr(mod, "use_interpret", lambda: False)
            return jax.jit(jax.value_and_grad(loss)).trace(params).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)

    text = lowered(LENGTH)
    kernels = _dispatch.kernel_calls(text)
    assert kernels["apex_attn_fwd"] == kernels["apex_attn_bwd"] == 2
    for scope in ("mla/proj", "mla/rope", "mla/attn", "mla/out", "moe/route",
                  "moe/dispatch", "moe/experts", "moe/combine", "moe/shared",
                  "lm/head"):
        assert scope in text, scope
    kernels = _dispatch.kernel_calls(lowered(1024))
    assert kernels["apex_attn_fwd"] == 2
    assert kernels["apex_attn_bwd_dq"] == kernels["apex_attn_bwd_dkv"] == 2


def _attention_operands_and_pads(model, length):
    """The differentiated O1 loss of ``model`` on ``length`` tokens: for
    each attention kernel the columns of its v operand, and for every
    ``pad`` under ``mla/`` its last size and scope path."""
    tokens = jnp.zeros((1, length), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(3), tokens))["params"]
    policy = amp.Policy.from_opt_level("O1")

    def loss(p):
        with amp.auto_cast(policy):
            return models.lm_loss(model, {"params": p}, tokens)[0]

    kernels, pads = [], []

    def walk(jaxpr, outer=""):
        for eqn in jaxpr.eqns:
            path = f"{outer}/{eqn.source_info.name_stack}"
            if eqn.primitive.name == "pallas_call":
                if eqn.params["name"].startswith("apex_attn"):
                    kernels.append((eqn.params["name"],
                                    eqn.invars[2].aval.shape[-1]))
                continue
            if eqn.primitive.name == "pad" and "mla/" in path:
                pads.append((eqn.outvars[0].aval.shape[-1], path))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, path)

    walk(jax.make_jaxpr(jax.value_and_grad(loss))(params).jaxpr)
    return kernels, pads


@pytest.mark.parametrize("name", ["kanana2", "kimi_linear"])
def test_the_kernels_take_v_at_its_own_head_size(name):
    """Both MLA cells' step at their published head sizes (q and k 128 +
    64, v 128; two heads, one kernel step's group) over 1024 tokens in the
    tiles ``models/mla.py`` passes: the two backward kernels read ``v`` at
    two heads of 128 columns, the forward kernel at two of 192 (the op pads
    it there, inside ``mla/attn``'s forward), and nothing else under
    ``mla/`` pads to the q/k head size of 192: not the model (``v`` under
    ``mla/proj``), not the backward (``dO``); the rotation's backward,
    which puts q's two parts back together, aside."""
    config = json.loads((ROOT / "benchmark" / "configs" / f"{name}.json")
                        .read_text())
    sizes = {**config, **config["toy"], "num_attention_heads": 2,
             "num_key_value_heads": 2, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "v_head_dim": 128}
    if name == "kanana2":
        sizes.update(num_hidden_layers=2, head_dim=64, qk_head_dim=192)
        model = models.deepseek_v3_from_config(sizes, remat=True)
    else:
        model = models.kimi_linear_from_config(sizes, remat=True)
    kernels, pads = _attention_operands_and_pads(model, 1024)
    assert set(kernels) == {("apex_attn_fwd", 2 * 192),
                            ("apex_attn_bwd_dq", 2 * 128),
                            ("apex_attn_bwd_dkv", 2 * 128)}
    padded = [path for width, path in pads
              if width == 128 + 64 and "mla/rope" not in path]
    assert padded and all("mla/attn" in path and "transpose(" not in path
                          for path in padded), padded


def test_o1_model_is_near_the_reference(toy):
    """Under ``auto_cast`` the matmuls run in bfloat16 with float32
    accumulation; the rotation, the router and the norms stay float32. On
    matrices at their initial spread."""
    model, _, tokens = toy
    params = stirred(model.init(jax.random.PRNGKey(0), tokens)["params"],
                     gain=1)
    policy = amp.Policy.from_opt_level("O1")

    def loss_fn(p):
        with amp.auto_cast(policy):
            return models.lm_loss(model, {"params": p}, tokens)[0]

    with amp.auto_cast(policy):
        logits = model.apply({"params": params}, tokens)[0]
    assert logits.dtype == jnp.bfloat16
    want = jnp.stack([REF.loss_and_logits(params, t, SMALL)[1]
                      for t in tokens])
    assert rel(logits, want) <= 3e-2
    loss, grads = jax.value_and_grad(loss_fn)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(params, tokens)
    assert float(abs(loss - ref_loss)) <= 2e-3 * float(ref_loss)
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))
    for path in (p for p in REF.GRAD_LEAVES if p[0] in grads):
        limit = 0.5 if "moe" in path and "shared" not in path else 8e-2
        assert rel(REF._leaf(grads, path), REF._leaf(ref_grads, path)) <= \
            limit, path


def test_the_control_reads_above_the_system_through_compare():
    """The cell built at its toy size as ``benchmark/run.py`` builds it, on
    one device: ``compare`` on the system and on ``reference.control`` (the
    reference's own loss and logits in bfloat16). The control is compared by
    the same code and reads above the system on the logits and on every
    gradient that rounding sets (at the published widths it must come out
    not correct; here every tolerance is ``OTHER_WIDTH_FACTOR`` times
    wider)."""
    from apex_tpu import parallel
    run = _load("benchmark_run", ROOT / "benchmark" / "run.py")
    traffic = run.with_toy(json.loads(
        (ROOT / "benchmark" / "traffic" / "lm_s8192_b1_v16k.json")
        .read_text()))
    mesh = parallel.data_parallel_mesh(jax.devices()[:1])
    key = run.seed_key(2654435761)
    pool = run.make_pool(traffic, TOY, key, mesh, 1)
    built = BUILD.build(TOY, key, mesh, pool[0])
    system = REF.compare(TOY, built, built["carry"], pool[0])
    control = REF.compare(TOY, REF.control(built, TOY), built["carry"],
                          pool[0])
    assert system["ok"] and system["tolerances_times"] == 2.0
    for name in ("logit_rel_diff", "logit_row_rel_diff", "rel_diff"):
        assert control[name] > system[name], name
    for leaf, got in control["grad_rel_diff"].items():
        if "moe/router" not in leaf and "moe/experts" not in leaf:
            assert got > system["grad_rel_diff"][leaf], leaf


def test_the_step_takes_the_warm_up_s_learning_rate():
    """DeepSeek-V3's recipe: the rate rises from 0 to 2.2e-4 over 2000 steps
    and stays there. The cell's step, built as ``benchmark/run.py`` builds it
    at the toy size on one device, takes it: Adam's first step moves each
    weight by the rate times the sign of its gradient plus the decay, so by
    ``2.2e-4 / 2000`` and no more."""
    from apex_tpu import parallel
    rate = BUILD.learning_rate
    assert float(rate(jnp.int32(1))) == pytest.approx(1.1e-7)
    assert float(rate(jnp.int32(1000))) == pytest.approx(1.1e-4)
    assert float(rate(jnp.int32(2000))) == float(rate(jnp.int32(9000))) \
        == pytest.approx(2.2e-4)
    run = _load("benchmark_run", ROOT / "benchmark" / "run.py")
    traffic = run.with_toy(json.loads(
        (ROOT / "benchmark" / "traffic" / "lm_s8192_b1_v16k.json")
        .read_text()))
    mesh = parallel.data_parallel_mesh(jax.devices()[:1])
    key = run.seed_key(3141592653)
    pool = run.make_pool(traffic, TOY, key, mesh, 1)
    built = BUILD.build(TOY, key, mesh, pool[0])
    before = jax.device_get(built["params"](built["carry"]))
    carry, _, finite = built["step"](built["carry"], *pool[0])
    assert bool(finite) and built["steps_taken"](carry) == 1
    after = jax.device_get(built["params"](carry))
    moved = [np.abs(a - b).max() / (1.1e-7 * (1 + 0.1 * np.abs(b).max()))
             for a, b in zip(jax.tree_util.tree_leaves(after),
                             jax.tree_util.tree_leaves(before))
             if b.ndim > 1]
    assert max(moved) <= 1.01 and max(moved) >= 0.9


def test_reference_imports_nothing_of_the_library():
    text = (ROOT / "benchmark" / "reference" / "kanana2.py").read_text()
    code = text.split('"""', 2)[2]
    assert "apex_tpu" not in code and "import ops" not in code
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in code


def test_both_latent_attentions_are_one_module():
    """Kimi-Linear's MLA (no positions) and DeepSeek-V3's (interleaved
    rotary) are ``models/mla.py``'s one module; the shell and the ops hold
    neither model's name."""
    from apex_tpu.models import decoder
    kimi = models.kimi_linear_from_config(
        json.loads((ROOT / "benchmark" / "configs" / "kimi_linear.json")
                   .read_text()))
    kimi_mla = kimi.dims.mixer("mla")
    mine = models.deepseek_v3_from_config(FULL).dims.mixer("mla")
    assert type(kimi_mla) is type(mine) is mla.LatentAttention
    assert kimi_linear.LatentAttention is mla.LatentAttention
    assert kimi_mla.rope_theta is None and mine.rope_theta == 1e6
    assert deepseek_v3.ExpertFFN is decoder.ExpertFFN
    assert issubclass(models.DeepseekV3, decoder.Decoder)
    for path in [ROOT / "apex_tpu" / "models" / "decoder.py",
                 ROOT / "apex_tpu" / "models" / "mla.py",
                 *sorted((ROOT / "apex_tpu" / "ops").glob("*.py"))]:
        code = path.read_text().lower().split('"""', 2)[2]
        for name in ("kanana", "kakao"):
            assert name not in code, (path.name, name)


def test_the_first_loss_is_ln_v_plus_the_logits_spread():
    """The traffic's first-loss check against ln(16 032) = 9.682: logits of
    variance 0.02^2 * 2048 at initialisation add about half that variance,
    0.41, 4.2% of ln V, inside ``first_rel_tol`` 0.1."""
    traffic = json.loads((ROOT / "benchmark" / "traffic" /
                          "lm_s8192_b1_v16k.json").read_text())
    assert traffic["arrays"][0]["high"] == "vocab_size"
    ln_v = math.log(FULL["vocab_size"])
    added = 0.02 ** 2 * FULL["hidden_size"] / 2
    assert ln_v == pytest.approx(9.682, abs=1e-3)
    assert added / ln_v == pytest.approx(0.042, abs=1e-3)
    assert added / ln_v < traffic["loss_band"]["first_rel_tol"] / 2
