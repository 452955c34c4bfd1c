"""``models/laguna.py`` against the plain reference the benchmark keeps
(``benchmark/reference/laguna_s.py``: float32 ``jax.numpy``, dense masked
attention in blocks over repeated k/v heads with its own YaRN, a sigmoid
gate a head, a loop over the held experts beside the shared one, an untied
head), at the configuration's toy size; and the configuration's own numbers
at the published widths."""

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
from apex_tpu.models import laguna

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference_laguna_s",
            ROOT / "benchmark" / "reference" / "laguna_s.py")
BUILD = _load("configs_laguna_s", ROOT / "benchmark" / "configs" /
              "laguna_s.py")
FULL = json.loads((ROOT / "benchmark" / "configs" / "laguna_s.json")
                  .read_text())
TOY = {**FULL, **FULL["toy"]}
LENGTH = 150            # no whole number of attention tiles
#: the published period: one global layer, three window layers
PERIOD = ["full_attention"] + ["sliding_attention"] * 3


def stirred(params, seed=7, gain=3):
    """Seeded weights that no part of the model is blind to: the norm
    scales off their constants, the matrices ``gain`` times their initial
    spread."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) if x.ndim == 1 else gain * x
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def toy():
    model = models.laguna_from_config(TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0,
                                TOY["vocab_size"])
    params = stirred(model.init(jax.random.PRNGKey(0), tokens)["params"])
    return model, params, tokens


def reference_loss(params, tokens, sizes=TOY):
    return sum(REF.lm_loss(params, t, sizes) for t in tokens) / len(tokens)


def rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / jnp.linalg.norm(b))


def test_layer_kinds_and_heads_are_read_from_the_file():
    """The cut's five layers, and the published 48: a window layer where
    ``layer_types`` says ``sliding_attention``, a global one where it says
    ``full_attention``, each with its entry of
    ``num_attention_heads_per_layer``; a dense FFN where ``mlp_layer_types``
    says ``dense``."""
    assert models.laguna_from_config(FULL).layer_kinds == (
        (("fullattn", 48), "dense"), (("swa", 72), "moe"),
        (("swa", 72), "moe"), (("swa", 72), "moe"),
        (("fullattn", 48), "moe"))
    published = {**FULL, "num_hidden_layers": 48,
                 "layer_types": PERIOD * 12,
                 "mlp_layer_types": ["dense"] + ["sparse"] * 47,
                 "gating_types": ["per_head"] * 48,
                 "num_attention_heads_per_layer": [48, 72, 72, 72] * 12}
    kinds = models.laguna_from_config(published).layer_kinds
    assert [m for m, _ in kinds].count(("fullattn", 48)) == 12
    assert [m for m, _ in kinds].count(("swa", 72)) == 36
    assert [f for _, f in kinds] == ["dense"] + ["moe"] * 47
    # the cut is the published lists' entries 0 to 4
    for key in ("layer_types", "mlp_layer_types", "gating_types",
                "num_attention_heads_per_layer"):
        assert FULL[key] == published[key][:5], key
    assert FULL["mlp_only_layers"] == [0]
    dims = models.laguna_from_config(FULL).dims
    rotary = dict(dims.rotary)
    assert (rotary["swa"].channels, rotary["swa"].theta,
            rotary["swa"].inv_freq, rotary["swa"].scale) == (
        128, 1e4, None, 1.0)
    assert (rotary["fullattn"].channels, rotary["fullattn"].theta) == (
        64, 5e5)
    assert rotary["fullattn"].scale == pytest.approx(1.4852030263919618)
    assert (dims.window, dims.kv_heads, dims.head_dim, dims.n_routed,
            dims.top_k, dims.held, dims.routed_scale, dims.shared_width) == (
        512, 8, 128, 256, 10, tuple(range(8)), 2.5, 1024)
    for wrong in ({"layer_types": PERIOD}, {"norm_topk_prob": False},
                  {"layer_types": ["mamba"] + PERIOD},
                  {"mlp_layer_types": ["dense"] * 4 + ["hybrid"]},
                  {"gating_types": ["per_head"] * 4 + ["none"]},
                  {"tie_word_embeddings": True},
                  {"moe_router_logit_softcapping": 30}):
        with pytest.raises(ValueError):
            models.laguna_from_config({**FULL, **wrong})


def test_yarn_frequencies_of_the_global_layers():
    """64 rotated channels at theta 5e5, factor 128 over an original 8192,
    beta_fast 32 and beta_slow 1: the correction range is pairs 9 (floor of
    9.04) to 18 (ceil of 17.49); pairs below it keep theta's frequency,
    pairs from 18 on take it over 128, and the ramp is linear between; cos
    and sin are multiplied by 0.1 ln 128 + 1 = 1.4852."""
    rope = FULL["rope_parameters"]["full_attention"]
    end = lambda beta: 64 * math.log(8192 / (2 * math.pi * beta)) / (
        2 * math.log(5e5))
    assert (end(32), end(1)) == (pytest.approx(9.04, abs=5e-3),
                                 pytest.approx(17.49, abs=5e-3))
    inv_freq, scale = models.yarn_frequencies(rope, 64)
    plain = 5e5 ** -(np.arange(0, 64, 2) / 64)
    ratio = inv_freq / plain
    np.testing.assert_allclose(ratio[:10], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[18:], 1 / 128, rtol=1e-6)
    ramp = (np.arange(10, 18) - 9) / 9
    np.testing.assert_allclose(ratio[10:18], 1 - ramp + ramp / 128,
                               rtol=1e-6)
    assert scale == pytest.approx(0.1 * math.log(128) + 1, rel=1e-9)
    # without its attention_factor the entry gives YaRN's own
    assert models.yarn_frequencies(
        {**rope, "attention_factor": None}, 64)[1] == pytest.approx(scale)
    # the reference's own YaRN, written apart, agrees
    ref_freq, ref_scale = REF.yarn(rope, 64)
    np.testing.assert_allclose(inv_freq, ref_freq, rtol=1e-6)
    assert ref_scale == scale


def test_parameter_count_at_the_published_widths():
    """811.02 M parameters = 12.98 GB at 16 B: a global layer 44.19 M with
    its gate, a window layer 63.14 M, an expert 9.437 M, the shared one the
    same, the router 0.786 M, the dense FFN 113.25 M, an eighth of the
    embedding and of the untied head 77.07 M, eleven norms."""
    model = models.laguna_from_config(FULL)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    d = 3072
    glob = d * (48 + 16) * 128 + 48 * 128 * d + d * 48
    window = d * (72 + 16) * 128 + 72 * 128 * d + d * 72
    expert, router = 3 * d * 1024, d * 256
    assert count(shapes["layers_0"]["fullattn"]) == glob == 44_187_648
    assert count(shapes["layers_1"]["swa"]) == window == 63_135_744
    assert count(shapes["layers_0"]["mlp"]) == 3 * d * 12288
    assert count(shapes["layers_1"]["moe"]) == router + 9 * expert
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == d * 12544
    assert set(shapes["layers_2"]["swa"]) == {"q_proj", "k_proj", "v_proj",
                                              "g_proj", "o_proj"}
    norms = 11 * d
    assert count(shapes) == (2 * glob + 3 * window + 3 * d * 12288
                             + 4 * (router + 9 * expert) + 2 * d * 12544
                             + norms) == 811_017_216


def test_the_flops_count_the_band():
    """``configs/laguna_s.py`` counts the window layers' band, 1 966 336
    (query, key) pairs at 4096 tokens, 23.4% of causal attention's
    8 390 656, and nothing recomputed."""
    assert sum(min(t + 1, 512) for t in range(4096)) == 1_966_336
    assert sum(t + 1 for t in range(4096)) == 8_390_656
    short = {**FULL, "layer_types": ["full_attention"] * 5}
    assert BUILD.flops_per_sequence(FULL, 300) == pytest.approx(
        BUILD.flops_per_sequence(short, 300))
    flops = BUILD.flops_per_sequence(FULL, 4096)
    attention = 12 * 128 * (2 * 48 * 8_390_656 + 3 * 72 * 1_966_336)
    assert 13.6e12 < flops < 13.9e12
    causal = BUILD.flops_per_sequence(
        {**FULL, "layer_types": ["full_attention"] * 5}, 4096)
    assert causal - flops == pytest.approx(
        12 * 128 * 3 * 72 * (8_390_656 - 1_966_336))
    assert flops - attention == pytest.approx(6 * 4096 * (
        2 * 44_187_648 + 3 * 63_135_744 + 3 * 3072 * 12288
        + 4 * (3072 * 256 + 3072 * 3 * 1024 + 10 * 8 / 256 * 3 * 3072 * 1024)
        + 3072 * 12544))


@pytest.mark.parametrize("layer,kind", [(0, "fullattn"), (1, "swa")])
def test_attention_against_the_reference(toy, layer, kind):
    """Each kind at the toy size (window 32 over 150 tokens), and each
    probe of the reference moves it: the window ignored, YaRN's frequencies
    or its factor left off, the gate left off."""
    model, params, tokens = toy
    p = params[f"layers_{layer}"][kind]
    heads = TOY["num_attention_heads_per_layer"][layer]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, LENGTH, 64))
    got = model.dims.mixer((kind, heads)).apply({"params": p}, x)[0]
    top = float(jnp.max(jnp.abs(got)))
    name = TOY["layer_types"][layer]
    with jax.default_matmul_precision("highest"):
        want = REF.attention(x[0], p, TOY, name, heads)
    assert float(jnp.max(jnp.abs(got - want))) <= 2e-5 * max(top, 1.0)
    probes = (("no_window",) if kind == "swa" else
              ("plain_yarn", "unscaled_yarn")) + ("ungated",)
    for probe in probes:
        other = REF.attention(x[0], p, TOY, name, heads, **{probe: True})
        assert float(jnp.max(jnp.abs(got - other))) > 1e-3 * top, probe


def test_float32_model_equals_the_reference(toy):
    """No policy (O0): loss, logits and every gradient leaf, tightly."""
    model, params, tokens = toy
    logits, load = model.apply({"params": params}, tokens)
    for seq, got in zip(tokens, logits):
        want = REF.loss_and_logits(params, seq, TOY)[1]
        assert rel(got, want) <= 1e-5
    loss_fn = lambda p: models.lm_loss(model, {"params": p}, tokens)
    (loss, routing), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(params, tokens)
    assert float(abs(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert {jax.tree_util.keystr(path) for path, _ in flat} >= {
        "".join(f"['{k}']" for k in leaf) for leaf in REF.GRAD_LEAVES}
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(got - want)) <= 2e-3 * max(
            float(jnp.linalg.norm(want)), 1e-3), jax.tree_util.keystr(path)
    # a row for each of the four expert layers
    assert routing["expert_load"].shape == (4, 4)
    assert load.tolist() == routing["expert_load"].tolist()


def test_the_band_in_several_tiles_equals_the_reference(toy, monkeypatch):
    """The op picks the window layers' tiles: 512 x 512 at the cell's window,
    128 x 128 at the toy's 32, so the toy's 150 tokens are two tiles a side;
    at 64 x 128 the kernels run a band over a grid of 3 x 2 tiles (the skip
    and the clamped fetches)."""
    from apex_tpu.ops import attention
    model, params, tokens = toy
    defaults = (attention.DEFAULT_BLOCK_Q, attention.DEFAULT_BLOCK_K)
    assert attention._window_blocks(FULL["sliding_window"], *defaults) == (
        512, 512)
    assert attention._window_blocks(TOY["sliding_window"], *defaults) == (
        128, 128)
    loss = lambda m: jax.value_and_grad(
        lambda p: models.lm_loss(m, {"params": p}, tokens)[0])(params)
    want, h = loss(model)
    monkeypatch.setattr(attention, "_window_blocks", lambda w, bq, bk: (
        (64, 128) if w else (bq, bk)))
    got, g = loss(models.laguna_from_config(TOY))
    assert float(abs(got - want)) <= 1e-6 * float(want)
    for x, y in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(h)):
        assert float(jnp.linalg.norm(x - y)) <= 1e-4 * max(
            float(jnp.linalg.norm(y)), 1e-3)


def test_the_eight_shares_of_an_expert_layer_add_up():
    """EP8 at a small size: the layer as Laguna builds it (softmax over 64,
    10 chosen, weights renormalised and times 2.5, an ungated shared
    expert), eight shares of 8 experts each over one router: the shares'
    routed parts and the shared expert, which every share computes alike,
    counted once, add up to the uncut reference's whole layer."""
    sizes = {**TOY, "num_experts_per_tok": 10, "router_experts": 64}
    dims = models.laguna_from_config(
        {**sizes, "num_experts": 64, "held_experts": list(range(64))}).dims
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    p = dims.experts().init(jax.random.PRNGKey(1), x)["params"]
    p = {**p, "router": p["router"] * 30,
         **{n: p[n] * 5 for n in ("experts_gate", "experts_up",
                                  "experts_down")}}
    assert set(p) == {"router", "experts_gate", "experts_up",
                      "experts_down", "shared"}
    rows = x.reshape(-1, 64)
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
    assert count == 2 * 40 * 10         # every assignment on one rank
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * top


def test_remat_changes_nothing(toy):
    model, params, tokens = toy
    again = models.laguna_from_config(TOY, remat=True)
    run = lambda m: jax.value_and_grad(
        lambda p: models.lm_loss(m, {"params": p}, tokens)[0])(params)
    (loss, a), (loss_again, b) = run(model), run(again)
    assert float(abs(loss - loss_again)) <= 1e-6 * float(loss)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-6


def test_kernels_in_the_lowered_step(monkeypatch):
    """Five layers, every block recomputed, lowered for the TPU: the
    differentiated loss holds attention's forward kernel once a layer (the
    rerun keeps its ``o`` and ``lse``), the two-kernel backward in each, and
    every scope a reader cuts by."""
    from apex_tpu.ops import _dispatch, attention
    monkeypatch.setattr(attention, "_window_blocks", lambda w, bq, bk: (
        (64, 128) if w else (bq, bk)))
    model = models.laguna_from_config(TOY, remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH), 0,
                                TOY["vocab_size"])
    params = model.init(jax.random.PRNGKey(3), tokens)["params"]
    policy = amp.Policy.from_opt_level("O1")

    def loss(p):
        with amp.auto_cast(policy):
            return models.lm_loss(model, {"params": p}, tokens)[0]

    step = jax.jit(jax.value_and_grad(loss))
    with monkeypatch.context() as m:
        for mod in (_dispatch, attention):
            m.setattr(mod, "use_interpret", lambda: False)
        text = step.trace(params).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    kernels = _dispatch.kernel_calls(text)
    assert kernels["apex_attn_fwd"] == 5
    # the window layers' several tiles take the two-kernel backward, the
    # global layers' single tile the fused one
    assert kernels["apex_attn_bwd_dq"] == kernels["apex_attn_bwd_dkv"] == 3
    assert kernels["apex_attn_bwd"] == 2
    for scope in ("swa/proj", "swa/rope", "swa/attn", "swa/out",
                  "fullattn/proj", "fullattn/rope", "fullattn/attn",
                  "fullattn/out", "moe/route", "moe/dispatch", "moe/experts",
                  "moe/combine", "moe/shared", "lm/head"):
        assert scope in text, scope


def test_o1_model_is_near_the_reference(toy):
    """Under ``auto_cast`` the matmuls run in bfloat16 with float32
    accumulation; the rotation, the gates, the router and the norms stay
    float32. On matrices at their initial spread."""
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
    want = jnp.stack([REF.loss_and_logits(params, t, TOY)[1] for t in tokens])
    assert rel(logits, want) <= 3e-2
    loss, grads = jax.value_and_grad(loss_fn)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(params, tokens)
    assert float(abs(loss - ref_loss)) <= 2e-3 * float(ref_loss)
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))
    for path in REF.GRAD_LEAVES:
        limit = 0.5 if "moe" in path and "shared" not in path else 8e-2
        assert rel(REF._leaf(grads, path), REF._leaf(ref_grads, path)) <= \
            limit, path


def test_the_control_reads_above_the_system_through_compare():
    """The cell built at its toy size as ``benchmark/run.py`` builds it, on
    one device: ``compare`` on the system and on ``reference.control`` (the
    reference's own loss and logits in bfloat16). The control is compared by
    the same code and reads above the system on the loss, the logits' rows
    and every gradient (at the published widths it must come out not
    correct; here every tolerance is ``OTHER_WIDTH_FACTOR`` times wider)."""
    from apex_tpu import parallel
    run = _load("benchmark_run", ROOT / "benchmark" / "run.py")
    traffic = run.with_toy(json.loads(
        (ROOT / "benchmark" / "traffic" / "lm_s4096_b1_v12k.json").read_text()))
    mesh = parallel.data_parallel_mesh(jax.devices()[:1])
    key = run.seed_key(2654435761)
    pool = run.make_pool(traffic, TOY, key, mesh, 1)
    built = BUILD.build(TOY, key, mesh, pool[0])
    system = REF.compare(TOY, built, built["carry"], pool[0])
    control = REF.compare(TOY, REF.control(built, TOY), built["carry"],
                          pool[0])
    assert system["ok"] and system["tolerances_times"] == 2.0
    for name in ("loss_rel_diff", "logit_row_rel_diff", "rel_diff"):
        assert control[name] > system[name], name
    for leaf, got in control["grad_rel_diff"].items():
        assert got > system["grad_rel_diff"][leaf], leaf


def test_reference_imports_nothing_of_the_library():
    text = (ROOT / "benchmark" / "reference" / "laguna_s.py").read_text()
    code = text.split('"""', 2)[2]
    assert "apex_tpu" not in code and "import ops" not in code
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in code


def test_the_shell_and_the_ops_name_no_model():
    """The fourth decoder is ``models/decoder.py``'s shell over its own
    ``dims``: block, expert layer, norm, rotary and loss exist once, and
    neither the shell nor ``ops/`` holds the model's name."""
    from apex_tpu.models import decoder
    assert laguna.ExpertFFN is decoder.ExpertFFN
    assert laguna.partial_rotary is decoder.partial_rotary
    assert not hasattr(laguna, "Block") and not hasattr(laguna, "SwiGLU")
    assert issubclass(models.Laguna, decoder.Decoder)
    sources = [ROOT / "apex_tpu" / "models" / "decoder.py",
               *sorted((ROOT / "apex_tpu" / "ops").glob("*.py"))]
    for path in sources:
        code = path.read_text().lower()
        if path.name == "decoder.py":       # its docstring lists the files
            code = code.split('"""', 2)[2]
        for name in ("laguna", "poolside"):
            assert name not in code, (path.name, name)
