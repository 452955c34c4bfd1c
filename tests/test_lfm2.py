"""``models/lfm2.py`` against the plain reference the benchmark keeps
(``benchmark/reference/lfm2_moe.py``: float32 ``jax.numpy``, the gated short
convolution as a sum over its taps, dense masked attention over repeated k/v
heads with the q/k norms before a rotary over the whole head, a loop over
the held experts, no shared expert, the embedding as the head), at the
configuration's toy size."""

import dataclasses
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp, models
from apex_tpu.ops import moe

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference_lfm2_moe",
            ROOT / "benchmark" / "reference" / "lfm2_moe.py")
FULL = json.loads((ROOT / "benchmark" / "configs" / "lfm2_moe.json")
                  .read_text())
TOY = {**FULL, **FULL["toy"]}
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
    model = models.lfm2_moe_from_config(TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0,
                                TOY["vocab_size"])
    params = stirred(model.init(jax.random.PRNGKey(0), tokens)["params"])
    return model, params, tokens


def reference_loss(params, tokens):
    return sum(REF.lm_loss(params, t, TOY) for t in tokens) / len(tokens)


def rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / jnp.linalg.norm(b))


def test_layer_kinds_are_read_from_layer_types():
    """The cut's five layers, and the published forty: a gated short
    convolution where the file says ``conv``, attention where it says
    ``full_attention``, a dense FFN in the leading ``num_dense_layers``."""
    assert models.lfm2_moe_from_config(FULL).layer_kinds == (
        ("lconv", "dense"), ("gqa", "moe"), ("lconv", "moe"),
        ("lconv", "moe"), ("lconv", "moe"))
    period = ["conv", "conv", "full_attention", "conv"]
    published = {**FULL, "num_hidden_layers": 40, "num_dense_layers": 2,
                 "layer_types": period * 10, "num_experts": 64,
                 "held_experts": list(range(64))}
    kinds = models.lfm2_moe_from_config(published).layer_kinds
    assert [m for m, _ in kinds].count("gqa") == 10 and kinds[2][0] == "gqa"
    assert [f for _, f in kinds] == ["dense"] * 2 + ["moe"] * 38
    # the cut is the published list's entries 1 to 5
    assert FULL["layer_types"] == (period * 10)[1:6]
    dims = models.lfm2_moe_from_config(FULL).dims
    assert (dims.head_dim, dims.tied_head, dims.shared_width,
            dims.rope_theta, dims.n_routed, dims.top_k, dims.held) == (
        64, True, 0, 1e6, 64, 4, tuple(range(8)))
    for wrong in ({"layer_types": ["conv"] * 4}, {"use_expert_bias": False},
                  {"layer_types": ["conv", "mamba", "conv", "conv", "conv"]}):
        with pytest.raises(ValueError):
            models.lfm2_moe_from_config({**FULL, **wrong})


def test_parameter_count_at_the_published_widths():
    """469.3 M parameters = 7.51 GB at 16 B, no ``lm_head`` among them."""
    model = models.lfm2_moe_from_config(FULL)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert "lm_head" not in shapes and "shared" not in shapes["layers_2"][
        "moe"]
    assert count(shapes["embed"]) == 8192 * 2048
    assert count(shapes["layers_0"]) == (
        2048 * 6144 + 3 * 2048 + 2048 * 2048 + 3 * 2048 * 11776 + 2 * 2048)
    assert count(shapes["layers_1"]["gqa"]) == (
        2048 * (2048 + 512 + 512) + 2048 * 2048 + 2 * 64)
    assert count(shapes["layers_2"]["moe"]) == (
        2048 * 64 + 64 + 8 * 3 * 2048 * 1536)
    assert count(shapes) == 469_285_248


def test_gated_short_conv_against_the_reference(toy):
    model, params, tokens = toy
    p = params["layers_0"]["lconv"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, LENGTH, 64))
    got = model.dims.mixer("lconv").apply({"params": p}, x)[0]
    top = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(got - REF.gated_short_conv(x[0], p)))) <= (
        2e-5 * max(top, 1.0))
    for probe in ("four_taps", "taps_reversed", "swap_b_c"):
        assert float(jnp.max(jnp.abs(got - REF.gated_short_conv(
            x[0], p, **{probe: True})))) > 1e-2 * top, probe


def test_attention_against_the_reference(toy):
    model, params, tokens = toy
    p = params["layers_1"]["gqa"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, LENGTH, 64))
    got = model.dims.mixer("gqa").apply({"params": p}, x)[0]
    top = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(got - REF.attention(x[0], p, TOY)))) <= (
        2e-5 * max(top, 1.0))
    heads = {**TOY, "num_attention_heads": 4, "num_key_value_heads": 2}
    wide = models.lfm2_moe_from_config(heads).dims.mixer("gqa")
    q = wide.init(jax.random.PRNGKey(5), x)["params"]
    q = stirred(q, gain=10)
    got4 = wide.apply({"params": q}, x)[0]
    assert float(jnp.max(jnp.abs(got4 - REF.attention(x[0], q, heads)))) <= (
        2e-5 * max(float(jnp.max(jnp.abs(got4))), 1.0))
    for probe in ("norm_after_rotary", "half_rotary", "kv_head_mod"):
        assert float(jnp.max(jnp.abs(got4 - REF.attention(
            x[0], q, heads, **{probe: True})))) > 1e-3 * float(
                jnp.max(jnp.abs(got4))), probe


def test_float32_model_equals_the_reference(toy):
    """No policy (O0): loss, logits and every gradient leaf, tightly."""
    model, params, tokens = toy
    logits, load = model.apply({"params": params}, tokens)
    for seq, got in zip(tokens, logits):
        want = REF.loss_and_logits(params, seq, TOY)[1]
        assert rel(got, want) <= 1e-5
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-4
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
        if "e_bias" in jax.tree_util.keystr(path):      # no gradient moves it
            assert not bool(jnp.any(got)) and not bool(jnp.any(want))
    # the counters: a row for each of the four expert layers
    assert routing["expert_load"].shape == (4, 4)
    assert routing["rows_routed_here"].tolist() == \
        routing["expert_load"].sum(-1).tolist()
    assert load.tolist() == routing["expert_load"].tolist()
    assert 0 < int(routing["rows_routed_here"][0]) < 2 * LENGTH * 2
    # what the grouped matmul ran on: the rows routed here and the tiles'
    # rounding, a tile more for each expert at most
    run, here = routing["expert_rows_run"], routing["rows_routed_here"]
    tile = moe.row_tile(tokens.size * model.dims.top_k)
    assert run.shape == (4,) and run.dtype == jnp.int32
    assert bool(jnp.all(run >= here)) and bool(jnp.all(run % tile == 0))
    assert bool(jnp.all(run <= -(-here // tile) * tile
                        + tile * (len(model.dims.held) - 1)))


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(toy):
    """One parameter, read by the lookup and by the head: its gradient is
    the embedding's plus the transposed head's of the same model with a head
    of its own that holds the same numbers."""
    model, params, tokens = toy
    assert "lm_head" not in params
    untied = models.Lfm2Moe(dataclasses.replace(model.dims, tied_head=False),
                            model.layer_kinds)
    both = {**params, "lm_head": params["embed"]["embedding"].T}
    loss = lambda m: lambda p: models.lm_loss(m, {"params": p}, tokens)[0]
    (one, g), (two, h) = (jax.value_and_grad(loss(m))(p)
                          for m, p in ((model, params), (untied, both)))
    assert float(abs(one - two)) <= 1e-6 * float(two)
    lookup, head = h["embed"]["embedding"], h["lm_head"].T
    assert float(jnp.linalg.norm(lookup)) > 1e-3 < float(
        jnp.linalg.norm(head))
    assert rel(g["embed"]["embedding"], lookup + head) <= 1e-5
    assert rel(g["layers_0"]["lconv"]["conv"],
               h["layers_0"]["lconv"]["conv"]) <= 1e-5


def test_the_eight_shares_of_an_expert_layer_add_up():
    """EP8 at a small size: the layer as LFM2 builds it (sigmoid scores over
    64, 4 chosen, no shared expert), eight shares of 8
    experts each over one router: their sum is the uncut reference's whole
    layer, nothing counted twice."""
    sizes = {**TOY, "num_experts_per_tok": 4, "router_experts": 64}
    dims = models.lfm2_moe_from_config(
        {**sizes, "num_experts": 64, "held_experts": list(range(64))}).dims
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 64))
    p = dims.experts().init(jax.random.PRNGKey(1), x)["params"]
    p = {**p, "router": p["router"] * 30,
         **{n: p[n] * 5 for n in ("experts_gate", "experts_up",
                                  "experts_down")}}
    assert set(p) == {"router", "e_bias", "experts_gate", "experts_up",
                      "experts_down"}
    whole = REF.experts(x.reshape(-1, 64), p, sizes, tuple(range(64)))
    top = float(jnp.max(jnp.abs(whole)))
    total, rows = 0.0, 0
    for rank in range(8):
        held = tuple(range(8 * rank, 8 * rank + 8))
        mine = {**p, **{n: p[n][8 * rank:8 * rank + 8] for n in (
            "experts_gate", "experts_up", "experts_down")}}
        part, load = dataclasses.replace(dims, held=held).experts().apply(
            {"params": mine}, x)
        want = REF.experts(x.reshape(-1, 64), mine, sizes, held)
        assert float(jnp.max(jnp.abs(part.reshape(-1, 64) - want))) <= (
            1e-5 * top)
        assert float(jnp.max(jnp.abs(part))) > 1e-2 * top
        total, rows = total + part, rows + int(load.sum())
    assert rows == 2 * 40 * 4           # every assignment on one rank
    assert float(jnp.max(jnp.abs(total.reshape(-1, 64) - whole))) <= (
        1e-5 * top)


def test_remat_changes_nothing(toy):
    model, params, tokens = toy
    again = models.lfm2_moe_from_config(TOY, remat=True)
    run = lambda m: jax.value_and_grad(
        lambda p: models.lm_loss(m, {"params": p}, tokens)[0])(params)
    (loss, a), (loss_again, b) = run(model), run(again)
    assert float(abs(loss - loss_again)) <= 1e-6 * float(loss)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-6


def test_kernels_in_the_lowered_step(monkeypatch):
    """The toy's 64 channels take the ``jax.numpy`` convolution: at 128, a
    whole lane tile, the mixer's middle is the two convolution kernels and
    nothing else. Five layers, every block recomputed: lowered for the TPU
    the differentiated loss holds the gated forward twice a conv layer
    (forward and rerun) and its backward once, attention's forward once, no
    shared expert and no head matrix."""
    from apex_tpu.ops import _dispatch, attention
    config = {**TOY, "hidden_size": 128}
    model = models.lfm2_moe_from_config(config, remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH), 0,
                                config["vocab_size"])
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
    assert kernels["apex_short_conv_fwd"] == 8
    assert kernels["apex_short_conv_bwd"] == 4
    assert kernels["apex_attn_fwd"] == 1
    assert sum(n for k, n in kernels.items()
               if k.startswith("apex_attn_bwd")) in (1, 2)
    for scope in ("lconv/proj", "lconv/conv", "lconv/out", "gqa/proj",
                  "gqa/rope", "gqa/attn", "gqa/out", "moe/route",
                  "moe/dispatch", "moe/experts", "moe/combine", "lm/head"):
        assert scope in text, scope
    assert "moe/shared" not in text and "lm_head" not in text
    # nothing but the kernel between the two projections: the GEMM's
    # bfloat16 goes in as it is and bfloat16 comes out
    mixer = model.dims.mixer("lconv")
    x = jnp.ones((1, LENGTH, 128), jnp.bfloat16)

    def mix(p, x):
        with amp.auto_cast(policy):
            return mixer.apply({"params": p}, x)

    with monkeypatch.context() as m:
        m.setattr(_dispatch, "use_interpret", lambda: False)
        eqns = jax.make_jaxpr(mix)(params["layers_0"]["lconv"], x).eqns
    names = [e.primitive.name for e in eqns
             if e.primitive.name != "convert_element_type"]
    assert names == ["dot_general", "custom_vjp_call", "dot_general"], names


def test_o1_model_is_near_the_reference(toy):
    """Under ``auto_cast`` the matmuls run in bfloat16 with float32
    accumulation; the convolution with its gates, the rotation, the router
    and the norms stay float32. On matrices at their initial spread."""
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
        limit = 0.5 if "moe" in path else 8e-2
        assert rel(REF._leaf(grads, path), REF._leaf(ref_grads, path)) <= \
            limit, path


def test_reference_imports_nothing_of_the_library():
    text = (ROOT / "benchmark" / "reference" / "lfm2_moe.py").read_text()
    code = text.split('"""', 2)[2]
    assert "apex_tpu" not in code and "import ops" not in code
    assert 'default_matmul_precision("highest")' in text
    assert "pallas" not in code and "capacity" not in code


def test_the_shell_and_the_ops_name_no_model():
    """The third decoder is ``models/decoder.py``'s shell over its own
    ``dims``: block, expert layer, norm, rotary and loss exist once, and
    neither the shell nor ``ops/`` holds a model's name."""
    from apex_tpu.models import decoder, lfm2, qwen3_next
    assert lfm2.ExpertFFN is decoder.ExpertFFN
    assert lfm2.partial_rotary is qwen3_next.partial_rotary is \
        decoder.partial_rotary is models.partial_rotary
    assert not hasattr(lfm2, "Block") and not hasattr(lfm2, "SwiGLU")
    assert issubclass(models.Lfm2Moe, decoder.Decoder)
    sources = [ROOT / "apex_tpu" / "models" / "decoder.py",
               *sorted((ROOT / "apex_tpu" / "ops").glob("*.py"))]
    for path in sources:
        code = path.read_text().lower()
        if path.name == "decoder.py":       # its docstring lists the files
            code = code.split('"""', 2)[2]
        for name in ("lfm2", "liquid"):
            assert name not in code, (path.name, name)
