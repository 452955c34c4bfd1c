"""``models/qwen3_next.py`` against the plain reference the benchmark keeps
(``benchmark/reference/qwen3_next.py``: float32 ``jax.numpy``, gated DeltaNet
one step a token, dense masked attention over repeated k/v heads, rotary
written out, a loop over the held experts), at the configuration's toy size."""

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


REF = _load("reference_qwen3_next",
            ROOT / "benchmark" / "reference" / "qwen3_next.py")
FULL = json.loads((ROOT / "benchmark" / "configs" / "qwen3_next.json")
                  .read_text())
TOY = {**FULL, **FULL["toy"]}
LENGTH = 150            # not a whole number of chunks, nor of attention tiles


def stirred(params, seed=7, gain=3):
    """Seeded weights that no part of the model is blind to: the zero-centred
    norm scales off zero, the matrices ``gain`` times their initial spread."""
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) if x.ndim == 1 else gain * x
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def toy():
    model = models.qwen3_next_from_config(TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0,
                                TOY["vocab_size"])
    params = stirred(model.init(jax.random.PRNGKey(0), tokens)["params"])
    return model, params, tokens


def reference_loss(params, tokens):
    return sum(REF.lm_loss(params, t, TOY) for t in tokens) / len(tokens)


def rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / jnp.linalg.norm(b))


def test_layer_kinds_are_read_from_the_configuration():
    model = models.qwen3_next_from_config(FULL)
    assert model.layer_kinds == (("gdn", "moe"),) * 3 + (("gattn", "moe"),)
    d = model.dims
    assert (d.hidden, d.gdn_key_heads, d.gdn_value_heads, d.gdn_key_dim,
            d.gdn_value_dim, d.conv_size) == (2048, 16, 32, 128, 128, 4)
    assert (d.attn_heads, d.kv_heads, d.head_dim, d.rotary_dim,
            d.rope_theta) == (16, 2, 256, 64, 1e7)
    assert (d.expert_width, d.shared_width, d.n_routed, d.top_k, d.eps) == (
        512, 512, 512, 10, 1e-6)
    assert d.held == tuple(range(32))
    whole = models.qwen3_next_from_config(
        {**FULL, "num_hidden_layers": 9, "num_experts": 512,
         "held_experts": list(range(512)), "mlp_only_layers": [1],
         "full_attention_interval": 3})
    assert [k[0] for k in whole.layer_kinds] == ["gdn", "gdn", "gattn"] * 3
    assert [k[1] for k in whole.layer_kinds] == ["moe", "dense"] + ["moe"] * 7
    assert whole.dims.dense_width == 5120
    sparse = models.qwen3_next_from_config({**FULL, "decoder_sparse_step": 2})
    assert [k[1] for k in sparse.layer_kinds] == ["dense", "moe"] * 2
    with pytest.raises(ValueError, match="norm_topk_prob"):
        models.qwen3_next_from_config({**FULL, "norm_topk_prob": False})


def test_parameter_count_at_the_published_widths():
    """625.7 M at this share, as ISSUE 32 reckons them: 16 B a parameter is
    10.0 GB of state."""
    model = models.qwen3_next_from_config(FULL)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64), jnp.int32))["params"])
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["layers_0"]["gdn"]) == pytest.approx(33.72e6, rel=1e-3)
    assert count(shapes["layers_3"]["gattn"]) == pytest.approx(27.26e6,
                                                               rel=1e-3)
    assert count(shapes["layers_1"]["moe"]) == pytest.approx(
        (1.05 + 3.15 + 32 * 3.146) * 1e6, rel=1e-3)
    assert count(shapes["layers_0"]) == pytest.approx(138.6e6, rel=1e-3)
    assert count(shapes["layers_3"]) == pytest.approx(132.1e6, rel=1e-3)
    assert count(shapes) == pytest.approx(625.7e6, rel=1e-3)
    assert "e_bias" not in shapes["layers_0"]["moe"]


@pytest.mark.parametrize("rotary_dim,theta", [(8, 1e4), (64, 1e7), (32, 1e7)])
def test_partial_rotary_against_a_complex_number_form(rotary_dim, theta):
    """Channels ``(m, m + R / 2)`` as one complex number turned by ``exp(i p
    theta^(-2m / R))``; what lies beyond ``R`` passes through to the bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 300, 3, 64))
    got = models.partial_rotary(x, rotary_dim, theta)
    half = rotary_dim // 2
    z = x[..., :half] + 1j * x[..., half:rotary_dim]
    angle = (jnp.arange(300)[:, None]
             * theta ** (-jnp.arange(half) * 2.0 / rotary_dim))
    turned = z * jnp.exp(1j * angle)[None, :, None, :]
    assert float(jnp.max(jnp.abs(got[..., :half] - turned.real))) <= 2e-4
    assert float(jnp.max(jnp.abs(
        got[..., half:rotary_dim] - turned.imag))) <= 2e-4
    assert bool(jnp.all(got[..., rotary_dim:] == x[..., rotary_dim:]))
    assert bool(jnp.all(got[:, 0] == x[:, 0]))          # position 0: no turn
    # a turn keeps each pair's length, and positions are the caller's to give
    assert float(jnp.max(jnp.abs(
        jnp.linalg.norm(got, axis=-1) - jnp.linalg.norm(x, axis=-1)))) <= 1e-4
    late = models.partial_rotary(x[:, :10], rotary_dim, theta,
                                 positions=jnp.arange(290, 300))
    assert float(jnp.max(jnp.abs(
        late[..., :half] - (z[:, :10] * jnp.exp(1j * angle[290:])[
            None, :, None, :]).real))) <= 2e-4
    # float32 under O1 whatever comes in
    with amp.auto_cast(amp.Policy.from_opt_level("O1")):
        assert models.partial_rotary(x.astype(jnp.bfloat16), rotary_dim,
                                     theta).dtype == jnp.float32
    assert amp.lists.classify("rotary") == "float"


def test_gated_attention_against_the_reference(toy):
    """4 q heads on 2 k/v heads of 32, rotary on 8 channels, q/k norms, the
    gate: forward and the gradient of the input; each probe of the reference
    moves it."""
    d = toy[0].dims
    layer = d.mixer("gattn")
    x = jax.random.normal(jax.random.PRNGKey(2), (1, LENGTH, d.hidden))
    p = stirred(layer.init(jax.random.PRNGKey(3), x)["params"], 4)
    # sharper scores than the initialisation's, so that what shapes them shows
    p = {**p, "q_proj": {"kernel": p["q_proj"]["kernel"] * 4}}
    got = layer.apply({"params": p}, x)[0]
    top = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(
        got - REF.gated_attention(x[0], p, TOY)))) <= 2e-5 * max(top, 1.0)
    for probe in ({"scaled": False}, {"kv_head_mod": True},
                  {"over_all": True}, {"interleaved": True}):
        assert float(jnp.max(jnp.abs(
            got - REF.gated_attention(x[0], p, TOY, **probe)))) > 1e-2 * top
    grad = lambda fn: jax.grad(lambda x: jnp.sum(jnp.sin(fn(x))))(x)
    assert float(jnp.max(jnp.abs(
        grad(lambda x: layer.apply({"params": p}, x))
        - grad(lambda x: REF.gated_attention(x[0], p, TOY)[None])))) <= 2e-5


def test_gated_deltanet_against_the_reference(toy):
    """2 key heads on 4 value heads of 16, one decay a value head."""
    d = toy[0].dims
    layer = d.mixer("gdn")
    x = jax.random.normal(jax.random.PRNGKey(5), (1, LENGTH, d.hidden))
    p = stirred(layer.init(jax.random.PRNGKey(6), x)["params"], 8)
    assert p["A_log"].shape == p["dt_bias"].shape == (4,)
    assert p["conv"].shape == (4, 2 * 32 + 64)
    got = layer.apply({"params": p}, x)[0]
    top = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(
        got - REF.gated_deltanet(x[0], p, TOY)))) <= 2e-5 * max(top, 1.0)
    assert float(jnp.max(jnp.abs(
        got - REF.gated_deltanet(x[0], p, TOY, tiled_keys=True)))) > 1e-2 * top


def test_float32_model_equals_the_reference(toy):
    """No policy (O0): loss, logits and every gradient, tightly."""
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


def test_remat_changes_nothing(toy):
    model, params, tokens = toy
    again = models.qwen3_next_from_config(TOY, remat=True)
    run = lambda m: jax.value_and_grad(
        lambda p: models.lm_loss(m, {"params": p}, tokens)[0])(params)
    (loss, a), (loss_again, b) = run(model), run(again)
    assert float(abs(loss - loss_again)) <= 1e-6 * float(loss)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-6


def test_kernels_in_the_lowered_step(monkeypatch):
    """The toy's DeltaNet heads are 16 wide and take the ``jax.numpy`` chunked
    form: at the published head size of 128 the scan is the two kernels of
    one decay a head, which read the decay as it is and each of the 2 key
    heads where it is (no KDA kernel, no broadcast, no repeat). One period
    of four layers, every block recomputed: lowered for the TPU the
    differentiated loss holds each forward kernel once and each backward
    kernel once a layer (three DeltaNet layers, one attention layer on the
    two-kernel or the fused backward), and no triangular solve."""
    from apex_tpu.ops import _dispatch, attention
    config = {**TOY, "linear_key_head_dim": 128, "linear_value_head_dim": 128}
    model = models.qwen3_next_from_config(config, remat=True)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH), 0,
                                config["vocab_size"])
    params = model.init(jax.random.PRNGKey(3), tokens)["params"]
    step = jax.jit(jax.value_and_grad(
        lambda p: models.lm_loss(model, {"params": p}, tokens)[0]))
    with monkeypatch.context() as m:
        for mod in (_dispatch, attention):
            m.setattr(mod, "use_interpret", lambda: False)
        text = step.trace(params).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    kernels = _dispatch.kernel_calls(text)
    assert kernels["apex_gdn_fwd"] == 3
    assert kernels["apex_gdn_bwd"] == 3
    assert kernels["apex_kda_fwd"] == kernels["apex_kda_bwd"] == 0
    # one convolution over q, k and v a layer: forward, rerun, backward
    assert kernels["apex_short_conv_fwd"] == 6
    assert kernels["apex_short_conv_bwd"] == 3
    assert kernels["apex_attn_fwd"] == 1
    assert sum(n for k, n in kernels.items()
               if k.startswith("apex_attn_bwd")) in (1, 2)
    assert "triangular_solve" not in text
    for scope in ("gdn/scan", "gdn/conv", "gattn/rope", "gattn/attn",
                  "moe/route", "moe/shared", "lm/head"):
        assert scope in text, scope


def test_o1_model_is_near_the_reference(toy):
    """Under ``auto_cast`` the matmuls run in bfloat16 with float32
    accumulation; state, decay, rotation, router and norms stay float32. On
    matrices at their initial spread: three times it, and where rounding
    changes a row's choice of experts the logits move by a tenth."""
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
    limits = {("layers_0", "gdn", "qkvz_proj", "kernel"): 8e-2,
              ("layers_0", "gdn", "A_log"): 8e-2,
              ("layers_0", "gdn", "dt_bias"): 8e-2,
              ("layers_3", "gattn", "q_proj", "kernel"): 8e-2,
              ("layers_3", "gattn", "k_norm", "scale"): 8e-2,
              ("layers_1", "moe", "router"): 0.5,
              ("layers_1", "moe", "experts_up"): 0.5,
              ("layers_1", "moe", "shared_gate", "kernel"): 8e-2,
              ("lm_head",): 5e-2}
    assert set(limits) == set(REF.GRAD_LEAVES)
    for path, limit in limits.items():
        assert rel(REF._leaf(grads, path), REF._leaf(ref_grads, path)) <= \
            limit, path


def test_reference_imports_nothing_of_the_library():
    text = (ROOT / "benchmark" / "reference" / "qwen3_next.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan" in text               # the recurrent form


def test_the_decoders_share_one_shell():
    """Block, expert layer, norm and loss exist once: both models' are
    ``models/decoder.py``'s."""
    from apex_tpu.models import decoder, kimi_linear, qwen3_next
    for mod in (kimi_linear, qwen3_next):
        assert mod.ExpertFFN is decoder.ExpertFFN
        assert mod.RMSNorm is decoder.RMSNorm
        assert not hasattr(mod, "Block") and not hasattr(mod, "SwiGLU")
    assert issubclass(models.KimiLinear, decoder.Decoder)
    assert issubclass(models.Qwen3Next, decoder.Decoder)
    assert models.lm_loss is decoder.lm_loss
