"""``models/kimi_linear.py`` against the plain reference the benchmark keeps
(``benchmark/reference/kimi_linear.py``: float32 ``jax.numpy``, KDA one step a
token, dense masked attention, a loop over the held experts), at the
configuration's toy size."""

import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp, models

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("reference_kimi_linear",
            ROOT / "benchmark" / "reference" / "kimi_linear.py")
FULL = json.loads((ROOT / "benchmark" / "configs" / "kimi_linear.json")
                  .read_text())
TOY = {**FULL, **FULL["toy"]}
LENGTH = 150            # not a whole number of chunks, nor of attention tiles


@pytest.fixture(scope="module")
def toy():
    model = models.kimi_linear_from_config(TOY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, LENGTH), 0,
                                TOY["vocab_size"])
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    return model, params, tokens


def reference_loss(params, tokens):
    return sum(REF.lm_loss(params, t, TOY) for t in tokens) / len(tokens)


def test_layer_kinds_are_read_from_the_configuration():
    model = models.kimi_linear_from_config(FULL)
    assert model.layer_kinds == (("kda", "dense"), ("kda", "moe"),
                                 ("kda", "moe"), ("mla", "moe"),
                                 ("kda", "moe"))
    d = model.dims
    assert (d.hidden, d.kda_heads, d.kda_head_dim, d.conv_size) == (
        2304, 32, 128, 4)
    assert (d.kv_rank, d.nope_dim, d.rope_dim, d.v_dim) == (512, 128, 64, 128)
    assert (d.dense_width, d.expert_width, d.n_routed, d.top_k) == (
        9216, 1024, 256, 8)
    assert d.held == tuple(range(8)) and d.route_scale == 2.446
    whole = models.kimi_linear_from_config(
        {**FULL, "num_hidden_layers": 8, "num_experts": 256,
         "router_experts": 256, "held_experts": list(range(256))})
    assert [k[0] for k in whole.layer_kinds] == ["kda"] * 3 + ["mla"] + [
        "kda"] * 3 + ["mla"]
    with pytest.raises(ValueError, match="neither"):
        models.kimi_linear_from_config(
            {**FULL, "num_hidden_layers": 28})


def test_parameter_count_at_the_published_widths():
    """602 M at this share, as ISSUE 28 reckons them: 16 B a parameter is
    9.64 GB of state."""
    model = models.kimi_linear_from_config(FULL)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64), jnp.int32))["params"])
    count = lambda t: sum(x.size for x in jax.tree_util.tree_leaves(t))
    assert count(shapes["layers_0"]["kda"]) == pytest.approx(39.5e6, rel=5e-3)
    assert count(shapes["layers_3"]["mla"]) == pytest.approx(29.1e6, rel=5e-3)
    assert count(shapes["layers_1"]["moe"]) == pytest.approx(
        (9 * 7.08 + 0.59) * 1e6, rel=5e-3)
    assert count(shapes) == pytest.approx(602e6, rel=5e-3)


def test_mla_against_the_reference(toy):
    """d_k 32 beside d_v 16, causal, the shared key part unrotated."""
    d = toy[0].dims
    layer = models.LatentAttention(d.hidden, d.mla_heads, d.kv_rank,
                                   d.nope_dim, d.rope_dim, d.v_dim)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, LENGTH, d.hidden))
    p = layer.init(jax.random.PRNGKey(3), x)["params"]
    # sharper scores than the initialisation's, so that what shapes them shows
    p = {**p, "q_proj": {"kernel": p["q_proj"]["kernel"] * 20}}
    got = layer.apply({"params": p}, x)[0]
    assert float(jnp.max(jnp.abs(got - REF.mla(x[0], p, TOY)))) <= 1e-5
    # the probes move it: a rotated k_pe, a dropped 1/sqrt(d_k)
    for probe in ({"rotate": True}, {"scaled": False}):
        assert float(jnp.max(jnp.abs(
            got - REF.mla(x[0], p, TOY, **probe)))) > 1e-2 * float(
                jnp.max(jnp.abs(got)))
    grad = lambda fn: jax.grad(lambda x: jnp.sum(jnp.sin(fn(x))))(x)
    assert float(jnp.max(jnp.abs(
        grad(lambda x: layer.apply({"params": p}, x))
        - grad(lambda x: REF.mla(x[0], p, TOY)[None])))) <= 1e-5


def test_float32_model_equals_the_reference(toy):
    """No policy (O0): loss, logits and every gradient, tightly."""
    model, params, tokens = toy
    logits, load = model.apply({"params": params}, tokens)
    for seq, got in zip(tokens, logits):
        want = REF.loss_and_logits(params, seq, TOY)[1]
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-5
    loss_fn = lambda p: models.lm_loss(model, {"params": p}, tokens)
    (loss, routing), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(params, tokens)
    assert float(abs(loss - ref_loss)) <= 1e-5 * float(ref_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(got - want)) <= 2e-5 * max(
            float(jnp.linalg.norm(want)), 1e-3), jax.tree_util.keystr(path)
    # the selection bias is outside the gradient
    assert float(jnp.max(jnp.abs(grads["layers_1"]["moe"]["e_bias"]))) == 0.0
    # the counters: a row for each of the four expert layers
    assert routing["expert_load"].shape == (4, 4)
    assert routing["rows_routed_here"].tolist() == \
        routing["expert_load"].sum(-1).tolist()
    assert load.tolist() == routing["expert_load"].tolist()
    assert 0 < int(routing["rows_routed_here"][0]) < 2 * LENGTH * 2


def test_remat_changes_nothing(toy):
    model, params, tokens = toy
    again = models.kimi_linear_from_config(TOY, remat=True)
    grad = lambda m: jax.grad(
        lambda p: models.lm_loss(m, {"params": p}, tokens)[0])(params)
    a, b = grad(model), grad(again)
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-6


def test_kda_kernels_in_the_lowered_step(monkeypatch):
    """The benchmark's toy has KDA heads of 16, which take the ``jax.numpy``
    chunked form: at the published head size of 128 the scan is two Pallas
    kernels. A two-layer decoder with such heads, every
    block recomputed in the backward: lowered for the TPU its differentiated
    loss holds the forward kernel once and the backward kernel once a KDA
    layer (the rerun of a block keeps what the forward kernel wrote,
    ``ops.KEPT_NAMES``, and so holds no kernel) and no triangular solve, and
    its loss and gradients are those of the same model on the chunked form."""
    from apex_tpu.ops import _dispatch, delta_rule
    config = {**TOY, "num_hidden_layers": 2, "linear_attn_config": {
        **TOY["linear_attn_config"], "head_dim": 128}}
    model = models.kimi_linear_from_config(config, remat=True)
    assert [k[0] for k in model.layer_kinds] == ["kda", "kda"]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH), 0,
                                config["vocab_size"])
    params = model.init(jax.random.PRNGKey(3), tokens)["params"]
    # a new function each time: ``jax.jit`` keeps a trace by its function
    step = lambda: jax.jit(jax.value_and_grad(
        lambda p: models.lm_loss(model, {"params": p}, tokens)[0]))
    with monkeypatch.context() as m:
        m.setattr(_dispatch, "use_interpret", lambda: False)
        text = step().trace(params).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    kernels = _dispatch.kernel_calls(text)
    assert kernels["apex_kda_fwd"] == 2
    assert kernels["apex_kda_bwd"] == 2
    # q, k and v of each layer: the convolution's forward in the forward
    # and in the rerun (its output is not kept), its backward once
    assert kernels["apex_short_conv_fwd"] == 12
    assert kernels["apex_short_conv_bwd"] == 6
    assert "triangular_solve" not in text
    loss, grads = step()(params)
    with monkeypatch.context() as m:
        m.setattr(delta_rule, "_tiled", lambda dk, dv: False)
        assert "triangular_solve" in step().lower(params).as_text(
            debug_info=True)
        ref_loss, ref_grads = step()(params)
    assert float(abs(loss - ref_loss)) <= 1e-6 * float(ref_loss)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, got), want in zip(flat, jax.tree_util.tree_leaves(ref_grads)):
        assert float(jnp.linalg.norm(got - want)) <= 2e-5 * max(
            float(jnp.linalg.norm(want)), 1e-3), jax.tree_util.keystr(path)


def _kernel_operands(jaxpr, kernel, made_by=None, found=None):
    """For every ``pallas_call`` named ``kernel`` in ``jaxpr`` (calls inside
    calls walked through), what made each of its operands: the name of the
    ``pallas_call`` whose result it is, untouched, or of whatever primitive
    came between."""
    from jax.extend.core import ClosedJaxpr, Jaxpr, Var
    made_by = {} if made_by is None else made_by
    found = [] if found is None else found
    tag = lambda v: made_by.get(v) if isinstance(v, Var) else None
    for eqn in jaxpr.eqns:
        inner = [p for p in eqn.params.values()
                 if isinstance(p, (Jaxpr, ClosedJaxpr))]
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            if name == kernel:
                found.append([tag(v) for v in eqn.invars])
            made = [name] * len(eqn.outvars)
        elif inner:                     # pjit, custom_vjp_call, checkpoint
            sub = getattr(inner[0], "jaxpr", inner[0])
            inside = dict(zip(sub.invars, map(tag, eqn.invars)))
            _kernel_operands(sub, kernel, inside, found)
            made = [inside.get(v) if isinstance(v, Var) else None
                    for v in sub.outvars]
        else:
            made = [eqn.primitive.name] * len(eqn.outvars)
        made_by.update(zip(eqn.outvars, made))
    return found


def test_the_scan_reads_what_the_convolution_wrote():
    """At the published head size the scan's forward kernel takes q, k and
    v from the convolution kernel as they are: no ``reshape``, ``transpose``
    or anything else between the two calls in the traced layer (each would
    be a copy under the TPU's tiling), and the decay per channel arrives as
    ``(B, T, H d)`` from the gate's own arithmetic."""
    from apex_tpu.models.kimi_linear import KimiDeltaAttention
    layer = KimiDeltaAttention(hidden=64, heads=2, head_dim=128)
    x = jnp.ones((1, 128, 64))
    params = layer.init(jax.random.PRNGKey(0), x)
    traced = jax.make_jaxpr(lambda p, x: layer.apply(p, x))(params, x)
    calls = _kernel_operands(traced.jaxpr, "apex_kda_fwd")
    assert len(calls) == 1
    q, k, v, g = calls[0][:4]
    assert q == k == v == "apex_short_conv_fwd", calls[0]
    assert g == "mul", g


def test_o1_model_is_near_the_reference(toy):
    """Under ``auto_cast`` the matmuls run in bfloat16 (2**-8 relative a
    product) with float32 accumulation, state, decay, router and norms. At
    this width a logit is a sum of 64 such products five layers deep; the
    loss, a mean over 298 positions, averages the errors out. An expert's
    and the router's gradients move more: where rounding changes which
    expert a row's second choice is, a whole row changes sides."""
    model, params, tokens = toy
    policy = amp.Policy.from_opt_level("O1")

    def loss_fn(p):
        with amp.auto_cast(policy):
            return models.lm_loss(model, {"params": p}, tokens)[0]

    with amp.auto_cast(policy):
        logits = model.apply({"params": params}, tokens)[0]
    assert logits.dtype == jnp.bfloat16
    want = jnp.stack([REF.loss_and_logits(params, t, TOY)[1] for t in tokens])
    rel = lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                             / jnp.linalg.norm(b))
    assert rel(logits, want) <= 3e-2
    loss, grads = jax.value_and_grad(loss_fn)(params)
    ref_loss, ref_grads = jax.value_and_grad(reference_loss)(params, tokens)
    assert float(abs(loss - ref_loss)) <= 2e-3 * float(ref_loss)
    assert all(g.dtype == jnp.float32 for g in jax.tree_util.tree_leaves(grads))
    for name in ("lm_head",):
        assert rel(grads[name], ref_grads[name]) <= 5e-2
    kda, ref_kda = grads["layers_0"]["kda"], ref_grads["layers_0"]["kda"]
    assert rel(kda["k_proj"]["kernel"], ref_kda["k_proj"]["kernel"]) <= 8e-2
    assert rel(kda["A_log"], ref_kda["A_log"]) <= 8e-2
    assert rel(grads["layers_1"]["moe"]["experts_up"],
               ref_grads["layers_1"]["moe"]["experts_up"]) <= 0.4


def test_reference_imports_nothing_of_the_library():
    text = (ROOT / "benchmark" / "reference" / "kimi_linear.py").read_text()
    assert "apex_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan" in text               # the recurrent form
