"""What a recomputed block keeps (``ops.KEPT_NAMES``): the forward rules of
``flash_attention`` and ``gated_delta_rule`` name what their forward kernel
wrote and their backward reads, and a checkpoint policy that keeps those
names leaves neither kernel in the rerun of the forward. ``short_conv``
names nothing: its forward kernel is cheap and its output large, so a rerun
holds it (PERF.md, "What a recomputed block still runs twice"). Kernels are
counted in the step lowered for the TPU, as ``test_kimi_linear.py`` counts
them."""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import models, ops
from apex_tpu.ops import _dispatch, attention, delta_rule

ROOT = pathlib.Path(__file__).resolve().parents[1]
FULL = json.loads((ROOT / "benchmark" / "configs" / "kimi_linear.json")
                  .read_text())
#: the cell's five layers (KDA, KDA, KDA, MLA, KDA) at the toy's widths, but
#: with heads the kernels take: KDA's of 128, attention's of 64 + 64
FIVE = {**FULL, **FULL["toy"], "num_hidden_layers": 5,
        "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "v_head_dim": 64,
        "linear_attn_config": {**FULL["toy"]["linear_attn_config"],
                               "head_dim": 128}}
LENGTH = 128
KEEP = jax.checkpoint_policies.save_only_these_names(*ops.KEPT_NAMES)


def kernels(fn, *args):
    """Kernel names, with their counts, of ``fn`` lowered for the TPU."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(_dispatch, "use_interpret", lambda: False)
        text = jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    return _dispatch.kernel_calls(text)


@pytest.fixture(scope="module")
def five():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, LENGTH), 0,
                                FIVE["vocab_size"])
    model = models.kimi_linear_from_config(FIVE)
    assert [k[0] for k in model.layer_kinds] == ["kda"] * 3 + ["mla", "kda"]
    return model.init(jax.random.PRNGKey(3), tokens)["params"], tokens


def value_and_grad(remat, tokens):
    model = models.kimi_linear_from_config(FIVE, remat=remat)
    # a new function each time: ``jax.jit`` keeps a trace by its function
    return jax.value_and_grad(
        lambda p: models.lm_loss(model, {"params": p}, tokens)[0])


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
def test_one_forward_kernel_a_layer(five, remat):
    """Recomputed or not, the scan's and attention's forward kernels are in
    the step once: the rerun of a block finds their outputs kept and holds
    no call. The one forward kernel a rerun may hold is the short
    convolution's (three a KDA layer: q, k, v), whose output is not kept:
    twice a layer under ``remat``, once without. An expert layer's kernels
    are its own ``custom_vjp``'s, recomputed or not: 3 + 5 ``apex_gmm``, 1 + 4
    ``apex_tgmm`` (the tokens' sums and the weights' gradients) and the
    unwritten buffers of its loops over the live rows."""
    params, tokens = five
    # (at this length attention's backward is the one fused kernel)
    assert kernels(value_and_grad(remat, tokens), params) == {
        "apex_kda_fwd": 4, "apex_kda_bwd": 4, "apex_attn_fwd": 1,
        "apex_attn_bwd": 1, "apex_xentropy_fwd": 1, "apex_xentropy_bwd": 1,
        "apex_short_conv_fwd": 24 if remat else 12,
        "apex_short_conv_bwd": 12,
        "apex_gmm": 4 * 8, "apex_tgmm": 4 * 5, "apex_unwritten": 4 * 14}


def test_remat_changes_nothing_with_kernels(five):
    params, tokens = five
    (loss, grads), (again, regrads) = (
        jax.jit(value_and_grad(remat, tokens))(params)
        for remat in (False, True))
    assert float(abs(loss - again)) <= 1e-6
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, x), y in zip(flat, jax.tree_util.tree_leaves(regrads)):
        assert float(jnp.max(jnp.abs(x - y))) <= 1e-6, \
            jax.tree_util.keystr(path)


def _attention_case():
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 256, 2, 128),
                                 jnp.bfloat16) for i in range(3))
    # tiles of 128: two blocks each way, so the backward is the two kernels
    # that read ``lse`` (one block's fused backward computes it again)
    loss = lambda q, k, v: jnp.sum(ops.flash_attention(
        q, k, v, None, None, True, 128, 128).astype(jnp.float32))
    return attention, "apex_attn_fwd", loss, (q, k, v)


def _delta_rule_case():
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    shape = (1, 128, 2, 128)
    q, k, v = (jax.random.normal(key, shape) for key in keys[:3])
    g = -jax.nn.softplus(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    loss = lambda *xs: jnp.sum(ops.gated_delta_rule(*xs))
    return delta_rule, "apex_kda_fwd", loss, (q, k, v, g, beta)


def _gated_deltanet_case():
    """One decay a head and a key head for every two value heads: the
    kernels of their own keep the same three names."""
    keys = jax.random.split(jax.random.PRNGKey(8), 5)
    q, k = (jax.random.normal(key, (1, 128, 1, 128)) for key in keys[:2])
    v = jax.random.normal(keys[2], (1, 128, 2, 128))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (1, 128, 2)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (1, 128, 2)))
    loss = lambda *xs: jnp.sum(ops.gated_delta_rule(*xs))
    return delta_rule, "apex_gdn_fwd", loss, (q, k, v, g, beta)


CASES = pytest.mark.parametrize(
    "case", [_attention_case, _delta_rule_case, _gated_deltanet_case],
    ids=["flash_attention", "gated_delta_rule", "gated_deltanet"])


@CASES
def test_the_names_are_free(case, monkeypatch):
    """Outside a checkpoint ``checkpoint_name`` is the identity and lowers to
    nothing: the differentiated op is the same program with and without."""
    module, _, loss, args = case()
    monkeypatch.setattr(_dispatch, "use_interpret", lambda: False)
    texts = []
    # both lowered from one line: a kernel's payload holds its call stack
    for name in (module.checkpoint_name, lambda x, name: x):
        monkeypatch.setattr(module, "checkpoint_name", name)
        texts.append(jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))
                     .trace(*args).lower(lowering_platforms=("tpu",))
                     .as_text())
    assert "tpu_custom_call" in texts[0]
    assert texts[0] == texts[1]


@CASES
def test_a_users_checkpoint_keeps_them(case):
    """``jax.checkpoint`` with the exported names in its policy drops the
    forward kernel from the rerun; with no policy the kernel runs twice."""
    _, forward, loss, args = case()
    # the value too: a forward pass nothing reads would be dropped whole
    grad = lambda **kw: jax.value_and_grad(
        jax.checkpoint(loss, **kw), argnums=tuple(range(len(args))))
    assert kernels(grad(), *args)[forward] == 2
    assert kernels(grad(policy=KEEP), *args)[forward] == 1
    one = jax.checkpoint_policies.save_only_these_names(
        ops.KEPT_KDA if forward == "apex_attn_fwd" else ops.KEPT_ATTN)
    assert kernels(grad(policy=one), *args)[forward] == 2
