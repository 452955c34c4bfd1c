"""The chunked gated delta rule (``ops/delta_rule.py``) against the
recurrence it stands for, one step a token: outputs and every gradient, at
lengths that are and are not whole chunks, from a decay that hardly decays
to one (``g = -5`` a step) whose ``exp(-sum g)`` passes float32 inside a
chunk."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp
from apex_tpu.ops import delta_rule
from apex_tpu.ops.delta_rule import (gated_delta_rule,
                                     gated_delta_rule_reference)


def inputs(seed, b, t, h, dk, dv, g_scale, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -g_scale * jax.random.uniform(ks[3], (b, t, h, dk), minval=0.5,
                                      maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g, beta)


def weighted(fn):
    return lambda *a: jnp.sum(fn(*a) * jnp.cos(jnp.arange(a[2].shape[-1])))


@pytest.mark.parametrize("g_scale", [1e-3, 0.1, 1.0, 5.0])
@pytest.mark.parametrize("length", [64, 100, 192])
def test_chunked_equals_recurrent(length, g_scale):
    args = inputs(length, 2, length, 2, 16, 8, g_scale)
    out, ref = gated_delta_rule(*args), gated_delta_rule_reference(*args)
    assert out.shape == ref.shape == (2, length, 2, 8)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6
    got = jax.grad(weighted(gated_delta_rule), argnums=range(5))(*args)
    want = jax.grad(weighted(gated_delta_rule_reference),
                    argnums=range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # float32 against float32: sums in another order, nothing more
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * float(
            jnp.max(jnp.abs(b))), name


def test_strong_decay_forms_nothing_unbounded():
    """At g = -5 a step exp(-G) is 1e139 at a chunk's end: every term the
    chunked form builds must still be finite, not only its result."""
    for term in delta_rule._prepared(*inputs(0, 1, 128, 1, 16, 8, 5.0)):
        assert bool(jnp.all(jnp.isfinite(term)))
        assert float(jnp.max(jnp.abs(term))) < 1e3


def test_heads_go_through_in_groups(monkeypatch):
    """More heads than a group holds: the same result, group by group."""
    monkeypatch.setattr(delta_rule, "HEAD_GROUP", 2)
    args = inputs(3, 1, 130, 6, 8, 8, 0.5)
    got = jax.grad(weighted(gated_delta_rule), argnums=range(5))(*args)
    want = jax.grad(weighted(gated_delta_rule_reference),
                    argnums=range(5))(*args)
    assert float(jnp.max(jnp.abs(
        gated_delta_rule(*args) - gated_delta_rule_reference(*args)))) <= 1e-5
    for a, b in zip(got, want):
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * float(
            jnp.max(jnp.abs(b)))


def test_float32_inside_whatever_comes_in():
    """Under O1 the op takes half inputs and a patched ``jnp.einsum``: state,
    decay and result stay float32, and the gradients come back in the
    inputs' dtypes."""
    policy = amp.Policy.from_opt_level("O1")
    args = inputs(1, 1, 64, 2, 16, 16, 0.1, jnp.bfloat16)
    with amp.auto_cast(policy):
        out = gated_delta_rule(*args)
        grads = jax.grad(weighted(gated_delta_rule), argnums=range(5))(*args)
    assert out.dtype == jnp.float32
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    ref = gated_delta_rule_reference(*args)
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6   # no half inside
    assert amp.lists.classify("gated_delta_rule") == "float"
