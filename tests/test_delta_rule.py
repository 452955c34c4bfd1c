"""The chunked gated delta rule (``ops/delta_rule.py``) against the
recurrence it stands for, one step a token: outputs and every gradient, at
lengths that are and are not whole chunks, from a decay that hardly decays
to one (``g = -5`` a step) whose ``exp(-sum g)`` passes float32 inside a
chunk; with a decay for each key channel (KDA) and with one a head and key
heads shared by pairs of value heads (gated DeltaNet)."""

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


@pytest.mark.parametrize("g_scale", [1e-3, 0.1, 1.0, 5.0])
@pytest.mark.parametrize("length", [64, 100, 192])
def test_kernels_equal_chunked_and_recurrent(length, g_scale, monkeypatch):
    """Head sizes of whole lane tiles take the Pallas kernels (interpreted
    here): against the recurrence and against the ``jax.numpy`` chunked form
    they replace, output and all five gradients."""
    batch = 2 if length == 100 else 1       # the grid's batch axis, once
    args = inputs(length, batch, length, 2, 128, 128, g_scale)
    both = lambda fn: (fn(*args), jax.grad(weighted(fn),
                                           argnums=range(5))(*args))
    assert "apex_kda_fwd/" in jax.jit(gated_delta_rule).lower(
        *args).as_text(debug_info=True)
    out, got = both(gated_delta_rule)
    ref, want = both(gated_delta_rule_reference)
    with monkeypatch.context() as m:     # the chunked form, by the tile test
        m.setattr(delta_rule, "_tiled", lambda dk, dv: False)
        mid, middle = both(gated_delta_rule)
    assert out.shape == ref.shape == (batch, length, 2, 128)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6
    assert float(jnp.max(jnp.abs(out - mid))) <= 2e-6
    for name, a, b, c in zip("q k v g beta".split(), got, want, middle):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * scale, name
        assert float(jnp.max(jnp.abs(a - c))) <= 5e-5 * scale, name


def test_small_heads_take_the_chunked_form():
    """``d_k = 16`` is no whole lane tile: no kernel in the lowered op."""
    assert not delta_rule._tiled(16, 8) and not delta_rule._tiled(128, 64)
    assert delta_rule._tiled(128, 256)
    lowered = jax.jit(jax.grad(weighted(gated_delta_rule))).lower(
        *inputs(0, 1, 64, 2, 16, 8, 0.1)).as_text(debug_info=True)
    assert "apex_kda" not in lowered and "triangular_solve" in lowered


def test_strong_decay_forms_nothing_unbounded_in_the_kernel():
    """Everything the forward kernel writes (output, states, the inverse),
    and every term it forms on the way (the levels' decayed operands, the
    chunk's six terms), at g = -5 a step."""
    q, k, v, g, beta = inputs(0, 1, 128, 2, 128, 128, 5.0)
    flat = lambda x: x.reshape(1, 128, -1)      # the kernels' (B, T, H d)
    for term in delta_rule._forward_kernel(*map(flat, (q, k, v, g)), beta):
        assert bool(jnp.all(jnp.isfinite(term)))
        assert float(jnp.max(jnp.abs(term))) < 1e3
    chunk = lambda x: x[0, :64, 0]
    terms = delta_rule._chunk_forward(
        *map(chunk, (q, k, v, g)), beta[0, :64, :1],
        jnp.asarray(delta_rule._sum_matrix(64, 1), jnp.bfloat16))
    for term in jax.tree_util.tree_leaves(terms):
        assert bool(jnp.all(jnp.isfinite(term)))
        assert float(jnp.max(jnp.abs(term))) < 1e3


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


@pytest.mark.parametrize("dim", [16, 128])
def test_float32_inside_whatever_comes_in(dim):
    """Under O1 the op takes half inputs and a patched ``jnp.einsum``: state,
    decay and result stay float32, and the gradients come back in the
    inputs' dtypes; in the kernels (``dim`` 128) as in the chunked form."""
    policy = amp.Policy.from_opt_level("O1")
    args = inputs(1, 1, 64, 2, dim, dim, 0.1, jnp.bfloat16)
    with amp.auto_cast(policy):
        out = gated_delta_rule(*args)
        grads = jax.grad(weighted(gated_delta_rule), argnums=range(5))(*args)
    assert out.dtype == jnp.float32
    assert [g.dtype for g in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    ref = gated_delta_rule_reference(*args)
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6   # no half inside
    assert amp.lists.classify("gated_delta_rule") == "float"


def shared(args, scalar=True, ratio=2):
    """The inputs with one decay a head (its first channel's) and a key head
    for every ``ratio`` value heads (every ``ratio``-th of them)."""
    q, k, v, g, beta = args
    return (q[:, :, ::ratio], k[:, :, ::ratio], v,
            g[..., 0] if scalar else g, beta)


@pytest.mark.parametrize("g_scale", [0.1, 5.0, 1.0])
@pytest.mark.parametrize("length", [64, 100])
@pytest.mark.parametrize("dim", [16, 128])
def test_scalar_decay_and_shared_key_heads_equal_recurrent(dim, length,
                                                           g_scale):
    """``g`` of ``(B, T, H)`` and 2 value heads a key head: the chunked form
    (``dim`` 16) and the kernels (128, interpreted; a grid step's two value
    heads read their key head in place) against the recurrence, output and
    all five gradients, in the shapes they came in."""
    args = shared(inputs(length, 1, length, 4, dim, dim, g_scale))
    assert args[0].shape[2] == 2 and args[3].shape == (1, length, 4)
    if dim == 128:
        assert "apex_gdn_fwd/" in jax.jit(gated_delta_rule).lower(
            *args).as_text(debug_info=True)
    _equal_recurrent(args, dim, length)


def _equal_recurrent(args, dim, length):
    out, ref = gated_delta_rule(*args), gated_delta_rule_reference(*args)
    assert out.shape == ref.shape == (1, length) + args[2].shape[2:]
    assert bool(jnp.all(jnp.isfinite(out)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 2e-6
    got = jax.grad(weighted(gated_delta_rule), argnums=range(5))(*args)
    want = jax.grad(weighted(gated_delta_rule_reference),
                    argnums=range(5))(*args)
    for name, x, a, b in zip("q k v g beta".split(), args, got, want):
        assert a.shape == x.shape, name
        assert bool(jnp.all(jnp.isfinite(a))), name
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * float(
            jnp.max(jnp.abs(b))), name


@pytest.mark.parametrize("g_scale", [0.1, 1.0, 5.0])
@pytest.mark.parametrize("heads,key_heads", [
    (4, 4),             # own key heads, two a grid step
    (8, 2),             # four value heads a key head: repeated a group
    (3, 3),             # an odd count: one head a grid step
], ids=["own_keys", "shared_by_four", "odd_heads"])
def test_scalar_kernels_equal_recurrent(heads, key_heads, g_scale):
    """The kernels of one decay a head (interpreted, ``dim`` 128) against
    the recurrence at 100 tokens (two chunks, the second padded), output and
    all five gradients in the shapes they came in, where the key heads meet
    a grid step's value heads otherwise than in the test above."""
    full = inputs(heads, 1, 100, heads, 128, 128, g_scale)
    args = shared(full, ratio=heads // key_heads)
    assert args[0].shape[2] == key_heads and args[3].shape == (1, 100, heads)
    lowered = jax.jit(gated_delta_rule).lower(*args).as_text(debug_info=True)
    assert "apex_gdn_fwd/" in lowered and "apex_kda" not in lowered
    _equal_recurrent(args, 128, 100)


def test_strong_decay_forms_nothing_unbounded_in_the_scalar_kernel():
    """One decay a head at g = -5 a step, key heads shared in place: what
    the forward kernel writes, and every term of a chunk (the decay matrix,
    the scores, the inverse), bounded."""
    q, k, v, g, beta = shared(inputs(0, 1, 128, 4, 128, 128, 5.0))
    flat = lambda x: x.reshape(1, 128, -1)
    for term in delta_rule._gdn_forward_kernel(*map(flat, (q, k, v)), g,
                                               beta, 2):
        assert bool(jnp.all(jnp.isfinite(term)))
        assert float(jnp.max(jnp.abs(term))) < 1e3
    # the first chunk of value heads 0 and 1, both on key head 0
    rows = lambda x, heads=(0, 1): jnp.concatenate(
        [x[0, :64, j] for j in heads], 0)
    column = lambda x: rows(x[..., None])
    terms = delta_rule._gdn_chunk_forward(
        rows(q, (0, 0)), rows(k, (0, 0)), rows(v), column(g), column(beta),
        jnp.asarray(delta_rule._sum_matrix(64, 2), jnp.bfloat16))
    for term in jax.tree_util.tree_leaves(terms):
        assert bool(jnp.all(jnp.isfinite(term)))
        assert float(jnp.max(jnp.abs(term))) < 1e3
    decay = terms[1][6]
    assert float(jnp.max(decay)) <= 1.0 and float(jnp.min(decay)) >= 0.0


@pytest.mark.parametrize("dim", [16, 128])
def test_scalar_path_equals_per_channel_path_fed_a_broadcast(dim):
    """A decay a head is the per-channel form's with every channel alike,
    shared key heads are that form's repeated, and the cotangents are the
    broadcast's summed. In the ``jax.numpy`` form (``dim`` 16) the op is
    that form, to the bit in the output; the kernels (128) have a form of
    their own, which sums a chunk's scores in another order: float32
    against float32, to ``2e-6`` of each result's largest magnitude."""
    full = inputs(5, 1, 100, 4, dim, dim, 1.0)
    q, k, v, g, beta = shared(full)
    wide = (jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v,
            jnp.broadcast_to(g[..., None], v.shape[:3] + (dim,)), beta)
    out, out_wide = gated_delta_rule(q, k, v, g, beta), gated_delta_rule(*wide)
    out_tol, grad_tol = (0.0, 1e-6) if dim == 16 else (2e-6, 2e-6)
    assert float(jnp.max(jnp.abs(out - out_wide))) <= out_tol * float(
        jnp.max(jnp.abs(out_wide)))
    got = jax.grad(weighted(gated_delta_rule), argnums=range(5))(
        q, k, v, g, beta)
    want = jax.grad(weighted(gated_delta_rule), argnums=range(5))(*wide)
    pairs = lambda x: x.reshape(*x.shape[:2], 2, 2, -1).sum(3)
    for a, b in zip(got, (pairs(want[0]), pairs(want[1]), want[2],
                          want[3].sum(-1), want[4])):
        assert float(jnp.max(jnp.abs(a - b))) <= grad_tol * max(
            1.0, float(jnp.max(jnp.abs(b))))
    # each alone: shared heads with a decay a channel, a decay a head with
    # every head its own keys
    for args in (shared(full, scalar=False), shared(full, ratio=1)):
        assert float(jnp.max(jnp.abs(
            gated_delta_rule(*args)
            - gated_delta_rule_reference(*args)))) <= 2e-6


@pytest.mark.parametrize("decay", ["a_channel", "a_head"])
@pytest.mark.parametrize("ratio", [1, 2], ids=["own_keys", "shared_keys"])
@pytest.mark.parametrize("dim", [16, 128])
def test_heads_side_by_side_equal_heads_apart(dim, ratio, decay):
    """``(B, T, H d)`` operands with the head size stated, as a projection
    writes them and the kernels read them, against the same values as ``(B,
    T, H, d)``: the output to the bit and the gradients in the layout each
    came in; with a decay a channel and a head, own and shared key heads;
    in the kernels (``dim`` 128, where the flat operands reach
    ``apex_kda_fwd`` or, with a decay a head, ``apex_gdn_fwd`` untouched:
    there ``g`` is not broadcast and shared key heads are not repeated) and
    in the chunked form (16)."""
    args = shared(inputs(7, 1, 100, 4, dim, dim, 0.5),
                  scalar=decay == "a_head", ratio=ratio)
    flat = lambda x: x.reshape(*x.shape[:2], -1) if x.ndim == 4 else x
    side_by_side = tuple(map(flat, args))
    assert side_by_side[0].shape == (1, 100, 4 // ratio * dim)
    assert side_by_side[3].shape == (1, 100, 4 * (dim if decay == "a_channel"
                                                  else 1))
    fn = lambda *a: gated_delta_rule(*a, head_dim=dim)
    out = fn(*side_by_side)
    assert out.shape == (1, 100, 4, dim)
    assert bool(jnp.all(out == gated_delta_rule(*args)))
    loss = lambda f: lambda *a: jnp.sum(f(*a) * jnp.cos(jnp.arange(dim)))
    got = jax.grad(loss(fn), argnums=range(5))(*side_by_side)
    want = jax.grad(loss(gated_delta_rule), argnums=range(5))(*args)
    for name, x, a, b in zip("q k v g beta".split(), side_by_side, got, want):
        assert a.shape == x.shape, name
        assert float(jnp.max(jnp.abs(a - flat(b)))) <= 1e-6 * max(
            1.0, float(jnp.max(jnp.abs(b)))), name
    if dim == 128:
        before = _bound_before(jax.make_jaxpr(fn)(*side_by_side).jaxpr,
                               "apex_gdn_fwd" if decay == "a_head"
                               else "apex_kda_fwd")
        assert before is not None
        assert "reshape" not in before and "transpose" not in before
        if decay == "a_head":
            assert "concatenate" not in before
            assert not any(p.startswith("broadcast") for p in before)


def _bound_before(jaxpr, kernel):
    """The primitives bound ahead of the first ``pallas_call`` named
    ``kernel`` (calls inside calls walked through, no kernel's body), or
    None where there is no such call."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    bound = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["name"] == kernel:
                return bound
            continue
        inner = [p for p in eqn.params.values()
                 if isinstance(p, (Jaxpr, ClosedJaxpr))]
        if inner:               # jit, custom_vjp_call, checkpoint
            found = _bound_before(getattr(inner[0], "jaxpr", inner[0]),
                                  kernel)
            if found is not None:
                return bound + found
        bound.append(eqn.primitive.name)
    return None
