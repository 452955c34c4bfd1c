"""One expert-parallel rank's expert layer (``ops/moe.py``): the held
experts' part of the result is exact for any routing (no token dropped),
the shares of all ranks add up to the whole layer, and the counters say
what reached this rank."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp
from apex_tpu.ops import moe

T, D, F, E, K = 160, 32, 16, 64, 2


def weights(seed, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (T, D)),
            jax.random.normal(ks[1], (n, D, F)) * 0.2,
            jax.random.normal(ks[2], (n, D, F)) * 0.2,
            jax.random.normal(ks[3], (n, F, D)) * 0.2)


def plain(x, w, chosen, w_gate, w_up, w_down, held):
    """A loop over the held experts with a 0/1 mask, every row."""
    y = jnp.zeros((x.shape[0], w_down.shape[-1]))
    for n, e in enumerate(held):
        w_row = jnp.sum(jnp.where(chosen == e, w, 0.0), -1)
        gate = x @ w_gate[n]
        y += w_row[:, None] * ((jax.nn.silu(gate) * (x @ w_up[n])) @ w_down[n])
    return y


def routing(kind, held):
    """``(chosen, weights)`` of a routing of the given kind."""
    key = jax.random.PRNGKey(7)
    if kind == "uniform":
        chosen = jnp.argsort(jax.random.uniform(key, (T, E)), -1)[:, :K]
    elif kind == "all_to_one_held":     # every row picks held[0] first
        chosen = jnp.stack([jnp.full((T,), held[0]),
                            jnp.full((T,), E - 1)], 1)
    elif kind == "two_over_rest_spread":    # held[0], held[1] overflow;
        first = jnp.where(jnp.arange(T) < T // 2, held[0], held[1])
        second = held[2] + jax.random.randint(key, (T,), 0, E - held[2])
        chosen = jnp.stack([first, second], 1)      # the others fit
    elif kind == "none_to_any":
        away = [e for e in range(E) if e not in held]
        chosen = jnp.stack([jnp.full((T,), away[0]),
                            jnp.full((T,), away[1])], 1)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (T, K), minval=0.2)
    return chosen.astype(jnp.int32), w


@pytest.mark.parametrize("kind", ["uniform", "all_to_one_held",
                                  "two_over_rest_spread", "none_to_any"])
def test_exact_for_any_routing(kind):
    held = (4, 5, 6, 7)
    x, w_gate, w_up, w_down = weights(0, len(held))
    chosen, w = routing(kind, held)
    cap = moe.capacity(T, K, E)
    load = moe.expert_load(chosen, held)
    # experts over their capacity run over every row, the others compacted
    assert int(jnp.sum(load > cap)) == {"all_to_one_held": 1,
                                        "two_over_rest_spread": 2}.get(kind, 0)
    if kind == "two_over_rest_spread":
        assert int(jnp.sum((load > 0) & (load <= cap))) == 2
    assert int(load.sum()) == {"all_to_one_held": T, "none_to_any": 0}.get(
        kind, int(load.sum()))
    run = lambda fn: lambda x, w, a, b, c: jnp.sum(
        fn(x, w, chosen, a, b, c, held) * jnp.sin(jnp.arange(D)))
    ours = lambda x, w, chosen, a, b, c, held: moe.held_experts(
        x, w, chosen, a, b, c, held, E)
    got = ours(x, w, chosen, w_gate, w_up, w_down, held)
    want = plain(x, w, chosen, w_gate, w_up, w_down, held)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    if kind == "none_to_any":
        assert float(jnp.max(jnp.abs(got))) == 0.0
    g_got = jax.grad(run(ours), argnums=range(5))(x, w, w_gate, w_up, w_down)
    g_want = jax.grad(run(plain), argnums=range(5))(x, w, w_gate, w_up, w_down)
    for a, b in zip(g_got, g_want):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * max(
            1.0, float(jnp.max(jnp.abs(b))))


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
def test_the_shares_add_up(scoring):
    """16 experts in 4 shares of 4: the four ranks' parts, summed, are the
    uncut layer's routed part (a shared expert would be counted once), for
    sigmoid scores with a selection bias and for a softmax over all 16."""
    E = 16
    x, w_gate, w_up, w_down = weights(1, E)
    router = jax.random.normal(jax.random.PRNGKey(2), (D, E)) * 0.3
    chosen, w = (moe.route(x, router, jnp.zeros(E), K, 2.446)
                 if scoring == "sigmoid" else
                 moe.route(x, router, None, 4, 1.0, "softmax"))
    whole = plain(x, w, chosen, w_gate, w_up, w_down, tuple(range(E)))
    parts = []
    for rank in range(4):
        held = tuple(range(4 * rank, 4 * rank + 4))
        sl = slice(4 * rank, 4 * rank + 4)
        parts.append(moe.held_experts(x, w, chosen, w_gate[sl], w_up[sl],
                                      w_down[sl], held, E))
        # what a share leaves out is there: it is not the whole
        assert float(jnp.max(jnp.abs(parts[-1] - whole))) > 1e-2
    assert float(jnp.max(jnp.abs(sum(parts) - whole))) <= 1e-5
    all_held = moe.held_experts(x, w, chosen, w_gate, w_up, w_down,
                                tuple(range(E)), E)
    assert float(jnp.max(jnp.abs(all_held - whole))) <= 1e-5


def test_the_shares_of_a_layer_with_a_gated_shared_expert_add_up():
    """``models.ExpertFFN`` as Qwen3-Next builds it (softmax over 16, 4
    chosen, a gate on the shared expert): four shares of 4, each with the
    same router and shared expert, minus the shared expert counted three
    times too often, are the uncut layer."""
    from apex_tpu import models
    E, k = 16, 4
    layer = lambda held: models.ExpertFFN(D, F, E, k, held, 1.0, "softmax",
                                          True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, D))
    p = layer(tuple(range(E))).init(jax.random.PRNGKey(1), x)["params"]
    p = {**p, "router": p["router"] * 30}      # scores that tell experts apart
    assert "e_bias" not in p and p["shared_gate"]["kernel"].shape == (D, 1)
    whole, load = layer(tuple(range(E))).apply({"params": p}, x)
    assert int(load.sum()) == 2 * 40 * k
    shared_only = models.SwiGLU(D, F).apply(
        {"params": p["shared"]}, x) * jax.nn.sigmoid(
            x @ p["shared_gate"]["kernel"])
    assert float(jnp.max(jnp.abs(shared_only))) > 1e-3
    total = 0.0
    for rank in range(4):
        held = tuple(range(4 * rank, 4 * rank + 4))
        mine = {**p, **{n: p[n][4 * rank:4 * rank + 4] for n in (
            "experts_gate", "experts_up", "experts_down")}}
        part, load = layer(held).apply({"params": mine}, x)
        assert load.shape == (4,)
        total = total + part
    assert float(jnp.max(jnp.abs(total - 3 * shared_only - whole))) <= 1e-5


def test_a_layer_with_no_shared_expert():
    """``shared_width`` 0: no ``shared`` parameters, no ``moe/shared`` scope,
    and the layer is the held experts' weighted sum alone; the default
    (None) still builds a shared expert of the routed width."""
    from apex_tpu import models
    E, k, held = 16, 4, (0, 1, 2, 3, 4, 5, 6, 7)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, D))
    layer = models.ExpertFFN(D, F, E, k, held, 1.0, shared_width=0)
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert set(p) == {"router", "e_bias", "experts_gate", "experts_up",
                      "experts_down"}
    p = {**p, "router": p["router"] * 30}
    y, load = layer.apply({"params": p}, x)
    rows = x.reshape(-1, D)
    chosen, w = moe.route(rows, p["router"], p["e_bias"], k, 1.0, "sigmoid")
    want = plain(rows, w, chosen, p["experts_gate"], p["experts_up"],
                 p["experts_down"], held)
    assert float(jnp.max(jnp.abs(y.reshape(-1, D) - want))) <= 1e-5 * float(
        jnp.max(jnp.abs(want)))
    assert load.tolist() == moe.expert_load(chosen, held).tolist()
    text = jax.jit(lambda p, x: layer.apply({"params": p}, x)[0]).lower(
        p, x).as_text(debug_info=True)
    assert "moe/experts" in text and "moe/shared" not in text
    with_shared = models.ExpertFFN(D, F, E, k, held, 1.0).init(
        jax.random.PRNGKey(1), x)["params"]
    assert with_shared["shared"]["up_proj"]["kernel"].shape == (D, F)


def test_softmax_route_normalises_over_the_chosen():
    x, *_ = weights(3, 1)
    router = jax.random.normal(jax.random.PRNGKey(4), (D, E))
    policy = amp.Policy.from_opt_level("O1")
    with amp.auto_cast(policy):
        chosen, w = moe.route(x.astype(jnp.bfloat16), router, None, 10, 1.0,
                              "softmax")
    assert chosen.shape == w.shape == (T, 10)
    assert chosen.dtype == jnp.int32 and w.dtype == jnp.float32
    probs = jax.nn.softmax(
        x.astype(jnp.bfloat16).astype(jnp.float32) @ router, -1)
    top, ids = jax.lax.top_k(probs, 10)
    assert bool(jnp.all(ids == chosen))
    assert float(jnp.max(jnp.abs(w - top / top.sum(-1, keepdims=True)))) <= 1e-6
    assert float(jnp.max(jnp.abs(w.sum(-1) - 1.0))) <= 1e-6
    with pytest.raises(KeyError):
        moe.route(x, router, None, 2, 1.0, "tanh")


def test_route_scores_every_expert_in_float32():
    x, *_ = weights(3, 1)
    router = jax.random.normal(jax.random.PRNGKey(4), (D, E))
    bias = jnp.zeros(E).at[3].set(10.0)         # the bias picks, does not weigh
    policy = amp.Policy.from_opt_level("O1")
    with amp.auto_cast(policy):
        chosen, w = moe.route(x.astype(jnp.bfloat16), router, bias, K, 2.446)
    assert chosen.dtype == jnp.int32 and w.dtype == jnp.float32
    assert bool(jnp.all(chosen[:, 0] == 3))
    scores = jax.nn.sigmoid(x.astype(jnp.bfloat16).astype(jnp.float32) @ router)
    picked = jnp.take_along_axis(scores, chosen, -1)
    assert float(jnp.max(jnp.abs(
        w - 2.446 * picked / picked.sum(-1, keepdims=True)))) <= 1e-5
    assert float(jnp.max(jnp.abs(w.sum(-1) - 2.446))) <= 1e-5
    assert jax.grad(lambda b: moe.route(x, router, b, K, 1.0)[1].sum())(
        bias).tolist() == [0.0] * E


@pytest.mark.parametrize("level,dtype", [("O0", jnp.float32),
                                         ("O1", jnp.bfloat16)])
def test_amp_lists_give_the_new_ops_their_dtypes(level, dtype):
    """``moe_experts`` is a HALF op, ``moe_router`` and ``gated_delta_rule``
    FLOAT ops: the experts' matmuls run in the policy's dtype, the weighted
    sum and the router in float32."""
    policy = amp.Policy.from_opt_level(level)
    assert policy.op_dtype("moe_experts", jnp.float32) == dtype
    assert policy.op_dtype("moe_router", jnp.bfloat16) == jnp.float32 or \
        level == "O0"
    assert policy.op_dtype("gated_delta_rule", jnp.bfloat16) == \
        jnp.float32 or level == "O0"
    assert amp.lists.classify("moe_experts") == "half"
    assert amp.lists.classify("moe_router") == "float"
    held = (0, 1, 2, 3)
    x, w_gate, w_up, w_down = weights(5, len(held))
    chosen, w = routing("uniform", held)
    seen = []
    real = moe._swiglu
    try:
        moe._swiglu = lambda x, *a: seen.append(x.dtype) or real(x, *a)
        with amp.policy_scope(policy):
            y = moe.held_experts(x, w, chosen, w_gate, w_up, w_down, held, E)
    finally:
        moe._swiglu = real
    assert seen and set(seen) == {jnp.dtype(dtype)} and y.dtype == jnp.float32


def test_capacity_is_eight_even_shares_in_sublanes():
    assert moe.capacity(8192, 8, 256) == 2048
    assert moe.capacity(160, 2, 64) == 40
    assert moe.capacity(8, 1, 64) == 8
