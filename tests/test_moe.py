"""One expert-parallel rank's expert layer (``ops/moe.py``): the held
experts' part of the result is exact for any routing (no token dropped),
the shares of all ranks add up to the whole layer, the counters say what
reached this rank and what the grouped matmul ran on, and the grouped
matmul itself (``ops/grouped_matmul.py``, interpreted here) gives each
group's product for any sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import amp
from apex_tpu.ops import grouped_matmul as gm
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
    elif kind == "every_assignment_held":
        first = jax.random.randint(key, (T,), 0, len(held))
        chosen = jnp.stack([jnp.asarray(held)[first],
                            jnp.asarray(held)[(first + 1) % len(held)]], 1)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (T, K), minval=0.2)
    return chosen.astype(jnp.int32), w


@pytest.mark.parametrize("tile", [512, 32])
@pytest.mark.parametrize("kind", ["uniform", "all_to_one_held",
                                  "two_over_rest_spread", "none_to_any",
                                  "every_assignment_held"])
def test_exact_for_any_routing(kind, tile, monkeypatch):
    """Values and all six gradients against the dense sum, with the sorted
    rows in one tile (what 320 assignments make of 512) and in ten, and the
    tokens in one tile of the way back and in five."""
    monkeypatch.setattr(gm, "ROW_TILE", tile)
    monkeypatch.setattr(moe, "TOKEN_TILE", tile)
    held = (4, 5, 6, 7)
    x, w_gate, w_up, w_down = weights(0, len(held))
    chosen, w = routing(kind, held)
    load = moe.expert_load(chosen, held)
    assert int(load.sum()) == {"all_to_one_held": T, "none_to_any": 0,
                               "every_assignment_held": T * K}.get(
        kind, int(load.sum()))
    if kind == "two_over_rest_spread":      # two with half the rows each
        assert load[:2].tolist() == [T // 2, T // 2] and int(load[2:].sum())
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
        assert bool(jnp.all(jnp.isfinite(a)))
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-5 * max(
            1.0, float(jnp.max(jnp.abs(b))))


def per_group(lhs, rhs, sizes, transposed=False):
    """Each group's rows times its own matrix, one ``einsum`` a group."""
    out = np.zeros((lhs.shape[0], rhs.shape[1 if transposed else 2]),
                   np.float32)
    start = 0
    for g, n in enumerate(sizes):
        w = np.asarray(rhs[g], np.float32)
        out[start:start + n] = np.einsum(
            "mk,kn->mn", np.asarray(lhs[start:start + n], np.float32),
            w.T if transposed else w)
        start += n
    return out


def per_group_t(lhs, rhs, sizes):
    out = np.zeros((len(sizes), lhs.shape[1], rhs.shape[1]), np.float32)
    start = 0
    for g, n in enumerate(sizes):
        out[g] = np.einsum("mk,mn->kn",
                           np.asarray(lhs[start:start + n], np.float32),
                           np.asarray(rhs[start:start + n], np.float32))
        start += n
    return out


GROUPS = {"even": [32, 32, 32, 32], "one_takes_all": [0, 128, 0, 0],
          "some_empty": [0, 50, 0, 33], "no_multiple_of_the_tile":
          [7, 9, 61, 3], "no_live_row": [0, 0, 0, 0],
          "the_last_to_the_end": [1, 0, 0, 127],
          "more_groups_than_tiles": [3, 0, 5, 1, 1, 0, 9, 2, 4, 6, 0, 8]}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(GROUPS))
def test_grouped_matmul_and_its_two_backward_forms(case, dtype, monkeypatch):
    """``apex_gmm`` (plain, against transposed weights, two pairs summed)
    and ``apex_tgmm`` against a per-group ``einsum``: the live rows' values,
    zeros for what a visited tile holds past them, and exactly zero for a
    group that got no row."""
    monkeypatch.setattr(gm, "ROW_TILE", 32)
    sizes = GROUPS[case]
    m, k, n, g = 128, 24, 40, len(sizes)
    live = sum(sizes)
    ks = jax.random.split(jax.random.PRNGKey(g), 4)
    lhs = jax.random.normal(ks[0], (m, k)).astype(dtype)
    rhs = jax.random.normal(ks[1], (g, k, n)).astype(dtype)
    rhs_t = jax.random.normal(ks[2], (g, n, k)).astype(dtype)
    other = jax.random.normal(ks[3], (m, n)).astype(dtype)
    sz = jnp.asarray(sizes, jnp.int32)
    # one rounding of a float32 sum, as the per-group einsum has none
    tol = lambda want: (2 ** -8 if dtype == jnp.bfloat16 else 1e-5) * max(
        1.0, float(np.max(np.abs(want))))
    f32 = lambda a: np.asarray(a.astype(jnp.float32))
    for got, want in (
            (gm.grouped_matmul(lhs, rhs, sz), per_group(lhs, rhs, sizes)),
            (gm.grouped_matmul(lhs, rhs_t, sz, transposed=True),
             per_group(lhs, rhs_t, sizes, True)),
            (gm.grouped_matmul((lhs, lhs), (rhs, rhs), sz),
             2 * per_group(lhs, rhs, sizes))):
        assert got.dtype == dtype and got.shape == want.shape
        assert np.max(np.abs(f32(got)[:live] - want[:live]),
                      initial=0.0) <= tol(want)
        # a visited tile is written whole: zeros past the last live row
        assert not np.any(f32(got)[live:-(-live // 32) * 32])
    got = gm.grouped_matmul_t(lhs, other, sz)
    want = per_group_t(lhs, other, sizes)
    assert got.dtype == dtype and got.shape == (g, k, n)
    assert np.max(np.abs(f32(got) - want)) <= tol(want)
    for i, size in enumerate(sizes):
        if size == 0:
            assert not np.any(f32(got)[i])
    # a left operand in parts, summed in float32 before anything is rounded
    parts = (lhs, (0.5 * lhs).astype(dtype), (0.25 * lhs).astype(dtype))
    got = gm.grouped_matmul_t(parts, other, sz, jnp.float32)
    assert got.dtype == jnp.float32
    assert np.max(np.abs(np.asarray(got) - 1.75 * want)) <= 1e-5 * max(
        1.0, float(np.max(np.abs(want))))
    # the grid's bound: tiles that hold a live row, one more where a group
    # starts inside another's tile
    *_, steps = gm.visits(sz, m, 32)
    assert -(-live // 32) <= int(steps) <= -(-live // 32) + g - 1
    if case == "no_live_row":
        assert int(steps) == 0
    assert int(gm.visits(sz, m, 32, empty=True)[3]) >= g * (live == 0)


def test_grouped_matmul_refuses_rows_that_are_no_whole_tiles():
    lhs, rhs = jnp.zeros((520, 8)), jnp.zeros((2, 8, 8))
    with pytest.raises(ValueError, match="whole tiles"):
        gm.grouped_matmul(lhs, rhs, jnp.array([1, 2]))
    with pytest.raises(ValueError, match="whole tiles"):
        gm.grouped_matmul_t(lhs, lhs, jnp.array([1, 2]))
    assert gm.row_tile(65536) == 512 and gm.row_tile(320) == 320
    assert gm.row_tile(330) == 336 and moe.sorted_rows(330) == 336
    assert moe.sorted_rows(81920) == 81920 and moe.sorted_rows(600) == 1024


def _equations(jaxpr, found, outer=""):
    """Every equation under ``jaxpr`` as ``(primitive, scope path)``; a
    jitted launcher's equations carry its call site's path before theirs,
    and a kernel is one equation (its body is not the step's)."""
    for eqn in jaxpr.eqns:
        path = f"{outer}/{eqn.source_info.name_stack}"
        found.append((eqn.primitive.name, path))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _equations(sub, found, path)
    return found


def test_every_move_is_a_gather_and_there_is_one_path():
    """The step of a toy layer, forward and backward: no ``scatter`` under
    ``moe/dispatch`` or ``moe/combine``, no loop under ``moe/`` but those
    over the chunks that hold a live row (``live_rows``: the gather of the
    tokens' rows and what is done to the sorted rows between the kernels; no
    matmul is in one), nothing named ``moe/overflow``, and the grouped
    matmuls where they belong."""
    from apex_tpu import models
    held = (0, 1, 2, 3)
    layer = models.ExpertFFN(D, F, 16, 4, held, 1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, D))
    p = layer.init(jax.random.PRNGKey(1), x)["params"]
    loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x)[0])
    step = jax.grad(loss, argnums=(0, 1))
    found = _equations(jax.make_jaxpr(step)(p, x).jaxpr, [])
    under = lambda scope: [name for name, path in found if scope in path]
    for scope in ("moe/dispatch", "moe/combine"):
        assert under(scope) and "gather" in under(scope)
        assert not [n for n in under(scope) if "scatter" in n], scope
    loops = [path for name, path in found
             if "moe/" in path and name in ("while", "scan", "cond")]
    assert len(loops) == 3 + 5 and all(       # forward, backward
        path.endswith("live_rows") for path in loops)
    assert not [name for name, path in found if "live_rows" in path
                and "apex_unwritten" not in path
                and name in ("dot_general", "pallas_call", "scatter-add")]
    assert not under("moe/overflow")
    kernels = [path for name, path in found if name == "pallas_call"]
    matmuls = [path for path in kernels if "apex_unwritten" not in path]
    experts = [path for path in matmuls if "moe/experts" in path]
    assert len(experts) == 3 + 3 + 2 + 3
    assert sum("apex_tgmm" in path for path in experts) == 3
    # the way back to the tokens: a sum of rows as a matmul, each direction
    back = [path for path in matmuls if path not in experts]
    assert len(back) == 2 and all("apex_tgmm" in path for path in back)
    assert sum("moe/combine" in path for path in back) == 1
    assert sum("moe/dispatch" in path for path in back) == 1
    # a loop's buffers start unwritten: no pass over every row fills them
    assert len(kernels) > len(matmuls) and all(
        "live_rows" in path for path in kernels if path not in matmuls)
    text = jax.jit(step).lower(p, x).as_text(debug_info=True)
    assert "moe/experts" in text and "moe/overflow" not in text


def test_expert_rows_run_follows_the_routing(monkeypatch):
    """The counter beside ``rows_routed_here``: visited tiles times a tile's
    rows, never under the rows routed here and over them by the tiles'
    rounding alone, whether one expert got everything or all got some."""
    monkeypatch.setattr(gm, "ROW_TILE", 32)
    held = (4, 5, 6, 7)
    run = {}
    for kind in ("all_to_one_held", "uniform", "every_assignment_held",
                 "none_to_any"):
        load = moe.expert_load(routing(kind, held)[0], held)
        run[kind] = int(moe.expert_rows_run(load, T * K))
        here = int(load.sum())
        assert here <= run[kind] <= -(-here // 32) * 32 + 32 * (len(held) - 1)
        assert run[kind] % 32 == 0
    assert run["all_to_one_held"] == T == 160      # five whole tiles
    assert run["none_to_any"] == 0
    assert run["every_assignment_held"] >= T * K > run["all_to_one_held"]
    assert run["uniform"] < run["all_to_one_held"]
    # a row for each layer, as ``lm_loss`` stacks the loads
    loads = jnp.stack([moe.expert_load(routing(kind, held)[0], held)
                       for kind in ("uniform", "all_to_one_held")])
    assert moe.expert_rows_run(loads, T * K).tolist() == [
        run["uniform"], run["all_to_one_held"]]


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
    def layer(x, w, w_gate, w_up, w_down):
        with amp.policy_scope(policy):
            return moe.held_experts(x, w, chosen, w_gate, w_up, w_down, held,
                                    E)
    y = layer(x, w, w_gate, w_up, w_down)

    def operands(jaxpr, seen):      # of the experts' matmuls, in the trace
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                if "apex_gmm" in str(eqn.params["name"]):
                    seen += [v.aval.dtype for v in eqn.invars
                             if v.aval.dtype != jnp.int32]
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                operands(sub, seen)
        return seen
    seen = operands(jax.make_jaxpr(layer)(x, w, w_gate, w_up, w_down).jaxpr,
                    [])
    assert seen and set(seen) == {jnp.dtype(dtype)} and y.dtype == jnp.float32
