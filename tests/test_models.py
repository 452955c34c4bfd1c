"""Model family smoke + driver artifact tests (CPU mesh)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models


class TestResNet:
    def test_resnet18_forward(self):
        model = models.ResNet18(num_classes=10, width=16)
        x = jnp.ones((2, 64, 64, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        y = model.apply(variables, x, train=False)
        assert y.shape == (2, 10)

    def test_resnet_train_updates_stats(self):
        model = models.ResNet(stage_sizes=[1], num_classes=4, width=8)
        x = jnp.asarray(np.random.RandomState(0)
                        .randn(2, 32, 32, 3).astype(np.float32))
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        y, mut = model.apply(variables, x, train=True,
                             mutable=["batch_stats"])
        assert y.shape == (2, 4)
        assert "batch_stats" in mut

    def test_resnet50_param_count(self):
        model = models.ResNet50(num_classes=1000)
        x = jnp.ones((1, 32, 32, 3))
        variables = model.init(jax.random.PRNGKey(0), x, train=False)
        n = sum(int(np.prod(p.shape)) for p in
                jax.tree_util.tree_leaves(variables["params"]))
        # torchvision resnet50: 25.56M params
        assert 25e6 < n < 26e6, n


def _resnet_layout(stage_sizes, bottleneck, width=64, classes=1000):
    """``path -> shape`` of the ResNet parameters as checkpoints hold them,
    written out from the architecture: in a block the convolutions are
    numbered main path first (``Conv_2`` is a bottleneck's final 1x1), the
    projection last (``Conv_3``; a basic block's ``Conv_2``), and the
    projection's BN sits before the unit that joins the residual."""
    bn = "FusedBNAct_0"
    out = {"stem_conv/kernel": (7, 7, 3, width),
           f"_BN_0/{bn}/scale": (width,), f"_BN_0/{bn}/bias": (width,)}
    cin, k = width, 0
    for i, n_blocks in enumerate(stage_sizes):
        f = width * 2 ** i
        cout = 4 * f if bottleneck else f
        for j in range(n_blocks):
            name = f"{'BottleneckBlock' if bottleneck else 'BasicBlock'}_{k}"
            proj = cin != cout or (i > 0 and j == 0)
            convs = ([(1, 1, cin, f), (3, 3, f, f), (1, 1, f, cout)]
                     if bottleneck else [(3, 3, cin, f), (3, 3, f, f)])
            bns = [f] * (len(convs) - 1)
            if proj:
                convs.append((1, 1, cin, cout))
                bns.append(cout)
            bns.append(cout)
            for c, shape in enumerate(convs):
                out[f"{name}/Conv_{c}/kernel"] = shape
            for c, ch in enumerate(bns):
                out[f"{name}/_BN_{c}/{bn}/scale"] = (ch,)
                out[f"{name}/_BN_{c}/{bn}/bias"] = (ch,)
            cin, k = cout, k + 1
    out["Dense_0/kernel"] = (cin, classes)
    out["Dense_0/bias"] = (classes,)
    return out


@pytest.mark.parametrize("arch", ["ResNet18", "ResNet50", "ResNet101"])
def test_resnet_param_tree_is_the_checkpoint_layout(arch):
    from flax.traverse_util import flatten_dict
    stage_sizes, bottleneck = {"ResNet18": ([2, 2, 2, 2], False),
                               "ResNet50": ([3, 4, 6, 3], True),
                               "ResNet101": ([3, 4, 23, 3], True)}[arch]
    model = getattr(models, arch)()
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 64, 64, 3)), train=True))
    got = {k: v.shape
           for k, v in flatten_dict(variables["params"], sep="/").items()}
    want = _resnet_layout(stage_sizes, bottleneck)
    assert got == want
    if bottleneck:
        # the first block of a stage projects: its final 1x1 and its
        # projection, by the names a checkpoint knows them under
        assert got["BottleneckBlock_3/Conv_2/kernel"] == (1, 1, 128, 512)
        assert got["BottleneckBlock_3/Conv_3/kernel"] == (1, 1, 256, 512)
        assert "BottleneckBlock_4/Conv_3/kernel" not in got
    stats = flatten_dict(variables["batch_stats"], sep="/")
    assert ({k.removesuffix("/mean") for k in stats if k.endswith("/mean")}
            == {k.removesuffix("/scale") for k in got
                if k.endswith("/scale")})


class TestTransformer:
    def test_encoder_forward(self):
        enc = models.BertEncoder(vocab_size=100, hidden=64, layers=2,
                                 heads=4, max_len=32)
        toks = jnp.ones((2, 16), jnp.int32)
        variables = enc.init(jax.random.PRNGKey(0), toks)
        y = enc.apply(variables, toks)
        assert y.shape == (2, 16, 64)

    def test_mlm_loss(self):
        enc = models.BertEncoder(vocab_size=50, hidden=32, layers=1,
                                 heads=2, max_len=16)
        toks = jnp.ones((2, 8), jnp.int32)
        variables = enc.init(jax.random.PRNGKey(0), toks)
        labels = jnp.full((2, 8), -1, jnp.int32).at[0, 2].set(5)
        loss = models.mlm_loss(enc, variables, toks, labels)
        assert np.isfinite(float(loss))

    def test_attention_mask(self):
        enc = models.BertEncoder(vocab_size=50, hidden=32, layers=1,
                                 heads=2, max_len=16)
        toks = jnp.ones((1, 8), jnp.int32)
        variables = enc.init(jax.random.PRNGKey(0), toks)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]])
        y = enc.apply(variables, toks, attn_mask=mask)
        assert y.shape == (1, 8, 32)


# ---- the MLM head on the labelled rows only --------------------------------
# B x S = 4 x 128 = 512 rows: the gathered head holds 128, one row block

HEAD_B, HEAD_S, HEAD_V = 4, 128, 300
HEAD_ROWS = HEAD_B * HEAD_S
HEAD_CAP = 128


def _label_case(case, rows=HEAD_ROWS, seq=HEAD_S, seed=7):
    """Flat labels, -1 where a position is not labelled."""
    rng = np.random.RandomState(seed)
    labels = np.full(rows, -1, np.int32)
    if case == "per_row_15pct":
        picked = np.concatenate([
            r * seq + rng.permutation(seq)[:round(0.15 * seq)]
            for r in range(rows // seq)])
    elif case == "one_row_all":         # the first sequence, whole: = cap
        picked = np.arange(seq)
    else:
        count = {"none": 0, "one": 1, "cap": HEAD_CAP,
                 "cap_plus_1": HEAD_CAP + 1, "every": rows}[case]
        picked = rng.permutation(rows)[:count]
    labels[picked] = rng.randint(0, HEAD_V, len(picked))
    return labels


@pytest.fixture(scope="module")
def head_setup():
    enc = models.BertEncoder(vocab_size=HEAD_V, hidden=32, layers=1,
                             heads=2, max_len=HEAD_S)
    tokens = jnp.asarray(np.random.RandomState(3).randint(
        0, HEAD_V, (HEAD_B, HEAD_S)), jnp.int32)
    params = enc.init(jax.random.PRNGKey(0), tokens)["params"]

    def system(params, tokens, labels, smoothing):
        return models.mlm_loss(enc, {"params": params}, tokens, labels,
                               smoothing)

    def plain(params, tokens, labels, smoothing):
        """Every row's logits, then the pure-jnp cross-entropy."""
        from apex_tpu import ops
        hidden = enc.apply({"params": params}, tokens)
        logits = hidden @ params["tok_emb"]["embedding"].T
        losses = ops.softmax_cross_entropy_reference(logits, labels,
                                                     smoothing)
        return jnp.sum(losses) / jnp.maximum(jnp.sum(labels >= 0), 1)

    def both(fn):       # one compile serves every label count
        return jax.jit(jax.value_and_grad(fn), static_argnums=3)

    return types.SimpleNamespace(
        enc=enc, tokens=tokens, params=params, system=system, plain=plain,
        system_grad=both(system), plain_grad=both(plain))


def _assert_same(got, want):
    (loss, grads), (ref_loss, ref_grads) = got, want
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5, atol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(ref_grads)
    assert len(flat) == len(jax.tree_util.tree_leaves(grads)) > 10
    for (path, want_g), got_g in zip(flat, jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(
            got_g, want_g, rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


class TestMlmHead:
    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    @pytest.mark.parametrize("case", [
        "none", "one", "per_row_15pct", "one_row_all", "cap", "cap_plus_1",
        "every"])
    def test_loss_and_gradients_equal_the_full_logits_reference(
            self, head_setup, case, smoothing):
        h = head_setup
        labels = jnp.asarray(_label_case(case).reshape(HEAD_B, HEAD_S))
        got = h.system_grad(h.params, h.tokens, labels, smoothing)
        if case == "none":
            assert float(got[0]) == 0.0
            assert all(not np.any(np.asarray(g))
                       for g in jax.tree_util.tree_leaves(got[1]))
        _assert_same(got, h.plain_grad(h.params, h.tokens, labels, smoothing))

    @pytest.mark.parametrize("case", ["cap_plus_1", "every"])
    def test_over_capacity_is_the_old_head(self, head_setup, case):
        """No label is dropped: a batch over the capacity gets what
        ``mlm_loss`` computed before it had two heads, the loss to the bit
        and the gradients to the order of a float32 sum."""
        from apex_tpu import ops
        h = head_setup

        def old(params, tokens, labels):
            hidden = h.enc.apply({"params": params}, tokens)
            emb = params["tok_emb"]["embedding"]
            logits = hidden @ emb.T.astype(hidden.dtype)
            losses = ops.softmax_cross_entropy_loss(logits, labels, 0.0)
            return jnp.sum(losses) / jnp.maximum(jnp.sum(labels >= 0), 1)

        labels = jnp.asarray(_label_case(case).reshape(HEAD_B, HEAD_S))
        loss, grads = h.system_grad(h.params, h.tokens, labels, 0.0)
        old_loss, old_grads = jax.jit(jax.value_and_grad(old))(
            h.params, h.tokens, labels)
        assert float(loss) == float(old_loss)
        for got, want in zip(jax.tree_util.tree_leaves(grads),
                             jax.tree_util.tree_leaves(old_grads)):
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)

    def test_each_shard_takes_its_own_branch(self, head_setup, devices):
        """Under shard_map one device's labels fit the capacity and the
        other's do not: each gets its own batch's loss and gradients."""
        from jax.sharding import Mesh, PartitionSpec as P
        tokens, params, system, plain = (
            head_setup.tokens, head_setup.params, head_setup.system,
            head_setup.plain)
        mesh = Mesh(np.array(devices[:2]), ("data",))
        labels = jnp.asarray(np.concatenate([
            _label_case("per_row_15pct"),                   # 76 <= 128
            _label_case("cap_plus_1", seed=11)])            # 129 > 128
            .reshape(2 * HEAD_B, HEAD_S))
        tokens2 = jnp.concatenate([tokens, tokens[::-1]])

        def local(params, tokens, labels):
            loss, grads = jax.value_and_grad(system)(params, tokens,
                                                     labels, 0.0)
            return jax.tree_util.tree_map(lambda x: x[None], (loss, grads))

        loss, grads = jax.jit(jax.shard_map(
            local, mesh=mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=P("data"), check_vma=False))(params, tokens2, labels)
        for shard in range(2):
            rows = slice(shard * HEAD_B, (shard + 1) * HEAD_B)
            got = jax.tree_util.tree_map(lambda x: x[shard], (loss, grads))
            _assert_same(got, jax.value_and_grad(plain)(
                params, tokens2[rows], labels[rows], 0.0))

    def test_capacity_rule(self):
        from apex_tpu.models.transformer import _head_capacity
        # a quarter of the rows in whole 128-row blocks, never under one
        assert _head_capacity(16 * 512) == 2048     # the benchmark's cell
        assert _head_capacity(2 * 512) == 256       # its reference's rows
        assert _head_capacity(HEAD_ROWS) == HEAD_CAP
        assert _head_capacity(1000) == 128 and _head_capacity(16) == 128
        assert _head_capacity(64 * 128) == 2048     # phase 1: 20 of 128

    def test_lowered_head(self):
        """What the compiled step rests on: one conditional a side of the
        differentiation, a vocabulary GEMM of ``cap`` rows in the gathered
        branch, nothing the size of the logits out of either conditional,
        and both scopes in the lowered text."""
        from apex_tpu.models.transformer import _mlm_head
        hidden = jnp.ones((HEAD_ROWS, 32))
        emb = jnp.ones((HEAD_V, 32))
        labels = jnp.asarray(_label_case("per_row_15pct"))

        def head(hidden, emb):
            return _mlm_head(hidden, emb, labels, 0.0)

        def conds(fn):
            """The conditionals of ``fn``, not those inside one's branches
            or inside an interpreted kernel."""
            found = []

            def walk(jaxpr):
                for e in jaxpr.eqns:
                    if e.primitive.name == "cond":
                        found.append(e)
                    elif e.primitive.name != "pallas_call":
                        for sub in jax.core.jaxprs_in_params(e.params):
                            walk(sub)
            walk(jax.make_jaxpr(fn)(hidden, emb).jaxpr)
            return found

        def dots(jaxpr):
            return [tuple(e.outvars[0].aval.shape) for e in jaxpr.eqns
                    if e.primitive.name == "dot_general"]

        fwd, = conds(head)
        assert [v.aval.shape for v in fwd.outvars] == [()]
        full, gathered = (b.jaxpr for b in fwd.params["branches"])
        assert dots(gathered) == [(HEAD_CAP, HEAD_V)]
        assert dots(full) == [(HEAD_ROWS, HEAD_V)]
        # differentiated: the forward's, and one more for the backward
        fwd2, bwd = conds(jax.grad(head, argnums=(0, 1)))
        assert [v.aval.shape for v in fwd2.outvars] == [()]
        assert [v.aval.shape for v in bwd.outvars] == [
            (HEAD_ROWS, 32), (HEAD_V, 32)]
        _, gathered_bwd = (b.jaxpr for b in bwd.params["branches"])
        assert sorted(dots(gathered_bwd)) == [
            (HEAD_CAP, 32), (HEAD_CAP, HEAD_V), (HEAD_V, 32)]
        text = jax.jit(jax.value_and_grad(head)).lower(
            hidden, emb).as_text(debug_info=True)
        for scope in ("mlm/head_gathered", "mlm/head_full"):
            for side in (f"_fun/{scope}/", f"_fun/jvp({scope})/",
                         f"_fun/transpose(jvp({scope}))/"):
                assert side in text, side

    def test_few_rows_take_the_full_head_with_no_conditional(self):
        from apex_tpu.models.transformer import _mlm_head
        hidden, emb = jnp.ones((64, 32)), jnp.ones((HEAD_V, 32))
        labels = jnp.zeros((64,), jnp.int32)
        jaxpr = jax.make_jaxpr(
            lambda h, e: _mlm_head(h, e, labels, 0.0))(hidden, emb)
        assert "cond" not in {e.primitive.name for e in jaxpr.jaxpr.eqns}


class TestDCGAN:
    def test_generator_shapes(self):
        g = models.Generator(nz=16, ngf=8, nc=3)
        z = jnp.ones((2, 1, 1, 16))
        variables = g.init(jax.random.PRNGKey(0), z, train=False)
        img = g.apply(variables, z, train=False)
        assert img.shape == (2, 64, 64, 3)
        assert bool(jnp.all(jnp.abs(img) <= 1.0))

    def test_discriminator_shapes(self):
        d = models.Discriminator(ndf=8, nc=3)
        x = jnp.ones((2, 64, 64, 3))
        variables = d.init(jax.random.PRNGKey(0), x, train=False)
        logit = d.apply(variables, x, train=False)
        assert logit.shape == (2,)


class TestGraftEntry:
    @pytest.mark.slow       # ~21s on CPU CI: full multichip dryrun
    def test_dryrun_multichip_8(self):
        """The driver contract: 8-virtual-device full training step."""
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "graft_entry", "__graft_entry__.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.dryrun_multichip(8)


class TestStemConv:
    def test_s2d_matches_plain_conv(self):
        from apex_tpu.models.resnet import _StemConv
        rng = np.random.RandomState(20)
        x = jnp.asarray(rng.randn(2, 32, 32, 3).astype(np.float32))
        s2d = _StemConv(16, space_to_depth=True)
        ref = _StemConv(16, space_to_depth=False)
        v = s2d.init(jax.random.PRNGKey(0), x)
        np.testing.assert_allclose(
            np.asarray(s2d.apply(v, x)), np.asarray(ref.apply(v, x)),
            atol=2e-5)
        g1 = jax.grad(lambda v_: jnp.sum(jnp.sin(s2d.apply(v_, x))))(v)
        g0 = jax.grad(lambda v_: jnp.sum(jnp.sin(ref.apply(v_, x))))(v)
        np.testing.assert_allclose(
            np.asarray(g1["params"]["kernel"]),
            np.asarray(g0["params"]["kernel"]), atol=2e-4)

    def test_stem_half_under_auto_cast(self):
        """The custom stem must be on the O1 whitelist like nn.Conv —
        auto_cast runs it in the half dtype."""
        from apex_tpu import amp
        from apex_tpu.models.resnet import _StemConv
        x = jnp.ones((1, 8, 8, 3), jnp.float32)
        m = _StemConv(4)
        v = m.init(jax.random.PRNGKey(0), x)
        policy = amp.Policy.from_opt_level("O1")
        with amp.auto_cast(policy):
            y = m.apply(v, x)
        assert y.dtype == jnp.bfloat16
        assert m.apply(v, x).dtype == jnp.float32  # outside: fp32
