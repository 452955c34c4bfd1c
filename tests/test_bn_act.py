"""Fused BN(+add)(+relu) unit: custom VJP vs plain-autodiff oracle.

The reference validates its fused BN kernels against torch.nn.BatchNorm
outputs and grads (`tests/L0/run_optimizers/..`, groupbn unit tests);
here the oracle is the same math built from jnp primitives and
differentiated by JAX.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.bn_act import (
    FusedBNAct, bn_act_reference, bn_act_train, bn_add_act_train, make_cfg,
)


def _rand(shape, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32), dtype)


@pytest.mark.parametrize("relu", [True, False])
def test_forward_matches_reference(relu):
    x = _rand((4, 6, 6, 16))
    scale = _rand((16,), 1) * 0.5 + 1.0
    bias = _rand((16,), 2) * 0.1
    cfg = make_cfg(relu=relu)
    z, mean, var, count = bn_act_train(x, scale, bias, cfg)
    zr, mr, vr = bn_act_reference(x, scale, bias, relu=relu)
    np.testing.assert_allclose(z, zr, atol=1e-5)
    np.testing.assert_allclose(mean, mr, atol=1e-6)
    np.testing.assert_allclose(var, vr, atol=1e-6)
    assert float(count) == 4 * 6 * 6


@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("with_residual", [True, False])
def test_grads_match_autodiff(relu, with_residual):
    x = _rand((4, 6, 6, 16))
    r = _rand((4, 6, 6, 16), 7) * 0.3
    scale = _rand((16,), 1) * 0.5 + 1.0
    bias = _rand((16,), 2) * 0.1
    g = _rand((4, 6, 6, 16), 3)  # upstream cotangent
    cfg = make_cfg(relu=relu)

    if with_residual:
        def fused(x, r, s, b):
            z, *_ = bn_add_act_train(x, r, s, b, cfg)
            return jnp.sum(z * g)

        def oracle(x, r, s, b):
            z, _, _ = bn_act_reference(x, s, b, residual=r, relu=relu)
            return jnp.sum(z * g)

        got = jax.grad(fused, argnums=(0, 1, 2, 3))(x, r, scale, bias)
        want = jax.grad(oracle, argnums=(0, 1, 2, 3))(x, r, scale, bias)
    else:
        def fused(x, s, b):
            z, *_ = bn_act_train(x, s, b, cfg)
            return jnp.sum(z * g)

        def oracle(x, s, b):
            z, _, _ = bn_act_reference(x, s, b, relu=relu)
            return jnp.sum(z * g)

        got = jax.grad(fused, argnums=(0, 1, 2))(x, scale, bias)
        want = jax.grad(oracle, argnums=(0, 1, 2))(x, scale, bias)

    for gg, ww in zip(got, want):
        np.testing.assert_allclose(gg, ww, atol=2e-4, rtol=1e-4)


def test_grads_zero_init_scale():
    """The zero-init final-BN case (identity residual at init): grads
    must match autodiff when scale == 0 (mask comes from z > 0)."""
    x = _rand((2, 4, 4, 8))
    r = _rand((2, 4, 4, 8), 5)
    scale = jnp.zeros((8,))
    bias = jnp.zeros((8,))
    g = _rand((2, 4, 4, 8), 3)
    cfg = make_cfg(relu=True)

    def fused(x, r, s, b):
        z, *_ = bn_add_act_train(x, r, s, b, cfg)
        return jnp.sum(z * g)

    def oracle(x, r, s, b):
        z, _, _ = bn_act_reference(x, s, b, residual=r, relu=True)
        return jnp.sum(z * g)

    got = jax.grad(fused, argnums=(0, 1, 2, 3))(x, r, scale, bias)
    want = jax.grad(oracle, argnums=(0, 1, 2, 3))(x, r, scale, bias)
    for gg, ww in zip(got, want):
        np.testing.assert_allclose(gg, ww, atol=2e-4, rtol=1e-4)


def test_sync_grads_match_single_device(mesh8):
    """dp-sharded fused BN over the mesh == one-device BN on the full
    batch — forward and dx (the SyncBN contract, `two_gpu_unit_test.py`
    semantics)."""
    from jax.sharding import PartitionSpec as P

    x = _rand((16, 4, 4, 8))
    scale = _rand((8,), 1) * 0.5 + 1.0
    bias = _rand((8,), 2) * 0.1
    g = _rand((16, 4, 4, 8), 3)
    cfg1 = make_cfg(relu=True)
    cfgN = make_cfg(relu=True, axis_name="data")

    def single(x, s, b):
        z, *_ = bn_act_train(x, s, b, cfg1)
        return jnp.sum(z * g)

    want_val, want = jax.value_and_grad(single, argnums=(0, 1, 2))(
        x, scale, bias)

    def shard_step(x, s, b, g):
        # NB: the loss stays *local* under grad — the unit's backward
        # psums the channel sums itself, so each shard feeding its local
        # cotangent yields the exact global grads (psum-of-loss through
        # autodiff would double-count under check_vma=False)
        def local(x, s, b):
            z, *_ = bn_act_train(x, s, b, cfgN)
            return jnp.sum(z * g)
        val, grads = jax.value_and_grad(local, argnums=(0, 1, 2))(x, s, b)
        return jax.lax.psum(val, "data"), grads

    mapped = jax.shard_map(
        shard_step, mesh=mesh8,
        in_specs=(P("data"), P(), P(), P("data")),
        out_specs=(P(), (P("data"), P(), P())), check_vma=False)
    got_val, got = jax.jit(mapped)(x, scale, bias, g)

    np.testing.assert_allclose(got_val, want_val, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(got[0], want[0], atol=2e-4, rtol=1e-4)
    # param grads are psum'd inside autodiff's transpose of the stat
    # gather; each shard holds the full-batch grad
    np.testing.assert_allclose(got[1], want[1], atol=2e-3, rtol=1e-4)
    np.testing.assert_allclose(got[2], want[2], atol=2e-3, rtol=1e-4)


def test_module_running_stats_and_eval():
    x = _rand((8, 5, 5, 12))
    mod = FusedBNAct(num_features=12, relu=True, momentum=0.9)
    variables = mod.init(jax.random.PRNGKey(0), x)
    z, mut = mod.apply(variables, x, train=True, mutable=["batch_stats"])
    stats = mut["batch_stats"]
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.var(x, axis=(0, 1, 2))
    n = 8 * 5 * 5
    np.testing.assert_allclose(stats["mean"], 0.1 * mean, atol=1e-5)
    np.testing.assert_allclose(stats["var"],
                               0.9 + 0.1 * var * n / (n - 1), atol=1e-5)
    # eval path uses running stats
    z_eval = mod.apply({"params": variables["params"],
                        "batch_stats": stats}, x, train=False)
    assert z_eval.shape == x.shape


@pytest.mark.slow           # ~85s pair on CPU CI: full-model trajectories
@pytest.mark.parametrize("arch", ["resnet18", "resnet50"])
def test_resnet_fused_matches_oracle(arch):
    """Full-model check: fused-BN ResNet loss and input grad equal the
    plain-autodiff model (param trees differ; values must not)."""
    from apex_tpu import models

    ctor = models.ResNet18 if arch == "resnet18" else models.ResNet50
    x = _rand((2, 32, 32, 3))
    y = jnp.asarray([1, 3])

    outs = {}
    leaves_fused = None
    for fused in (True, False):
        model = ctor(num_classes=10, fused_bn=fused)
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        leaves, treedef = jax.tree_util.tree_flatten(variables)
        if fused:
            leaves_fused = leaves
        else:
            # graft the fused-init values onto the oracle tree: the two
            # structures differ only in the BN submodule name, so the
            # sorted leaf order (and every shape) lines up
            assert len(leaves) == len(leaves_fused)
            for a, b in zip(leaves, leaves_fused):
                assert a.shape == b.shape
            variables = jax.tree_util.tree_unflatten(treedef, leaves_fused)

        def loss_fn(xb, variables=variables, model=model):
            logits, _ = model.apply(variables, xb, train=True,
                                    mutable=["batch_stats"])
            return jnp.mean(
                -jax.nn.log_softmax(logits)[jnp.arange(2), y])

        outs[fused] = jax.value_and_grad(loss_fn)(x)

    np.testing.assert_allclose(outs[True][0], outs[False][0],
                               atol=1e-4, rtol=1e-4)
    # isolated relu-threshold ties can flip masks between the two
    # formulations (fp32 reassociation); allow a few small outliers
    np.testing.assert_allclose(outs[True][1], outs[False][1],
                               atol=5e-3, rtol=1e-2)


def _block_vars(block, x, seed):
    """Variables of ``block`` with every leaf random (the zero-init of
    the last BN scale would silence every gradient upstream of it)."""
    variables = block.init(jax.random.PRNGKey(0), x, train=True)
    leaves, treedef = jax.tree_util.tree_flatten(variables["params"])
    rng = np.random.RandomState(seed)
    rand = [jnp.asarray((rng.randn(*leaf.shape) * 0.3
                         + (1.0 if leaf.ndim == 1 else 0.0)), jnp.float32)
            for leaf in leaves]
    return {"params": jax.tree_util.tree_unflatten(treedef, rand),
            "batch_stats": variables["batch_stats"]}


@pytest.mark.parametrize("channels_match", [True, False])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("kind", ["bottleneck", "basic"])
def test_block_grads_match_flax_oracle(kind, stride, channels_match):
    """A residual block on the fused BN units (``fused_bn=True``, the
    hand-written backward) against the same block on flax BatchNorm under
    plain autodiff: output, batch statistics, and the gradient of every
    parameter and of the input. A block projects its residual when the
    stride or the channel count changes."""
    from apex_tpu.models.resnet import BasicBlock, BottleneckBlock

    feats = 8
    ctor, out_ch, proj_conv = ((BottleneckBlock, 4 * feats, "Conv_3")
                               if kind == "bottleneck"
                               else (BasicBlock, feats, "Conv_2"))
    cin = out_ch if channels_match else 12
    b, hw = 4, 8
    x = _rand((b, hw, hw, cin), 3)
    g = _rand((b, hw // stride, hw // stride, out_ch), 4)

    outs = {}
    for fused in (True, False):
        block = ctor(feats, (stride, stride), fused_bn=fused)
        variables = _block_vars(block, x, seed=5)
        assert ((proj_conv in variables["params"])
                == (stride == 2 or not channels_match))

        def loss_fn(params, xb, block=block, variables=variables):
            z, mut = block.apply(
                {"params": params, "batch_stats": variables["batch_stats"]},
                xb, train=True, mutable=["batch_stats"])
            return jnp.sum(z * g), (z, mut["batch_stats"])

        (_, (z, stats)), grads = jax.jit(jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True))(variables["params"], x)
        outs[fused] = (z, stats, grads)

    (z_f, stats_f, grads_f), (z_o, stats_o, grads_o) = outs[True], outs[False]
    assert float(jnp.mean(z_o > 0)) > 0.2      # the ReLUs cut both ways
    np.testing.assert_allclose(z_f, z_o, atol=1e-4, rtol=1e-4)
    # the two trees differ in the BN submodule's name only, so their
    # sorted leaves line up
    for a, o in zip(jax.tree_util.tree_leaves(grads_f),
                    jax.tree_util.tree_leaves(grads_o), strict=True):
        np.testing.assert_allclose(a, o, atol=2e-3, rtol=2e-3)
    # running mean: the same EMA. Running variance: the fused unit feeds
    # the unbiased batch variance (torch), flax the biased one; undo the
    # EMA (momentum 0.9 from 1.0) and the n / (n - 1) before comparing
    for (path, a), o in zip(
            jax.tree_util.tree_leaves_with_path(stats_f),
            jax.tree_util.tree_leaves(stats_o), strict=True):
        keys = [k.key for k in path]
        if keys[-1] == "mean":
            np.testing.assert_allclose(a, o, atol=1e-5, rtol=1e-5)
            continue
        at_input = kind == "bottleneck" and keys[0] == "_BN_0"
        n = b * (hw if at_input else hw // stride) ** 2
        batch_var = lambda ra: (ra - 0.9) / 0.1
        np.testing.assert_allclose(batch_var(a) * (n - 1) / n, batch_var(o),
                                   atol=1e-4, rtol=1e-4)
