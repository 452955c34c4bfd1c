"""Fused LayerNorm / MLP / xentropy kernels vs jnp oracles.

Mirrors `tests/L0/run_fused_layer_norm/test_fused_layer_norm.py` (fused vs
torch.nn.LayerNorm fwd+bwd), `tests/L0/run_mlp/test_mlp.py` (MLP vs
nn.Sequential fwd+bwd), and `apex/contrib/test/test_label_smoothing.py`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import ops


class TestFusedLayerNorm:
    @pytest.mark.parametrize("shape", [(16, 32, 64), (8, 768), (4, 7, 129),
                                       (3, 50)])
    def test_forward_affine(self, shape):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        w = jnp.asarray(rng.rand(shape[-1]).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(shape[-1]).astype(np.float32))
        got = ops.fused_layer_norm_affine(x, w, b, 1e-5)
        ref = ops.layer_norm_reference(x, w, b, 1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_forward_no_affine(self):
        rng = np.random.RandomState(1)
        x = jnp.asarray(rng.randn(6, 200).astype(np.float32))
        got = ops.fused_layer_norm(x, 1e-5)
        ref = ops.layer_norm_reference(x, None, None, 1e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    @pytest.mark.parametrize("shape", [(8, 768), (4, 7, 129)])
    def test_backward_matches_autodiff(self, shape):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        w = jnp.asarray(rng.rand(shape[-1]).astype(np.float32) + 0.5)
        b = jnp.asarray(rng.randn(shape[-1]).astype(np.float32))

        def loss_fused(x_, w_, b_):
            return jnp.sum(jnp.square(
                ops.fused_layer_norm_affine(x_, w_, b_, 1e-5)))

        def loss_ref(x_, w_, b_):
            return jnp.sum(jnp.square(
                ops.layer_norm_reference(x_, w_, b_, 1e-5)))

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, w, b)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
        for a, e, name in zip(gf, gr, "xwb"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(e), atol=2e-4,
                err_msg=f"grad {name}")

    def test_bf16_io(self):
        rng = np.random.RandomState(3)
        x = jnp.asarray(rng.randn(8, 256).astype(np.float32)
                        ).astype(jnp.bfloat16)
        w = jnp.ones((256,), jnp.float32)
        b = jnp.zeros((256,), jnp.float32)
        got = ops.fused_layer_norm_affine(x, w, b, 1e-5)
        assert got.dtype == jnp.bfloat16
        ref = ops.layer_norm_reference(x, w, b, 1e-5)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=2e-2)

    def test_module(self):
        ln = ops.FusedLayerNorm(64)
        x = jnp.ones((4, 64))
        variables = ln.init(jax.random.PRNGKey(0), x)
        y = ln.apply(variables, x)
        assert y.shape == (4, 64)
        np.testing.assert_allclose(np.asarray(y), 0.0, atol=1e-5)


class TestFusedMLP:
    @pytest.mark.parametrize("activation", ["relu", "sigmoid", "none"])
    def test_forward(self, activation):
        rng = np.random.RandomState(4)
        sizes = [39, 128, 57]
        x = jnp.asarray(rng.randn(10, sizes[0]).astype(np.float32))
        ws = tuple(jnp.asarray(
            (rng.randn(sizes[i], sizes[i + 1]) / np.sqrt(sizes[i]))
            .astype(np.float32)) for i in range(len(sizes) - 1))
        bs = tuple(jnp.asarray(rng.randn(sizes[i + 1]).astype(np.float32)
                               * 0.1) for i in range(len(sizes) - 1))
        got = ops.fused_mlp(x, ws, bs, activation)
        ref = ops.mlp_reference(x, ws, bs, activation)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-4)

    def test_no_bias(self):
        rng = np.random.RandomState(5)
        x = jnp.asarray(rng.randn(6, 16).astype(np.float32))
        ws = (jnp.asarray(rng.randn(16, 24).astype(np.float32)),)
        got = ops.fused_mlp(x, ws, None, "relu")
        ref = ops.mlp_reference(x, ws, None, "relu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-5)

    def test_backward(self):
        rng = np.random.RandomState(6)
        sizes = [20, 64, 12]
        x = jnp.asarray(rng.randn(8, sizes[0]).astype(np.float32))
        ws = tuple(jnp.asarray(
            (rng.randn(sizes[i], sizes[i + 1]) / np.sqrt(sizes[i]))
            .astype(np.float32)) for i in range(2))
        bs = tuple(jnp.asarray(rng.randn(sizes[i + 1]).astype(np.float32)
                               * 0.1) for i in range(2))

        def lf(x_, ws_, bs_):
            return jnp.sum(jnp.sin(ops.fused_mlp(x_, ws_, bs_, "relu")))

        def lr(x_, ws_, bs_):
            return jnp.sum(jnp.sin(ops.mlp_reference(x_, ws_, bs_, "relu")))

        gf = jax.grad(lf, argnums=(0, 1, 2))(x, ws, bs)
        gr = jax.grad(lr, argnums=(0, 1, 2))(x, ws, bs)
        for a, e in zip(jax.tree_util.tree_leaves(gf),
                        jax.tree_util.tree_leaves(gr)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=1e-4)

    def test_module_params(self):
        mlp = ops.MLP([10, 20, 5], bias=True, activation="relu")
        x = jnp.ones((3, 10))
        variables = mlp.init(jax.random.PRNGKey(0), x)
        names = set(variables["params"].keys())
        assert names == {"weight_0", "weight_1", "bias_0", "bias_1"}
        y = mlp.apply(variables, x)
        assert y.shape == (3, 5)
        assert bool(jnp.all(y >= 0))  # trailing relu, like the reference


class TestSoftmaxCrossEntropy:
    @pytest.mark.parametrize("smoothing", [0.0, 0.1])
    @pytest.mark.parametrize("vocab", [100, 128, 1000])
    def test_forward(self, smoothing, vocab):
        rng = np.random.RandomState(7)
        x = jnp.asarray(rng.randn(12, vocab).astype(np.float32) * 3)
        labels = jnp.asarray(rng.randint(0, vocab, 12), jnp.int32)
        got = ops.softmax_cross_entropy_loss(x, labels, smoothing)
        ref = ops.softmax_cross_entropy_reference(x, labels, smoothing)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("smoothing", [0.0, 0.15])
    def test_backward(self, smoothing):
        rng = np.random.RandomState(8)
        x = jnp.asarray(rng.randn(9, 257).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 257, 9), jnp.int32)

        def lf(x_):
            return jnp.sum(ops.softmax_cross_entropy_loss(
                x_, labels, smoothing) * 1.7)

        def lr(x_):
            return jnp.sum(ops.softmax_cross_entropy_reference(
                x_, labels, smoothing) * 1.7)

        gf = jax.grad(lf)(x)
        gr = jax.grad(lr)(x)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=1e-5)

    def test_ignored_labels(self):
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(4, 50).astype(np.float32))
        labels = jnp.asarray([3, -1, 7, -1], jnp.int32)
        loss = ops.softmax_cross_entropy_loss(x, labels, 0.0)
        assert float(loss[1]) == 0.0 and float(loss[3]) == 0.0
        g = jax.grad(lambda x_: jnp.sum(
            ops.softmax_cross_entropy_loss(x_, labels, 0.0)))(x)
        np.testing.assert_allclose(np.asarray(g)[1], 0.0)
        np.testing.assert_allclose(np.asarray(g)[3], 0.0)

    def test_batched_shape(self):
        rng = np.random.RandomState(10)
        x = jnp.asarray(rng.randn(2, 5, 64).astype(np.float32))
        labels = jnp.asarray(rng.randint(0, 64, (2, 5)), jnp.int32)
        loss = ops.softmax_cross_entropy_loss(x, labels, 0.1)
        assert loss.shape == (2, 5)

    @pytest.mark.parametrize("vocab, rows, forward, backward", [
        (30522, 1280, 64, 32), (20480, 8192, 96, 48), (18992, 8192, 96, 48),
        (8192, 16384, 128, 128), (8192, 16000, 160, 128), (8192, 0, 240, 128)],
        ids=["bert_large", "kimi_linear", "qwen3_next", "lfm2_moe",
             "rows_with_another_divisor", "rows_unknown"])
    def test_row_block_of_the_cells_vocabularies(self, vocab, rows, forward,
                                                 backward):
        """bfloat16 logits as O1 makes them. The three cells before PR 34
        keep the rows the budget gives; a vocabulary of 8192 would take 256
        rows forward, whose float32 working copy does not fit the v5e's
        scoped VMEM beside them: the largest multiple of 16 that fits (240)
        and divides the rows, so that no padded copy is made."""
        from apex_tpu.ops.xentropy import LANES, VMEM_FITS, _row_block
        padded = -(-vocab // LANES) * LANES
        got = [_row_block(padded, bufs, 2, rows) for bufs in (1, 2)]
        assert got == [forward, backward]
        for bufs, block in zip((1, 2), got):
            assert block % 16 == 0
            assert (2 * bufs * 2 + 4) * padded * block <= VMEM_FITS


class TestGroupBN:
    def test_single_device_module(self):
        bn = ops.BatchNorm2d_NHWC(8, fuse_relu=True)
        rng = np.random.RandomState(11)
        x = jnp.asarray(rng.randn(4, 6, 6, 8).astype(np.float32))
        variables = bn.init(jax.random.PRNGKey(0), x)
        y, mut = bn.apply(variables, x, mutable=["batch_stats"])
        assert bool(jnp.all(y >= 0))
        mean = np.asarray(x).mean(axis=(0, 1, 2))
        np.testing.assert_allclose(
            np.asarray(mut["batch_stats"]["mean"]), 0.1 * mean, atol=1e-5)

    def test_bn_group_spec(self):
        assert ops.bn_group_spec(8, 2) == [[0, 1], [2, 3], [4, 5], [6, 7]]
        # group of 1 = per-device stats, NOT None (None = whole axis)
        assert ops.bn_group_spec(8, 1) == [[i] for i in range(8)]
        assert ops.bn_group_spec(8, 8) is None
