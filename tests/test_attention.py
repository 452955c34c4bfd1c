"""Fused (flash) attention vs the default impl.

Mirrors `apex/contrib/test/multihead_attn/*`: fast kernel outputs and
input grads match ``impl='default'`` within tolerance, for self/encdec,
additive masks, norm-add variants.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import ops
from apex_tpu.ops import attention as A


def rand_qkv(rng, b, s, h, d, sk=None):
    sk = sk or s
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, sk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, sk, h, d).astype(np.float32))
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("s,d", [(64, 32), (128, 64), (200, 48)])
    def test_forward_matches_reference(self, s, d):
        rng = np.random.RandomState(0)
        q, k, v = rand_qkv(rng, 2, s, 2, d)
        got = A.flash_attention(q, k, v)
        ref = A.attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_causal(self):
        rng = np.random.RandomState(1)
        q, k, v = rand_qkv(rng, 1, 96, 2, 32)
        got = A.flash_attention(q, k, v, causal=True)
        ref = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_additive_bias(self):
        rng = np.random.RandomState(2)
        q, k, v = rand_qkv(rng, 2, 64, 2, 32)
        # padding mask as additive bias on keys
        bias = jnp.where(jnp.arange(64)[None, None, None, :] < 48,
                         0.0, -1e9)
        got = A.flash_attention(q, k, v, bias=bias)
        ref = A.attention_reference(q, k, v, bias=bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_cross_attention_lengths(self):
        rng = np.random.RandomState(3)
        q, k, v = rand_qkv(rng, 2, 40, 2, 32, sk=72)
        got = A.flash_attention(q, k, v)
        ref = A.attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_backward_matches_reference(self, causal):
        rng = np.random.RandomState(4)
        q, k, v = rand_qkv(rng, 2, 72, 2, 32)

        def lf(q_, k_, v_):
            return jnp.sum(jnp.sin(
                A.flash_attention(q_, k_, v_, causal=causal)))

        def lr(q_, k_, v_):
            return jnp.sum(jnp.sin(
                A.attention_reference(q_, k_, v_, causal=causal)))

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
        for a, e, name in zip(gf, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=5e-5, err_msg=f"d{name}")

    def test_backward_with_bias(self):
        rng = np.random.RandomState(5)
        q, k, v = rand_qkv(rng, 1, 64, 2, 32)
        bias = jnp.where(jnp.arange(64)[None, None, None, :] < 50,
                         0.0, -1e9)

        gf = jax.grad(lambda q_: jnp.sum(
            A.flash_attention(q_, k, v, bias=bias)))(q)
        gr = jax.grad(lambda q_: jnp.sum(
            A.attention_reference(q_, k, v, bias=bias)))(q)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5)

    def test_bf16(self):
        rng = np.random.RandomState(6)
        q, k, v = rand_qkv(rng, 1, 64, 2, 32)
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
        got = A.flash_attention(q, k, v)
        assert got.dtype == jnp.bfloat16
        ref = A.attention_reference(q, k, v)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            atol=2e-2)

    def test_long_sequence_blocks(self):
        """Multiple q and k blocks (S > block size) exercise the online
        renormalization."""
        rng = np.random.RandomState(7)
        q, k, v = rand_qkv(rng, 1, 384, 1, 32)
        got = A.flash_attention(q, k, v, block_q=128, block_k=128)
        ref = A.attention_reference(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)


def grouped_oracle(q, k, v, causal):
    """Dense softmax attention with q head ``i`` reading k/v head ``i //
    (H / H_kv)``, the mapping written out a head."""
    h, hkv, d = q.shape[2], k.shape[2], q.shape[3]
    out = []
    for i in range(h):
        j = i // (h // hkv)
        s_ = jnp.einsum("bqd,bkd->bqk", q[:, :, i], k[:, :, j]) / np.sqrt(d)
        if causal:
            s_ = jnp.where(np.tril(np.ones(s_.shape[-2:], bool)), s_, -1e30)
        out.append(jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s_, -1),
                              v[:, :, j]))
    return jnp.stack(out, 2)


class TestGroupedQueryAndHead256:
    """k and v with fewer heads than q (a divisor), and the head size of
    256 that Qwen3-Next's attention has: forward and all three gradients,
    ``dk`` and ``dv`` summed over a group and in k's own shape."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("h,hkv,d,s,blocks", [
        (4, 2, 32, 96, None), (8, 1, 64, 200, None),
        (16, 2, 256, 160, None),        # the cell's heads, one block
        (8, 1, 256, 300, (128, 128)),   # several blocks: the two-kernel form
        (2, 2, 256, 200, None),         # head 256, every head its own k/v
    ])
    def test_matches_a_dense_oracle(self, h, hkv, d, s, blocks, causal):
        rng = np.random.RandomState(8)
        q = jnp.asarray(rng.randn(2, s, h, d).astype(np.float32))
        k, v = (jnp.asarray(rng.randn(2, s, hkv, d).astype(np.float32))
                for _ in range(2))
        kw = dict(zip(("block_q", "block_k"), blocks)) if blocks else {}
        ours = lambda q, k, v: A.flash_attention(q, k, v, causal=causal, **kw)
        want = grouped_oracle(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(ours(q, k, v)),
                                   np.asarray(want), atol=3e-5)
        np.testing.assert_allclose(
            np.asarray(A.attention_reference(q, k, v, causal=causal)),
            np.asarray(want), atol=3e-5)
        loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
        got = jax.grad(loss(ours), argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(loss(lambda q, k, v: grouped_oracle(q, k, v, causal)),
                       argnums=(0, 1, 2))(q, k, v)
        for a, e, x, name in zip(got, ref, (q, k, v), "qkv"):
            assert a.shape == x.shape, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                       atol=2e-4, err_msg=f"d{name}")

    def test_bf16_grouped(self):
        rng = np.random.RandomState(9)
        q = jnp.asarray(rng.randn(1, 128, 16, 256), jnp.bfloat16)
        k, v = (jnp.asarray(rng.randn(1, 128, 2, 256), jnp.bfloat16)
                for _ in range(2))
        got = A.flash_attention(q, k, v, None, 1 / 16, True)
        assert got.dtype == jnp.bfloat16 and got.shape == q.shape
        want = grouped_oracle(*(x.astype(jnp.float32) for x in (q, k, v)),
                              True)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), atol=3e-2)


def _narrow_v_case(id_, d_v_native, s=512, h=2, hkv=2, d=192, d_v=128,
                   tiles=(128, 256), **kw):
    return pytest.param(dict(s=s, h=h, hkv=hkv, d=d, d_v=d_v, tiles=tiles,
                             native=d_v_native, kw=kw), id=id_)


class TestValueHeadSize:
    """``v`` with a smaller head size than q and k (latent attention: 128
    beside 192). The native multi-block backward kernels take it as it is;
    the forward kernel and every other path take it through the op's own
    zero-pad. Either way forward and the three gradients are the
    reference's, and the padded call's (what the model passed before) to
    float32 rounding."""

    @pytest.mark.parametrize("case", [
        # the cells' shape cut down: causal, several tiles, two heads a step
        _narrow_v_case("causal-multi-block-g2", True),
        _narrow_v_case("shared-kv-heads", True, h=4, hkv=2),
        _narrow_v_case("non-causal-padded-rows", True, s=450, causal=False),
        _narrow_v_case("dropout", True, dropout_rate=0.2, dropout_seed=3),
        _narrow_v_case("transposed-fallback", False, s=200, d=48, d_v=32,
                       tiles=(128, 128)),
        _narrow_v_case("single-block-fused", False, s=256,
                       tiles=(256, 256)),
        _narrow_v_case("bias", False, bias=True),
        _narrow_v_case("lse-variant", False, lse=True),
    ])
    def test_matches_the_reference_and_the_padded_call(self, case,
                                                       monkeypatch):
        s, h, hkv, d, d_v = (case[k] for k in ("s", "h", "hkv", "d", "d_v"))
        kw = dict(case["kw"])
        causal, lse = kw.pop("causal", True), kw.pop("lse", False)
        rng = np.random.RandomState(11)
        q = jnp.asarray(rng.randn(1, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(1, s, hkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(1, s, hkv, d_v), jnp.float32)
        w = jnp.asarray(rng.randn(1, s, h, d_v), jnp.float32)
        bias = (jnp.asarray(rng.randn(1, h, s, s), jnp.float32) * 0.3
                if kw.pop("bias", False) else None)
        widths = []             # v's head size at the native backward
        real = A._flash_bwd_nl

        def spy(q2, k2, v2, *a, **kw):
            widths.append(v2.shape[-1] // h)
            return real(q2, k2, v2, *a, **kw)

        monkeypatch.setattr(A, "_flash_bwd_nl", spy)

        def op(q, k, v):
            if lse:
                return A.flash_attention_lse(q, k, v, bias, None, causal,
                                             *case["tiles"])[0]
            return A.flash_attention(q, k, v, bias, None, causal,
                                     *case["tiles"], **kw)

        def padded(q, k, v):
            return op(q, k, jnp.pad(v, [(0, 0)] * 3 + [(0, d - d_v)]))[
                ..., :d_v]

        def reference(q, k, v):
            return A.attention_reference(q, k, v, bias, None, causal)

        def run(fn):
            return jax.jit(jax.value_and_grad(
                lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                argnums=(0, 1, 2)))(q, k, v), jax.jit(fn)(q, k, v)

        (_, got), o = run(op)
        assert (d_v in widths) == case["native"], widths
        assert o.shape == (1, s, h, d_v) and got[2].shape == v.shape
        (_, was), o_was = run(padded)
        for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *got),
                              (o_was, *was)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6, err_msg=name)
        if kw.get("dropout_rate"):
            return                  # the reference draws no keep mask
        (_, want), o_want = run(reference)
        for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *got),
                              (o_want, *want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, err_msg=name)

    @pytest.mark.parametrize("nh,d,d_v,g0,g", [
        (32, 192, 128, 2, 2),       # latent attention: v's 128 adds nothing
        (32, 192, 192, 2, 2),       # the same with v padded to 192
        (16, 64, 32, 4, 4),         # v's 32 asks for four heads a step
        (2, 64, 32, None, None),    # ... which two heads cannot give
    ])
    def test_native_head_group(self, nh, d, d_v, g0, g):
        """The smallest head group lane-aligns both head sizes; the forward
        ledger counts v, o and the accumulator at ``d_v``, and with ``d_v``
        equal to ``d`` it is the ledger without it."""
        assert A._native_g0(nh, d, d_v) == g0
        if g0 is None:
            return
        assert A._native_g(nh, d, 0.0, 1024, 256, 2, d_v=d_v) == g
        if d_v == d:
            assert A._native_g(nh, d, 0.0, 1024, 256, 2) == g


class TestMHAModules:
    @pytest.mark.parametrize("norm_add", [False, True])
    def test_self_attn_fast_vs_default(self, norm_add):
        rng = np.random.RandomState(8)
        x = jnp.asarray(rng.randn(2, 48, 64).astype(np.float32))
        fast = ops.SelfMultiheadAttn(64, 4, impl="fast",
                                     include_norm_add=norm_add)
        slow = ops.SelfMultiheadAttn(64, 4, impl="default",
                                     include_norm_add=norm_add)
        variables = fast.init(jax.random.PRNGKey(0), x)
        yf = fast.apply(variables, x)
        ys = slow.apply(variables, x)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(ys),
                                   atol=2e-4)

    def test_self_attn_separate_qkv(self):
        rng = np.random.RandomState(9)
        x = jnp.asarray(rng.randn(1, 32, 32).astype(np.float32))
        m = ops.SelfMultiheadAttn(32, 2, separate_qkv_params=True)
        variables = m.init(jax.random.PRNGKey(0), x)
        names = set(variables["params"].keys())
        assert {"q_proj", "k_proj", "v_proj", "out_proj"} <= names
        assert m.apply(variables, x).shape == x.shape

    def test_encdec_fast_vs_default(self):
        rng = np.random.RandomState(10)
        q = jnp.asarray(rng.randn(2, 24, 64).astype(np.float32))
        mem = jnp.asarray(rng.randn(2, 56, 64).astype(np.float32))
        fast = ops.EncdecMultiheadAttn(64, 4, impl="fast")
        slow = ops.EncdecMultiheadAttn(64, 4, impl="default")
        variables = fast.init(jax.random.PRNGKey(0), q, mem)
        yf = fast.apply(variables, q, mem)
        ys = slow.apply(variables, q, mem)
        np.testing.assert_allclose(np.asarray(yf), np.asarray(ys),
                                   atol=2e-4)

    def test_grad_through_module(self):
        rng = np.random.RandomState(11)
        x = jnp.asarray(rng.randn(1, 32, 32).astype(np.float32))
        m = ops.SelfMultiheadAttn(32, 2, impl="fast")
        variables = m.init(jax.random.PRNGKey(0), x)

        g = jax.grad(lambda v: jnp.sum(m.apply(v, x) ** 2))(variables)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(bool(jnp.all(jnp.isfinite(l))) for l in leaves)
        assert any(float(jnp.max(jnp.abs(l))) > 0 for l in leaves)

    def test_mask_softmax_dropout(self):
        rng = np.random.RandomState(12)
        s = jnp.asarray(rng.randn(2, 4, 16, 16).astype(np.float32))
        mask = jnp.asarray(rng.rand(2, 1, 16, 16) > 0.3)
        p = ops.mask_softmax_dropout(s, mask)
        sums = np.asarray(jnp.sum(p, axis=-1))
        np.testing.assert_allclose(sums, 1.0, atol=1e-5)
        assert bool(jnp.all(jnp.where(~mask, p == 0, True)))


class TestCausalCrossLength:
    def test_causal_cross_attention_alignment(self):
        """Bottom-right causal alignment for Sq != Sk (decode-style)."""
        rng = np.random.RandomState(13)
        q, k, v = rand_qkv(rng, 1, 8, 2, 32, sk=16)
        got = A.flash_attention(q, k, v, causal=True)
        ref = A.attention_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_causal_cross_backward(self):
        rng = np.random.RandomState(14)
        q, k, v = rand_qkv(rng, 1, 24, 2, 32, sk=40)
        gf = jax.grad(lambda k_: jnp.sum(
            A.flash_attention(q, k_, v, causal=True) ** 2))(k)
        gr = jax.grad(lambda k_: jnp.sum(
            A.attention_reference(q, k_, v, causal=True) ** 2))(k)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5)


class TestSoftmaxDropout:
    def test_single_softmax_dropout(self):
        """Dropout applies ONCE, to the probabilities (reference
        semantics) — mean output magnitude stays unbiased."""
        rng = np.random.RandomState(15)
        x = jnp.asarray(rng.randn(2, 32, 64).astype(np.float32))
        m = ops.SelfMultiheadAttn(64, 4, dropout=0.5, impl="fast")
        variables = m.init(jax.random.PRNGKey(0), x)
        y_det = m.apply(variables, x, deterministic=True)
        y_drop = m.apply(variables, x, deterministic=False,
                         rngs={"dropout": jax.random.PRNGKey(1)})
        # dropped path differs but is unbiased: mean ratio near 1
        assert not np.allclose(np.asarray(y_det), np.asarray(y_drop))
        r = float(jnp.mean(jnp.abs(y_drop)) / jnp.mean(jnp.abs(y_det)))
        assert 0.5 < r < 2.0


class TestBiasGradient:
    """Learned-bias cotangent (ADVICE round-1 #4): d/dbias of the fused
    path must match the jnp oracle — relative-position-bias training."""

    @pytest.mark.parametrize("bias_shape", [
        (1, 1, 64, 64),   # shared (ring-attention causal-offset shape)
        (1, 2, 64, 64),   # per-head (relative position bias)
        (2, 1, 64, 64),   # per-batch mask
        (2, 2, 64, 64),   # full
    ])
    def test_dbias_matches_reference(self, bias_shape):
        rng = np.random.RandomState(7)
        q, k, v = rand_qkv(rng, 2, 64, 2, 32)
        bias = jnp.asarray(rng.randn(*bias_shape).astype(np.float32))

        gf = jax.grad(lambda b_: jnp.sum(
            A.flash_attention(q, k, v, bias=b_)), )(bias)
        gr = jax.grad(lambda b_: jnp.sum(
            A.attention_reference(q, k, v, bias=b_)))(bias)
        assert gf.shape == bias.shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5)

    def test_dbias_causal(self):
        rng = np.random.RandomState(8)
        q, k, v = rand_qkv(rng, 1, 48, 2, 32)
        bias = jnp.asarray(rng.randn(1, 2, 48, 48).astype(np.float32))
        gf = jax.grad(lambda b_: jnp.sum(
            A.flash_attention(q, k, v, bias=b_, causal=True)))(bias)
        gr = jax.grad(lambda b_: jnp.sum(
            A.attention_reference(q, k, v, bias=b_, causal=True)))(bias)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5)

    def test_broadcast_bias_not_materialized(self):
        """The (1,1,S,S) bias must flow to the kernel ungrown — assert the
        jaxpr contains no (B*H, S, S)-sized broadcast of it."""
        # s must differ from the padded head dim (128) or the q/k/v
        # d-padding pad op's (B*H, S, 128) shape collides with the
        # (B*H, S, S) pattern this test greps for
        b, s, h, d = 4, 256, 4, 32
        rng = np.random.RandomState(9)
        q, k, v = rand_qkv(rng, b, s, h, d)
        bias = jnp.zeros((1, 1, s, s), jnp.float32)
        jaxpr = jax.make_jaxpr(
            lambda q_, k_, v_, b_: A.flash_attention(q_, k_, v_, bias=b_)
        )(q, k, v, bias)
        blown_up = f"{b * h},{s},{s}"
        assert blown_up not in str(jaxpr).replace(" ", ""), \
            "bias was broadcast to B*H copies before the kernel"


class TestFusedDropout:
    """In-kernel softmax dropout (the reference's fused Philox dropout,
    `apex/contrib/csrc/multihead_attn/dropout.h:1-308`). The mask is
    counter-based, so a dense jnp replica (`_keep_mask_dense`) lets us
    compare the kernel against an exact oracle — forward AND gradients."""

    def _oracle(self, q, k, v, seed, rate, bias=None, causal=False):
        """Reference attention applying the *same* mask the kernel
        generates, via the dense mask replica."""
        b, sq, h, d = q.shape
        sk = k.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32)) / np.sqrt(d)
        if bias is not None:
            s = s + bias.astype(jnp.float32)
        if causal:
            cm = np.tril(np.ones((sq, sk), bool), k=sk - sq)
            s = jnp.where(cm, s, A.NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        # apply the same tile cap the kernels use (shared definition —
        # the dropout mask is a function of block coordinates)
        cq, ck = A._block_cap(A.DEFAULT_BLOCK_Q, A.DEFAULT_BLOCK_K,
                              False, rate)
        bq = A._choose_block(cq, sq)
        bk = A._choose_block(ck, sk, lane=True)
        keep = A._keep_mask_dense(jnp.asarray(seed, jnp.int32), b, h,
                                  sq, sk, bq, bk, rate)
        keep = keep.reshape(b, h, sq, sk)
        pt = jnp.where(keep, p / (1.0 - rate), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", pt,
                          v.astype(jnp.float32)).astype(q.dtype)

    def test_forward_matches_masked_oracle(self):
        rng = np.random.RandomState(3)
        q, k, v = rand_qkv(rng, 2, 192, 2, 32)
        got = A.flash_attention(q, k, v, dropout_rate=0.25,
                                dropout_seed=7)
        ref = self._oracle(q, k, v, 7, 0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=2e-5)

    def test_grads_match_masked_oracle(self):
        rng = np.random.RandomState(4)
        q, k, v = rand_qkv(rng, 1, 128, 2, 32)

        def loss_fused(q_, k_, v_):
            o = A.flash_attention(q_, k_, v_, dropout_rate=0.3,
                                  dropout_seed=11)
            return jnp.sum(o * o)

        def loss_ref(q_, k_, v_):
            o = self._oracle(q_, k_, v_, 11, 0.3)
            return jnp.sum(o * o)

        gf = jax.grad(loss_fused, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)

    def test_dbias_with_dropout(self):
        rng = np.random.RandomState(5)
        q, k, v = rand_qkv(rng, 1, 64, 2, 32)
        bias = jnp.asarray(rng.randn(1, 2, 64, 64).astype(np.float32))
        gf = jax.grad(lambda b_: jnp.sum(A.flash_attention(
            q, k, v, bias=b_, dropout_rate=0.2, dropout_seed=13)))(bias)
        gr = jax.grad(lambda b_: jnp.sum(self._oracle(
            q, k, v, 13, 0.2, bias=b_)))(bias)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   atol=5e-5)

    def test_keep_rate_statistics(self):
        """Uniform scores (q=0) make every prob 1/S, so with v=1 the
        output row-sum directly reads off the kept fraction."""
        b, s, h, d = 2, 256, 2, 32
        rate = 0.3
        q = jnp.zeros((b, s, h, d), jnp.float32)
        k = jnp.zeros((b, s, h, d), jnp.float32)
        v = jnp.ones((b, s, h, d), jnp.float32)
        out = A.flash_attention(q, k, v, dropout_rate=rate,
                                dropout_seed=99)
        # out = kept_count / (S * keep_prob); recover mean keep fraction
        keep_frac = float(jnp.mean(out)) * (1.0 - rate)
        n = b * h * s * s
        sigma = np.sqrt(rate * (1 - rate) / n)
        assert abs(keep_frac - (1.0 - rate)) < 5 * sigma, \
            f"keep fraction {keep_frac} vs expected {1 - rate}"

    def test_seed_determinism(self):
        rng = np.random.RandomState(6)
        q, k, v = rand_qkv(rng, 1, 64, 2, 32)
        a1 = A.flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=1)
        a2 = A.flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=1)
        b2 = A.flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=2)
        assert bool(jnp.all(a1 == a2)), "same seed must be bitwise equal"
        assert not bool(jnp.all(a1 == b2)), "different seeds must differ"

    def test_module_keeps_fused_path_under_dropout(self):
        """Training with dropout>0 must NOT fall back to the O(S²) jnp
        path — the jaxpr of the training forward contains the kernel."""
        x = jnp.zeros((2, 64, 64), jnp.float32)
        m = ops.SelfMultiheadAttn(64, 4, dropout=0.1, impl="fast")
        variables = m.init(jax.random.PRNGKey(0), x)
        jaxpr = jax.make_jaxpr(lambda v_, x_: m.apply(
            v_, x_, deterministic=False,
            rngs={"dropout": jax.random.PRNGKey(1)}))(variables, x)
        assert "pallas_call" in str(jaxpr), \
            "fused kernel not used in training forward with dropout"

    def test_missing_seed_raises(self):
        rng = np.random.RandomState(7)
        q, k, v = rand_qkv(rng, 1, 32, 1, 32)
        with pytest.raises(ValueError, match="dropout_seed"):
            A.flash_attention(q, k, v, dropout_rate=0.5)


class TestNativeLayoutPath:
    """d=64-class shapes route through the native-layout kernels
    (heads sliced from the lane axis — see the native-kernel block in
    ops/attention.py); these pin the fwd, both bwd variants (fused
    single-sweep and two-kernel multi-block) and the dropout
    coordinate reconstruction against the same oracles the transposed
    path is held to. d=32/d=16 tests elsewhere cover the transposed
    fallback."""

    def _grads(self, fn, args, argn=(0, 1, 2)):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
            argnums=argn))(*args)

    @pytest.mark.parametrize("s,causal", [(128, False), (384, True)])
    def test_fused_single_sweep_bwd_matches_oracle(self, s, causal):
        # single-block grid -> the fused dq/dk/dv sweep
        rng = np.random.RandomState(5)
        q, k, v = rand_qkv(rng, 2, s, 4, 64)
        assert A._native_g0(4, 64) == 2

        def fn(q, k, v):
            return A.flash_attention(q, k, v, causal=causal)

        def ref(q, k, v):
            return A.attention_reference(q, k, v, causal=causal)

        np.testing.assert_allclose(jax.jit(fn)(q, k, v), ref(q, k, v),
                                   atol=2e-5, rtol=1e-5)
        for g, w in zip(self._grads(fn, (q, k, v)),
                        self._grads(ref, (q, k, v))):
            np.testing.assert_allclose(g, w, atol=5e-4, rtol=1e-3)

    def test_two_kernel_multiblock_bwd_matches_oracle(self):
        # force a multi-block grid (block_q/k < s) -> two-kernel path
        rng = np.random.RandomState(6)
        q, k, v = rand_qkv(rng, 1, 256, 4, 64)

        def fn(q, k, v):
            return A.flash_attention(q, k, v, causal=True, block_q=128,
                                     block_k=128)

        def ref(q, k, v):
            return A.attention_reference(q, k, v, causal=True)

        np.testing.assert_allclose(jax.jit(fn)(q, k, v), ref(q, k, v),
                                   atol=2e-5, rtol=1e-5)
        for g, w in zip(self._grads(fn, (q, k, v)),
                        self._grads(ref, (q, k, v))):
            np.testing.assert_allclose(g, w, atol=5e-4, rtol=1e-3)

    @pytest.mark.parametrize("s,bq", [(128, None), (256, 128)])
    def test_native_dropout_matches_dense_mask_oracle(self, s, bq):
        """gb = t·g + h must reproduce the dense replica's bh-row
        numbering — fwd values AND gradients, single- and multi-block."""
        rng = np.random.RandomState(7)
        q, k, v = rand_qkv(rng, 1, s, 4, 64)
        rate, seed = 0.3, 17
        kw = {} if bq is None else {"block_q": bq, "block_k": bq}

        def fn(q, k, v):
            return A.flash_attention(q, k, v, dropout_rate=rate,
                                     dropout_seed=seed, **kw)

        cq, ck = A._block_cap(kw.get("block_q", A.DEFAULT_BLOCK_Q),
                              kw.get("block_k", A.DEFAULT_BLOCK_K),
                              False, rate)
        bq_ = A._choose_block(cq, s)
        bk_ = A._choose_block(ck, s, lane=True)

        def ref(q, k, v):
            b, sq, h, d = q.shape
            sm = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
            p = jax.nn.softmax(sm, axis=-1)
            keep = A._keep_mask_dense(jnp.asarray(seed, jnp.int32), b,
                                      h, sq, sq, bq_, bk_, rate)
            pd = jnp.where(keep.reshape(b, h, sq, sq), p / (1 - rate),
                           0.0)
            return jnp.einsum("bhqk,bkhd->bqhd", pd, v)

        np.testing.assert_allclose(jax.jit(fn)(q, k, v), ref(q, k, v),
                                   atol=2e-5, rtol=1e-5)
        for g, w in zip(self._grads(fn, (q, k, v)),
                        self._grads(ref, (q, k, v))):
            np.testing.assert_allclose(g, w, atol=5e-4, rtol=1e-3)


class TestCausalOffset:
    """flash_attention(causal_offset=...) vs the additive-mask oracle:
    the offset (a traced scalar) must reproduce exactly the mask a
    caller would build — native path (d=64) and bias-fallback path
    (d=32), lse variant included (the ring-hop building block)."""

    @pytest.mark.parametrize("d", [64, 32])
    @pytest.mark.parametrize("off", [0, 64, 4096])
    def test_matches_offset_bias_oracle(self, d, off):
        rng = np.random.RandomState(11)
        q, k, v = rand_qkv(rng, 1, 128, 4, d)

        def fn(q, k, v, off_):
            return A.flash_attention(q, k, v, causal=True,
                                     causal_offset=off_)

        rows = np.arange(128)[:, None] + off
        cols = np.arange(128)[None, :]
        bias = jnp.asarray(np.where(rows >= cols, 0.0, A.NEG_INF),
                           jnp.float32)[None, None]

        def ref(q, k, v):
            return A.attention_reference(q, k, v, bias=bias)

        got = jax.jit(fn)(q, k, v, jnp.int32(off))
        np.testing.assert_allclose(got, ref(q, k, v), atol=2e-5,
                                   rtol=1e-5)
        g1 = jax.jit(jax.grad(
            lambda q, k, v, o_: jnp.sum(fn(q, k, v, o_) ** 2),
            argnums=(0, 1, 2)))(q, k, v, jnp.int32(off))
        g2 = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)

    def test_fully_masked_rows_finite_and_lse_guarded(self):
        """Negative offsets can leave early rows with NO valid keys.
        Those rows are out-of-contract (softmax over an empty set);
        what the framework guarantees is (a) finite outputs/gradients
        and (b) an lse of ~NEG_INF so ring attention's merge gives the
        hop zero weight — the guard `ring.py` relies on. Valid rows
        must still match the oracle exactly."""
        rng = np.random.RandomState(14)
        q, k, v = rand_qkv(rng, 1, 128, 2, 64)
        off = -96   # rows 0..95 fully masked
        o, lse = jax.jit(lambda q, k, v: A.flash_attention_lse(
            q, k, v, causal=True,
            causal_offset=jnp.int32(off)))(q, k, v)
        assert np.all(np.isfinite(np.asarray(o, np.float32)))
        # masked rows: merge weight exp(lse - lse_c) underflows to 0
        assert np.all(np.asarray(lse)[..., :96] < -1e29)
        assert np.all(np.asarray(lse)[..., 96:] > -1e4)
        # valid rows agree with the dense oracle
        rows = np.arange(128)[:, None] + off
        cols = np.arange(128)[None, :]
        bias = jnp.asarray(np.where(rows >= cols, 0.0, A.NEG_INF),
                           jnp.float32)[None, None]
        want = A.attention_reference(q, k, v, bias=bias)
        np.testing.assert_allclose(np.asarray(o)[:, 96:],
                                   np.asarray(want)[:, 96:], atol=2e-5,
                                   rtol=1e-5)
        g = jax.jit(jax.grad(lambda q: jnp.sum(A.flash_attention(
            q, k, v, causal=True,
            causal_offset=jnp.int32(off)) ** 2)))(q)
        assert np.all(np.isfinite(np.asarray(g, np.float32)))

    def test_lse_variant_offset(self):
        rng = np.random.RandomState(12)
        q, k, v = rand_qkv(rng, 1, 128, 2, 64)
        o1, lse1 = A.flash_attention_lse(q, k, v, causal=True,
                                         causal_offset=jnp.int32(32))
        rows = np.arange(128)[:, None] + 32
        cols = np.arange(128)[None, :]
        bias = jnp.asarray(np.where(rows >= cols, 0.0, A.NEG_INF),
                           jnp.float32)[None, None]
        o2, lse2 = A.flash_attention_lse(q, k, v, bias=bias)
        np.testing.assert_allclose(o1, o2, atol=2e-5, rtol=1e-5)
        np.testing.assert_allclose(lse1, lse2, atol=1e-5, rtol=1e-5)

    def test_offset_requires_causal(self):
        rng = np.random.RandomState(13)
        q, k, v = rand_qkv(rng, 1, 64, 2, 64)
        with pytest.raises(ValueError):
            A.flash_attention(q, k, v, causal_offset=jnp.int32(1))

    @pytest.mark.parametrize("off", [0, 96])
    def test_multiblock_native_offset_bwd(self, off):
        """Small blocks over S=256 force the two-kernel native backward
        (the kernels a ring hop at per-shard S > the tile hits): the
        off_ref handling in _bwd_dq_kernel_nl/_bwd_dkv_kernel_nl must
        match the dense oracle, gradients included."""
        rng = np.random.RandomState(15)
        q, k, v = rand_qkv(rng, 1, 256, 2, 64)
        kw = {"block_q": 128, "block_k": 128}

        def fn(q, k, v, off_):
            return A.flash_attention(q, k, v, causal=True,
                                     causal_offset=off_, **kw)

        rows = np.arange(256)[:, None] + off
        cols = np.arange(256)[None, :]
        bias = jnp.asarray(np.where(rows >= cols, 0.0, A.NEG_INF),
                           jnp.float32)[None, None]

        def ref(q, k, v):
            return A.attention_reference(q, k, v, bias=bias)

        got = jax.jit(fn)(q, k, v, jnp.int32(off))
        np.testing.assert_allclose(got, ref(q, k, v), atol=2e-5,
                                   rtol=1e-5)
        g1 = jax.jit(jax.grad(
            lambda q, k, v, o_: jnp.sum(fn(q, k, v, o_) ** 2),
            argnums=(0, 1, 2)))(q, k, v, jnp.int32(off))
        g2 = jax.grad(lambda q, k, v: jnp.sum(ref(q, k, v) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def _causal_case(id_, sq=512, sk=None, nh=2, d=64, tiles=(128, 128),
                 off=None, d_v=None, **kw):
    return pytest.param(dict(sq=sq, sk=sk or sq, nh=nh, d=d, tiles=tiles,
                             off=off, d_v=d_v or d, kw=kw), id=id_)


class TestCausalTileSkip:
    """The native multi-block kernels run no arithmetic for a causal tile
    that lies wholly above the frontier (``_Frontier``), and with a static
    offset their index maps fetch nothing for it. A skipped tile would have
    added ``p = 0`` under ``alpha = 1``: forward and the three gradients
    are the unskipped kernels' bit for bit (a zero's sign aside), which is
    what every case holds them to, the skip disabled by patching
    ``_frontier``. A ``v`` narrower than q and k goes through
    ``flash_attention``, which hands it to the backward kernels as it is
    (the lse variant pads it): no lse to compare there."""

    @staticmethod
    def _run(case, rng, bias=None):
        sq, sk, nh, d, d_v = (case[k]
                              for k in ("sq", "sk", "nh", "d", "d_v"))
        q = jnp.asarray(rng.randn(1, sq, nh, d), jnp.float32)
        k = jnp.asarray(rng.randn(1, sk, nh, d), jnp.float32)
        v = jnp.asarray(rng.randn(1, sk, nh, d_v), jnp.float32)
        w = jnp.asarray(rng.randn(1, sq, nh, d_v), jnp.float32)
        bq, bk = case["tiles"]

        def both(q, k, v, off):
            def loss(q, k, v):
                if d_v != d:
                    o = A.flash_attention(q, k, v, bias, None, True, bq, bk,
                                          causal_offset=off, **case["kw"])
                    return jnp.sum(o * w), (o, None)
                o, lse = A.flash_attention_lse(
                    q, k, v, bias, None, True, bq, bk, causal_offset=off,
                    **case["kw"])
                return jnp.sum(o * w) + 0.01 * jnp.sum(
                    jnp.where(lse > -1e29, lse, 0.0)), (o, lse)
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (*out, *grads)

        off = case["off"]
        if off is None:
            return jax.jit(lambda q, k, v: both(q, k, v, None))(q, k, v)
        return jax.jit(both)(q, k, v, jnp.int32(off))

    @pytest.mark.parametrize("case", [
        _causal_case("square-128x128-g2-d64"),
        _causal_case("square-256x128-g2-d64", tiles=(256, 128)),
        _causal_case("square-128x256-g2-d192", d=192, tiles=(128, 256)),
        _causal_case("square-256x128-g1-d256", nh=1, d=256,
                     tiles=(256, 128)),
        _causal_case("square-128x128-g1-d128", nh=3, d=128),
        _causal_case("kv-longer-bottom-right", sq=256, sk=512),
        _causal_case("q-longer-bottom-right", sq=512, sk=256),
        _causal_case("padded-q-and-kv-450", sq=450),
        _causal_case("padded-kv-only", sq=384, sk=450),
        _causal_case("dropout", dropout_rate=0.2, dropout_seed=5),
        _causal_case("dropout-256x128", tiles=(256, 128), dropout_rate=0.1,
                     dropout_seed=9),
        _causal_case("bias-per-head", bias="head"),
        _causal_case("bias-shared-256x128", tiles=(256, 128),
                     bias="shared"),
        _causal_case("traced-offset-zero", off=0),
        _causal_case("traced-offset-plus-64", off=64),
        _causal_case("traced-offset-plus-200", off=200),
        _causal_case("traced-offset-plus-4096", off=4096),
        _causal_case("traced-offset-minus-64", off=-64),
        _causal_case("traced-offset-minus-200-256x128", off=-200,
                     tiles=(256, 128)),
        _causal_case("traced-offset-minus-200-d192", off=-200, d=192,
                     tiles=(128, 256)),
        _causal_case("square-128x256-g2-d192-dv128", d=192, d_v=128,
                     tiles=(128, 256)),
        _causal_case("traced-offset-minus-200-d192-dv128", off=-200, d=192,
                     d_v=128, tiles=(128, 256)),
    ])
    def test_bit_identical_to_the_unskipped_kernels(self, case, monkeypatch):
        case = dict(case, kw=dict(case["kw"]))
        sq, sk, nh = case["sq"], case["sk"], case["nh"]
        bias = case["kw"].pop("bias", None)
        if bias is not None:
            bias = jnp.asarray(np.random.RandomState(3).randn(
                1, nh if bias == "head" else 1, sq, sk), jnp.float32) * 0.3
        frontiers = []
        real = A._frontier

        def spy(*a):
            frontiers.append(real(*a))
            return frontiers[-1]

        monkeypatch.setattr(A, "_frontier", spy)
        got = self._run(case, np.random.RandomState(21), bias)
        # the forward and both backward kernels each asked and were given one
        assert sum(f is not None for f in frontiers) >= 3
        monkeypatch.setattr(A, "_frontier", lambda *a: None)
        want = self._run(case, np.random.RandomState(21), bias)

        off = sk - sq if case["off"] is None else case["off"]
        seen = np.arange(sq) + off >= 0     # rows with a key to attend
        for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
            if a is None:
                continue
            a, b = np.asarray(a), np.asarray(b)
            assert np.all(np.isfinite(a)), name
            if name == "o":
                a, b = a[:, seen], b[:, seen]
            elif name == "lse":
                # a row with no key: out of contract but for lse ~ NEG_INF
                assert np.all(a[..., ~seen] < -1e29)
                a, b = a[..., seen], b[..., seen]
            assert np.array_equal(a, b), name

    @pytest.mark.parametrize("off", [-512, -640])
    def test_a_hop_wholly_in_the_future_is_zeros(self, off):
        """A ring hop whose keys all lie after its queries: every tile is
        skipped, the write-out finds the init's ``l = 0`` and ``m =
        NEG_INF``, which is the single-block kernel's answer too."""
        rng = np.random.RandomState(4)
        q, k, v = rand_qkv(rng, 1, 512, 2, 64)

        def fn(q, k, v, off_):
            return A.flash_attention_lse(q, k, v, None, None, True, 128,
                                         128, causal_offset=off_)

        o, lse = jax.jit(fn)(q, k, v, jnp.int32(off))
        assert not np.any(np.asarray(o))
        assert np.all(np.asarray(lse) < -1e29)
        grads = jax.jit(jax.grad(
            lambda q, k, v, off_: jnp.sum(fn(q, k, v, off_)[0] ** 2),
            argnums=(0, 1, 2)))(q, k, v, jnp.int32(off))
        for g in grads:
            assert not np.any(np.asarray(g))

    @pytest.mark.parametrize("traced", [False, True])
    def test_skipped_tiles_take_no_part(self, traced):
        """Keys and values that a row meets in skipped tiles alone may be
        anything: a NaN there does not reach the row (under the unskipped
        kernels ``p = 0`` times a NaN value is a NaN). Static frontier: the
        last k tile's NaNs stay out of the q tiles before the last. Traced,
        at 0 over 256 queries and 512 keys: the k tiles 2 and 3 reach no
        row."""
        rng = np.random.RandomState(8)
        sq, hole, clean = (256, 256, 256) if traced else (512, 384, 384)
        q, k, v = rand_qkv(rng, 1, sq, 2, 64, sk=512)
        nan = jnp.arange(512)[None, :, None, None] >= hole
        k, v = (jnp.where(nan, jnp.nan, x) for x in (k, v))
        kw = dict(causal_offset=jnp.int32(0)) if traced else {}
        out = jax.jit(lambda q, k, v: A.flash_attention(
            q, k, v, None, None, True, 128, 128, **kw))(q, k, v)
        assert np.all(np.isfinite(np.asarray(out)[:, :clean]))

    @pytest.mark.parametrize("bq,bk,nq,nk", [
        (128, 128, 4, 4), (256, 128, 2, 4), (128, 256, 4, 2),
        (1024, 256, 8, 32), (8, 128, 5, 3), (128, 384, 7, 2)])
    @pytest.mark.parametrize("off", [0, 1, 127, 128, 300, -1, -128, -300,
                                     10 ** 6, -10 ** 6])
    def test_frontier_in_tile_units(self, bq, bk, nq, nk, off):
        """``last_k``, ``first_q``, the two index-map clamps and the count
        against the one predicate, and the predicate against the mask."""
        fr = A._Frontier(bq, bk, nq, nk, off)
        rows = np.arange(nq * bq)[:, None] + off
        cols = np.arange(nk * bk)[None, :]
        kept = (rows >= cols).reshape(nq, bq, nk, bk).any((1, 3))
        runs = np.array([[bool(fr.runs(i, j)) for j in range(nk)]
                         for i in range(nq)])
        assert np.array_equal(runs, kept)
        assert fr.tiles_run() == kept.sum()
        for i in range(nq):
            assert np.array_equal(runs[i], np.arange(nk) <= fr.last_k(i))
        for j in range(nk):
            assert np.array_equal(runs[:, j], np.arange(nq) >= fr.first_q(j))
        i, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
        kb, qb = np.asarray(fr.k_block(i, j)), np.asarray(fr.q_block(i, j))
        assert kb.min() >= 0 and kb.max() < nk and 0 <= qb.min() < nq > qb.max()
        # a running step's own block, else the neighbouring running step's
        assert np.array_equal(kb, np.where(
            runs, j, np.maximum(fr.last_k(i), 0)))
        assert np.array_equal(qb, np.where(
            runs, i, np.minimum(fr.first_q(j), nq - 1)))


def _band_case(id_, window, sq=512, sk=None, nh=2, nkv=None, d=64,
               tiles=(128, 128)):
    return pytest.param(dict(window=window, sq=sq, sk=sk or sq, nh=nh,
                             nkv=nkv or nh, d=d, tiles=tiles), id=id_)


class TestSlidingWindow:
    """``window=``: query ``i`` attends keys ``i + off − window + 1 … i +
    off`` (``off = Sk − Sq``). The native kernels, interpreted, against a
    dense oracle with the band as its mask, forward and the three gradients;
    and against themselves with the skip disabled (``_frontier`` answering
    None: every tile runs under the same mask), bit for bit."""

    CASES = [
        _band_case("w64-tiles-128", 64),
        _band_case("w200-not-a-tile-multiple-padded-450", 200, sq=450),
        _band_case("w100-tiles-256x128", 100, tiles=(256, 128)),
        _band_case("w130-gqa-4-on-2-d128-tiles-128x256", 130, nh=4, nkv=2,
                   d=128, tiles=(128, 256)),
        _band_case("w64-kv-longer-bottom-right", 64, sq=256, sk=512),
        _band_case("w300-kv-longer-tiles-256x128", 300, sq=384, sk=640,
                   tiles=(256, 128)),
        _band_case("w1-own-key-alone", 1, sq=256),
        _band_case("w64-single-block-fused", 64, sq=256,
                   tiles=(256, 256)),
    ]

    @staticmethod
    def _run(case, rng, window):
        sq, sk, nh, nkv, d = (case[k] for k in ("sq", "sk", "nh", "nkv",
                                                 "d"))
        q = jnp.asarray(rng.randn(1, sq, nh, d), jnp.float32)
        k = jnp.asarray(rng.randn(1, sk, nkv, d), jnp.float32)
        v = jnp.asarray(rng.randn(1, sk, nkv, d), jnp.float32)
        w = jnp.asarray(rng.randn(1, sq, nh, d), jnp.float32)

        def loss(q, k, v):
            o = A.flash_attention(q, k, v, None, None, True, *case["tiles"],
                                  window=window)
            return jnp.sum(o * w), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

        def want(q, k, v):
            o = A.attention_reference(q, k, v, None, None, True, window)
            return jnp.sum(o * w), o
        with jax.default_matmul_precision("highest"):
            (_, o_ref), g_ref = jax.jit(jax.value_and_grad(
                want, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return (o, *grads), (o_ref, *g_ref)

    @pytest.mark.parametrize("case", CASES)
    def test_matches_the_dense_band(self, case):
        got, want = self._run(case, np.random.RandomState(5),
                              case["window"])
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-4, err_msg=name)
        # the band is not causal attention: the oldest keys took no part
        causal, _ = self._run(case, np.random.RandomState(5), None)
        assert not np.allclose(np.asarray(causal[0]), np.asarray(got[0]),
                               atol=1e-3)

    @pytest.mark.parametrize("case", CASES)
    def test_bit_identical_to_the_whole_grid(self, case, monkeypatch):
        frontiers = []
        real = A._frontier

        def spy(*a):
            frontiers.append(real(*a))
            return frontiers[-1]

        monkeypatch.setattr(A, "_frontier", spy)
        got, _ = self._run(case, np.random.RandomState(9), case["window"])
        monkeypatch.setattr(A, "_frontier", lambda *a: None)
        whole, _ = self._run(case, np.random.RandomState(9), case["window"])
        bands = [f for f in frontiers if f is not None]
        if case["tiles"] != (256, 256):
            assert bands and all(f.window == case["window"] for f in bands)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, whole):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name

    def test_a_window_of_the_whole_sequence_is_causal_attention(self):
        """``window >= Sk`` keeps every causal key: the call is causal
        attention's, the same program."""
        rng = np.random.RandomState(3)
        q, k, v = rand_qkv(rng, 1, 512, 2, 64)

        def text(window):
            return jax.jit(lambda q, k, v: A.flash_attention(
                q, k, v, None, None, True, 128, 128, window=window)).lower(
                    q, k, v).as_text()
        assert text(512) == text(4096) == text(None)
        assert text(511) != text(None)

    @pytest.mark.parametrize("window,blocks,want", [
        (512, None, (512, 512)), (32, None, (128, 128)),
        (200, None, (256, 256)), (1000, None, (1024, 1024)),
        (3000, None, (1024, 1024)), (512, (128, 256), (128, 256)),
        (None, None, (1024, 1024))])
    def test_the_op_picks_tiles_no_wider_than_the_window(self, window,
                                                         blocks, want):
        """Left at the defaults, a windowed call's tiles are the power of two
        at or above the window, a lane at least and the default at most;
        explicit tiles and a call without a window keep theirs."""
        blocks = blocks or (A.DEFAULT_BLOCK_Q, A.DEFAULT_BLOCK_K)
        assert A._window_blocks(window, *blocks) == want

    def test_a_windowed_call_at_the_defaults_runs_the_window_s_tiles(self):
        rng = np.random.RandomState(4)
        q, k, v = rand_qkv(rng, 1, 512, 2, 64)

        def text(*tiles):
            return jax.jit(lambda q, k, v: A.flash_attention(
                q, k, v, None, None, True, *tiles, window=64)).lower(
                    q, k, v).as_text()
        assert text() == text(128, 128) != text(256, 256)

    def test_the_lse_variant_takes_the_band(self):
        rng = np.random.RandomState(6)
        q, k, v = rand_qkv(rng, 1, 384, 2, 64)
        o, lse = jax.jit(lambda q, k, v: A.flash_attention_lse(
            q, k, v, None, None, True, 128, 128, window=96))(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = A.attention_reference(q, k, v, None, None, True, 96)
        np.testing.assert_allclose(np.asarray(o), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8.0
        lag = jnp.arange(384)[:, None] - jnp.arange(384)[None, :]
        s = jnp.where((lag >= 0) & (lag < 96), s, -jnp.inf)
        np.testing.assert_allclose(
            np.asarray(lse), np.asarray(jax.scipy.special.logsumexp(s, -1)),
            atol=1e-4, rtol=1e-5)

    @pytest.mark.parametrize("kw", [
        dict(causal=False), dict(bias=True), dict(dropout_rate=0.1,
                                                  dropout_seed=1),
        dict(causal_offset=0), dict(window=0), dict(d=48)])
    def test_what_the_band_does_not_take(self, kw):
        kw = dict(kw)
        d = kw.pop("d", 64)
        q = k = v = jnp.zeros((1, 256, 2, d), jnp.float32)
        bias = (jnp.zeros((1, 2, 256, 256), jnp.float32)
                if kw.pop("bias", False) else None)
        args = dict(causal=True, window=64)
        args.update(kw)
        with pytest.raises((ValueError, NotImplementedError)):
            A.flash_attention(q, k, v, bias, None, block_q=128, block_k=128,
                              **args)

    @pytest.mark.parametrize("bq,bk,nq,nk", [
        (128, 128, 4, 4), (256, 128, 2, 4), (128, 256, 4, 2),
        (512, 512, 8, 8), (8, 128, 5, 3), (128, 384, 7, 2)])
    @pytest.mark.parametrize("off", [0, 1, 127, 300, -1, -300])
    @pytest.mark.parametrize("window", [1, 64, 128, 200, 512, 5000])
    def test_band_in_tile_units(self, bq, bk, nq, nk, off, window):
        """``first_k``/``last_k``, ``first_q``/``last_q``, the two index-map
        clamps and the count against the one predicate, and the predicate
        against the band's mask."""
        fr = A._Frontier(bq, bk, nq, nk, off, window)
        lag = (np.arange(nq * bq)[:, None] + off
               - np.arange(nk * bk)[None, :])
        kept = ((lag >= 0) & (lag < window)).reshape(nq, bq, nk, bk).any(
            (1, 3))
        runs = np.array([[bool(fr.runs(i, j)) for j in range(nk)]
                         for i in range(nq)])
        assert np.array_equal(runs, kept)
        assert fr.tiles_run() == kept.sum()
        cols, rows = np.arange(nk), np.arange(nq)
        for i in range(nq):
            assert np.array_equal(runs[i], (cols >= fr.first_k(i))
                                  & (cols <= fr.last_k(i)))
        for j in range(nk):
            assert np.array_equal(runs[:, j], (rows >= fr.first_q(j))
                                  & (rows <= fr.last_q(j)))
        i, j = np.arange(nq)[:, None], np.arange(nk)[None, :]
        kb, qb = np.asarray(fr.k_block(i, j)), np.asarray(fr.q_block(i, j))
        assert 0 <= kb.min() and kb.max() < nk
        assert 0 <= qb.min() and qb.max() < nq
        # a running step's own block; a skipped one names a block of its
        # row's (column's) band where there is one, so nothing is fetched
        # for it that the band's steps do not fetch
        assert np.array_equal(np.where(runs, kb, -1), np.where(runs, j, -1))
        assert np.array_equal(np.where(runs, qb, -1), np.where(runs, i, -1))
        for r in range(nq):
            if runs[r].any():
                assert set(kb[r]) == set(np.flatnonzero(runs[r]))
        for c in range(nk):
            if runs[:, c].any():
                assert set(qb[:, c]) == set(np.flatnonzero(runs[:, c]))

    @pytest.mark.parametrize("t,tile,band,causal", [
        (4096, 512, 15, 36), (4096, 1024, 7, 10), (4096, 256, 45, 136)])
    def test_tiles_run_at_the_cells_length(self, t, tile, band, causal):
        """A window of 512 at 4096 tokens: the band against causal
        attention's tiles, a head group, of a grid of ``(t / tile)^2``."""
        grid = (t // tile) ** 2
        assert A._causal_tiles(tile, tile, t, t, True, 512) == (band, grid)
        assert A._causal_tiles(tile, tile, t, t, True) == (causal, grid)
        # the (query, key) pairs the band keeps: 23.4% of causal's
        pairs = sum(min(i + 1, 512) for i in range(t))
        assert (pairs, t * (t + 1) // 2) == (1966336, 8390656)


def test_lse_variant_offsets_are_keyword_only():
    """A ninth positional argument used to be ``causal_offset`` and would
    now bind to ``dropout_rate``: it must fail at the call site."""
    from apex_tpu.ops.attention import flash_attention_lse

    q = k = v = jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(TypeError):
        flash_attention_lse(q, k, v, None, None, True, 128, 128, 0)


def test_lse_variant_bias_cotangent():
    """flash_attention_lse returns a bias gradient that folds the lse
    cotangent (ds = p*(dp - (delta - dlse))) — round-5; previously the
    bias slot was silently None."""
    from apex_tpu.ops.attention import flash_attention_lse

    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(rng.randn(1, 2, 128, 128), jnp.float32) * 0.3

    def loss(bias):
        o, lse = flash_attention_lse(q, k, v, bias)
        # lse term makes dlse nonzero, exercising the shift fold
        return jnp.sum(jnp.sin(o)) + jnp.sum(lse * 0.01)

    def loss_ref(bias):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(64) + bias
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v)
        lse = jax.scipy.special.logsumexp(s, -1)
        return jnp.sum(jnp.sin(o)) + jnp.sum(lse * 0.01)

    with jax.default_matmul_precision("highest"):
        db = jax.jit(jax.grad(loss))(bias)
        db_ref = jax.jit(jax.grad(loss_ref))(bias)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               atol=2e-4)


MIB = 2 ** 20


# Expected plans are the parent's: read off what `_flash_bwd_nl` asked
# `pallas_call` for before the choice moved into `_bwd_plan` (PR 30).
@pytest.mark.parametrize("shape,want", [
    # BERT-Large's: one block, fused sweep, eight heads a step
    pytest.param(dict(batch=16, s=512, d=64, nh=16, itemsize=2),
                 (512, 512, 8, None, "fused"), id="bert-single-block-fused"),
    # Kimi's MLA with the tiles models/kimi_linear.py passes: a 1 MiB
    # score tile is under the 4 MiB at which the limit is raised
    pytest.param(dict(batch=1, s=8192, d=192, nh=32, itemsize=2,
                      blocks=(1024, 256)),
                 (1024, 256, 2, None, "two_kernel"), id="mla-not-raised"),
    # the same with v at its own 128 (both MLA cells): v, dO, dv and the dv
    # accumulator a third narrower, the same plan
    pytest.param(dict(batch=1, s=8192, d=192, nh=32, itemsize=2,
                      blocks=(1024, 256), d_v=128, causal=True,
                      tiles=(144, 256)),
                 (1024, 256, 2, None, "two_kernel"), id="mla-v128"),
    pytest.param(dict(batch=4, s=2048, d=64, nh=16, itemsize=2),
                 (1024, 1024, 2, 32 * MIB, "two_kernel_raised"),
                 id="1024sq-raised"),
    # the raised path's own ledger passes 32 MiB at d = 192: back to
    # 512 tiles under the default limit, at most 2 · g0 = 4 heads
    pytest.param(dict(batch=1, s=2048, d=192, nh=32, itemsize=2),
                 (512, 512, 4, None, "two_kernel"), id="over-32mib-to-512"),
    # float32, one block of 1024: the fused sweep passes 13 MiB even at
    # g0 = 1 and splits
    pytest.param(dict(batch=1, s=1024, d=128, nh=16, itemsize=4),
                 (1024, 1024, 1, None, "two_kernel"), id="f32-fused-splits"),
    pytest.param(dict(batch=4, s=2048, d=64, nh=16, itemsize=2,
                      dropout_rate=0.1),
                 (512, 512, 8, None, "two_kernel"), id="dropout-capped"),
    # Qwen3-Next's gated attention: at d = 256 a head is two whole lane
    # tiles (g0 = 1), and the default 1024 x 1024 tiles that the compiler
    # refused at d = 192 (g0 = 2) fit the raised limit with one head a step
    pytest.param(dict(batch=1, s=8192, d=256, nh=16, itemsize=2),
                 (1024, 1024, 1, 32 * MIB, "two_kernel_raised"),
                 id="qwen3-next-d256-raised"),
    # LFM2's grouped-query attention: two sequences, 32 heads of 64 (the
    # 8 k/v heads reach the kernels repeated), causal, 8192 tokens: two
    # heads a step fill the lanes, 1024 x 1024 tiles under the raised limit
    # (compiled for the v5e at 16.1 MiB for dk/dv: over the default)
    pytest.param(dict(batch=2, s=8192, d=64, nh=32, itemsize=2),
                 (1024, 1024, 2, 32 * MIB, "two_kernel_raised"),
                 id="lfm2-causal-d64-32-heads-s8192-raised"),
    # tiles_run / tiles_grid a head group of the three decoder cells' causal
    # calls (PR 38: a tile wholly above the frontier runs nothing): 43.75%
    # of each grid is skipped; without the mask all of it runs
    pytest.param(dict(batch=1, s=8192, d=192, nh=32, itemsize=2,
                      blocks=(1024, 256), causal=True, tiles=(144, 256)),
                 (1024, 256, 2, None, "two_kernel"), id="kimi-tiles-run"),
    pytest.param(dict(batch=2, s=8192, d=64, nh=32, itemsize=2, causal=True,
                      tiles=(36, 64)),
                 (1024, 1024, 2, 32 * MIB, "two_kernel_raised"),
                 id="lfm2-tiles-run"),
    pytest.param(dict(batch=1, s=8192, d=256, nh=16, itemsize=2, causal=True,
                      tiles=(36, 64)),
                 (1024, 1024, 1, 32 * MIB, "two_kernel_raised"),
                 id="qwen3-next-tiles-run"),
    pytest.param(dict(batch=1, s=8192, d=256, nh=16, itemsize=2,
                      causal=False, tiles=(64, 64)),
                 (1024, 1024, 1, 32 * MIB, "two_kernel_raised"),
                 id="non-causal-runs-the-whole-grid"),
    pytest.param(dict(batch=16, s=512, d=64, nh=16, itemsize=2, causal=True,
                      tiles=(1, 1)),
                 (512, 512, 8, None, "fused"), id="single-block-one-tile"),
    # the window cell's attention: 8 k/v heads of 128 repeated to 72 q heads
    # (window layers) and 48 (global), 4096 tokens. The window layers take
    # the op's tiles for a window of 512 (``_window_blocks``: 512 x 512),
    # four heads a step under the default limit: the band runs 15 of 64
    # tiles; the global layers keep the op's 1024 x 1024 tiles, two heads a
    # step under the raised limit (the configuration Qwen3-Next's d 256
    # compiles with on the v5e): 10 of 16
    pytest.param(dict(batch=1, s=4096, d=128, nh=72, itemsize=2,
                      causal=True, window=512, tiles=(15, 64)),
                 (512, 512, 4, None, "two_kernel"),
                 id="window-d128-72-heads-s4096-band"),
    pytest.param(dict(batch=1, s=4096, d=128, nh=48, itemsize=2, causal=True,
                      tiles=(10, 16)),
                 (1024, 1024, 2, 32 * MIB, "two_kernel_raised"),
                 id="global-d128-48-heads-s4096-raised"),
])
def test_backward_plan(shape, want):
    kw = dict(shape)
    batch, s, d, nh, itemsize = (
        kw.pop(k) for k in ("batch", "s", "d", "nh", "itemsize"))
    window = kw.pop("window", None)
    blocks = kw.pop("blocks", A._window_blocks(
        window, A.DEFAULT_BLOCK_Q, A.DEFAULT_BLOCK_K))
    causal, tiles = kw.pop("causal", None), kw.pop("tiles", None)
    plan = A._bwd_plan(nh, d, s, s, batch * nh, itemsize, *blocks, **kw)
    assert tuple(plan) == want
    if tiles is not None:
        assert plan.tiles(s, s, causal, window) == tiles
