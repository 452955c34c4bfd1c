"""On-device compile validation for every Pallas kernel.

The CI suite runs all kernels in interpret mode on a CPU mesh (see
tests/conftest.py) — semantically exact, but Mosaic's block-shape/tiling
rules are only enforced when a kernel actually *compiles* for a TPU. The
reference never had this gap (every test tier runs on real GPUs,
SURVEY.md §4); this module closes it: ``python -m apex_tpu.ops`` compiles
and runs every kernel family across the shape grid the tests use — plus
the known-nasty shapes (short multi-head sequences, odd hidden widths,
non-power-of-two block preferences, tail partitions) — on the attached
accelerator, checking outputs against interpret-mode or jnp oracles.

The artifact is the ``--json`` file. The command refuses to run off a
TPU unless ``--interpret`` asks for the interpreted (CPU) run by name.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import traceback
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

CASES: List[Tuple[str, Callable[[], None]]] = []


def case(name: str):
    def reg(fn):
        CASES.append((name, fn))
        return fn
    return reg


def _rand(shape, seed=0, dtype=jnp.float32, scale=1.0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(*shape).astype(np.float32) * scale, dtype)


@contextlib.contextmanager
def _interpret_oracle():
    """Trace kernels in interpret mode (the CI-validated semantics) —
    the oracle for kernels without a standalone jnp reference."""
    old = os.environ.get("APEX_TPU_FORCE_INTERPRET")
    os.environ["APEX_TPU_FORCE_INTERPRET"] = "1"
    try:
        yield
    finally:
        if old is None:
            del os.environ["APEX_TPU_FORCE_INTERPRET"]
        else:
            os.environ["APEX_TPU_FORCE_INTERPRET"] = old


def _check(label, got, want, atol, rtol=1e-5):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, atol=atol, rtol=rtol,
                               err_msg=label)


# --- flash attention ---------------------------------------------------------

def _attn_case(b, sq, sk, h, d, *, causal=False, with_bias=False,
               block_q=None, block_k=None, dtype=jnp.float32,
               seed=0, atol=2e-2):
    from apex_tpu.ops.attention import attention_reference, flash_attention
    q = _rand((b, sq, h, d), seed, dtype, 0.5)
    k = _rand((b, sk, h, d), seed + 1, dtype, 0.5)
    v = _rand((b, sk, h, d), seed + 2, dtype, 0.5)
    bias = _rand((b, h, sq, sk), seed + 3, dtype, 0.5) if with_bias else None
    kw = {}
    if block_q:
        kw["block_q"] = block_q
    if block_k:
        kw["block_k"] = block_k

    def fwd(q, k, v, bias):
        return flash_attention(q, k, v, bias=bias, causal=causal, **kw)

    got = jax.jit(fwd)(q, k, v, bias)
    want = attention_reference(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), bias=bias,
                               causal=causal)
    _check("attention fwd", got, want, atol)

    g = _rand((b, sq, h, d), seed + 4, dtype, 0.5)

    def loss(q, k, v, bias):
        return jnp.sum(fwd(q, k, v, bias).astype(jnp.float32) * g)

    def loss_ref(q, k, v, bias):
        return jnp.sum(attention_reference(q, k, v, bias=bias,
                                           causal=causal) * g)

    argn = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    got_g = jax.jit(jax.grad(loss, argnums=argn))(q, k, v, bias)
    want_g = jax.grad(loss_ref, argnums=argn)(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), bias)
    for name, gg, ww in zip("qkvb", got_g, want_g):
        _check(f"attention d{name}", gg, ww, atol * 4, rtol=1e-3)


@case("attention/basic-256")
def _():
    _attn_case(2, 256, 256, 4, 64)


@case("attention/causal-384")
def _():
    _attn_case(1, 384, 384, 2, 128, causal=True)


@case("attention/bias-256")
def _():
    _attn_case(2, 256, 256, 2, 64, with_bias=True)


@case("attention/bias-native-no-transpose")
def _():
    # round-5: per-head additive bias rides the native-layout grid —
    # the compiled fwd+bwd graph must contain NO transpose ops (the
    # 10.6 ms/step-class tax the (B·H,S,D) wrappers paid) and exactly
    # the two native custom-calls
    import jax
    import numpy as np
    from apex_tpu.ops.attention import flash_attention

    B, S, H, D = 2, 256, 4, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    bias = jnp.asarray(rng.randn(1, H, S, S), jnp.float32)

    def f(q, k, v, bias):
        return jnp.sum(flash_attention(q, k, v, bias)
                       .astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(f, argnums=(0, 1, 2)))
    from apex_tpu.ops._dispatch import use_interpret
    if use_interpret():
        # interpret mode lowers pallas_call to plain HLO — the
        # custom-call/transpose structure only exists on the chip;
        # still drive the compiled grads (same condition the kernel
        # dispatch uses, incl. APEX_TPU_FORCE_INTERPRET on a TPU host)
        jax.block_until_ready(g(q, k, v, bias))
        return
    hlo = g.lower(q, k, v, bias).compile().as_text()
    n_tr = sum(1 for l in hlo.splitlines() if " transpose(" in l)
    assert n_tr == 0, f"biased attention compiled {n_tr} transposes"
    assert hlo.count("tpu_custom_call") == 2, "expected fwd + fused bwd"


@case("attention/short-seq-multihead")
def _():
    # sq < 128 with several heads — the round-2 lse-alignment bug shape
    _attn_case(2, 64, 64, 4, 64)


@case("attention/cross-200x112")
def _():
    # ragged cross-attention lengths exercise tail masking
    _attn_case(1, 200, 112, 3, 64)


@case("attention/nonpow2-block-pref")
def _():
    # ADVICE round-2: block_k=384 over sk=400 must not produce an
    # unaligned multi-block tile when a bias is present
    _attn_case(1, 256, 400, 2, 64, with_bias=True, block_k=384)


@case("attention/bf16-512")
def _():
    _attn_case(1, 512, 512, 4, 64, dtype=jnp.bfloat16, atol=5e-2)


@case("attention/long-2048-1024tiles")
def _():
    # multi-block grids at the 1024-tile default (the long-sequence
    # fast path; also the causal multi-block masking)
    _attn_case(1, 2048, 2048, 2, 64, causal=True, dtype=jnp.bfloat16,
               atol=5e-2)


@case("attention/long-bias-2048")
def _():
    # the ring causal-hop shape: long sequence WITH an additive bias —
    # the path the 512-tile bias cap protects (a 1024-tile fp32 bias
    # block would blow the scoped VMEM); grads included
    _attn_case(1, 2048, 2048, 1, 64, with_bias=True,
               dtype=jnp.bfloat16, atol=5e-2)


@case("attention/dropout-runs-finite")
def _():
    from apex_tpu.ops.attention import flash_attention
    # S=256 (single block) and S=2048 (multi-block at the capped 512
    # dropout tile — the VMEM-sensitive combination)
    for s in (256, 2048):
        q = _rand((1, s, 2, 64), 0)
        k = _rand((1, s, 2, 64), 1)
        v = _rand((1, s, 2, 64), 2)

        def loss(q, k, v):
            o = flash_attention(q, k, v, dropout_rate=0.1,
                                dropout_seed=7)
            return jnp.sum(o * o)

        val, grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2)))(q, k, v)
        assert np.isfinite(float(val))
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g, np.float32)))


def _dense_dropout_oracle(q, k, v, bias, seed, rate, causal,
                          block_q, block_k):
    """Dense attention applying the EXACT keep mask the kernels
    generate (same hash, same block decomposition via the shared cap) —
    the on-chip value oracle for the compiled dropout paths
    (VERDICT r3 item 3: the bitwise mask agreement across the fwd
    kernel, both bwd kernels, and the dense `_bias_grad` replica was
    previously validated only in interpret mode)."""
    from apex_tpu.ops.attention import (
        NEG_INF, _block_cap, _choose_block, _keep_mask_dense)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    cq, ck = _block_cap(block_q, block_k, bias is not None, rate)
    bq = _choose_block(cq, sq)
    bk = _choose_block(ck, sk, lane=True)
    seed_arr = jnp.asarray(seed, jnp.int32).reshape(-1)[:1]
    keep = _keep_mask_dense(seed_arr[0], b, h, sq, sk, bq, bk,
                            rate).reshape(b, h, sq, sk)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(d)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    pd = jnp.where(keep, p / (1.0 - rate), 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", pd, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _dropout_equiv_case(s, *, with_bias=False, causal=False, seed=11,
                        rate=0.3):
    """Elementwise compiled-vs-dense dropout equivalence under HIGHEST
    matmul precision: f32 dots on the MXU default to bf16 passes
    (~1e-3 relative noise — larger than a long-sequence mask-flip's
    ~p-sized signal), so the mask certification needs the fp32-exact
    passes. At highest precision the fp noise floor is ~1e-6 while a
    single flipped keep bit moves affected o/grad elements by
    ≥ ~1/(2s) through the 1/(1-rate) scale — cleanly detectable at the
    tolerances below."""
    from apex_tpu.ops.attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                        flash_attention)
    q = _rand((1, s, 2, 64), 0)
    k = _rand((1, s, 2, 64), 1)
    v = _rand((1, s, 2, 64), 2)
    bias = _rand((1, 2, s, s), 3, scale=0.5) if with_bias else None
    g = _rand((1, s, 2, 64), 4)

    def fwd(q, k, v, bias):
        return flash_attention(q, k, v, bias=bias, causal=causal,
                               dropout_rate=rate, dropout_seed=seed)

    def fwd_ref(q, k, v, bias):
        return _dense_dropout_oracle(q, k, v, bias, seed, rate, causal,
                                     DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)

    def loss(q, k, v, bias):
        return jnp.sum(fwd(q, k, v, bias).astype(jnp.float32) * g)

    def loss_ref(q, k, v, bias):
        return jnp.sum(fwd_ref(q, k, v, bias).astype(jnp.float32) * g)

    argn = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    with jax.default_matmul_precision("highest"):
        got_o = jax.jit(fwd)(q, k, v, bias)
        want_o = jax.jit(fwd_ref)(q, k, v, bias)
        got_g = jax.jit(jax.grad(loss, argnums=argn))(q, k, v, bias)
        want_g = jax.jit(jax.grad(loss_ref, argnums=argn))(q, k, v, bias)
    _check("dropout o", got_o, want_o, 5e-5, rtol=1e-4)
    for name, gg, ww in zip("qkvb", got_g, want_g):
        _check(f"dropout d{name}", gg, ww, 2e-4, rtol=1e-3)


@case("attention/dropout-mask-equivalence-256")
def _():
    # single-block grid: compiled fwd + both bwd kernels must regenerate
    # the dense replica's mask bit-for-bit (values asserted, not
    # finiteness)
    _dropout_equiv_case(256)


@case("attention/dropout-mask-equivalence-2048")
def _():
    # multi-block grid at the capped 512 dropout tile: the block-
    # coordinate hash must agree across a non-trivial decomposition
    _dropout_equiv_case(2048, causal=True)


@case("attention/dropout-bias-grad-equivalence")
def _():
    # the learned-bias cotangent path (`_bias_grad`) shares the dense
    # mask with the kernels: dbias values must match the oracle too
    _dropout_equiv_case(384, with_bias=True)


@case("attention/fp32-1024-gpack-vmem")
def _():
    # fp32 inputs double the g-pack VMEM estimate (ADVICE r3 item 1):
    # the largest single-q-block fp32 shape must stay Mosaic-compilable
    # with the itemsize-aware packing
    _attn_case(4, 1024, 1024, 4, 64, dtype=jnp.float32, atol=2e-2)


@case("attention/lse-dropout-block-offset")
def _():
    # ring-hop dropout on the chip: the lse variant with a traced
    # (q-block, k-block) offset must equal a dense replica hashed at
    # the SHIFTED global coordinates — bitwise mask, fp-tolerance
    # values (round-5 ring dropout machinery)
    import numpy as np
    from apex_tpu.ops import attention as A

    B, S, H, D = 1, 512, 2, 64
    rate, seed = 0.3, 9
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    dbo = jnp.asarray([2, 3], jnp.int32)

    def dense(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
        p = jax.nn.softmax(s, axis=-1)
        gb = jax.lax.broadcasted_iota(jnp.uint32, (B * H, S, S), 0)
        rows = jax.lax.broadcasted_iota(jnp.uint32, (B * H, S, S), 1)
        cols = jax.lax.broadcasted_iota(jnp.uint32, (B * H, S, S), 2)
        keep = A._mix_keep(jnp.uint32(seed), gb, jnp.uint32(2),
                           jnp.uint32(3), rows, cols, rate)
        pk = jnp.where(keep.reshape(B, H, S, S), p / (1.0 - rate), 0.0)
        return jnp.einsum("bhqk,bkhd->bqhd", pk, v)

    # highest precision: default lowers f32 dots to bf16 passes whose
    # ~1e-3 noise would swamp a flipped-keep signal (see
    # _dropout_equiv_case)
    with jax.default_matmul_precision("highest"):
        o, lse = jax.jit(lambda q, k, v: A.flash_attention_lse(
            q, k, v, dropout_rate=rate, dropout_seed=seed,
            dropout_block_offset=dbo))(q, k, v)
        ref = jax.jit(dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               atol=5e-5)


@case("attention/ring-hop-shapes")
def _():
    # the ring per-hop call: flash_attention_lse under the TRACED
    # causal_offset (the native-path hop — no O(S²) bias), grads
    # through (o, lse) both, PLUS the (1,1,sq,sk) additive-bias form
    # it replaced (still the fallback for non-native geometries) —
    # Mosaic legality on the chip for the hop kernels the CPU-mesh
    # dryrun exercises only in interpret mode
    from apex_tpu.ops.attention import (attention_reference,
                                        flash_attention_lse)
    sq = sk = 1024
    q = _rand((1, sq, 2, 64), 0, jnp.bfloat16, 0.5)
    k = _rand((1, sk, 2, 64), 1, jnp.bfloat16, 0.5)
    v = _rand((1, sk, 2, 64), 2, jnp.bfloat16, 0.5)
    # hop: query global offset sq (second shard), key offset 0
    rows = np.arange(sq)[:, None] + sq
    cols = np.arange(sk)[None, :]
    bias = jnp.asarray(np.where(rows >= cols, 0.0, -1e9),
                       jnp.float32).reshape(1, 1, sq, sk)
    g = _rand((1, sq, 2, 64), 3)

    def loss_off(q, k, v, off):
        o, lse = flash_attention_lse(q, k, v, causal=True,
                                     causal_offset=off)
        return jnp.sum(o.astype(jnp.float32) * g) \
            + 1e-3 * jnp.sum(lse.astype(jnp.float32))

    def loss_bias(q, k, v):
        o, lse = flash_attention_lse(q, k, v, bias=bias)
        return jnp.sum(o.astype(jnp.float32) * g) \
            + 1e-3 * jnp.sum(lse.astype(jnp.float32))

    off = jnp.int32(sq)
    want_o = attention_reference(q.astype(jnp.float32),
                                 k.astype(jnp.float32),
                                 v.astype(jnp.float32), bias=bias)
    o_off, _ = jax.jit(lambda q, k, v, s: flash_attention_lse(
        q, k, v, causal=True, causal_offset=s))(q, k, v, off)
    _check("ring hop fwd (offset)", o_off, want_o, 5e-2)
    o_b, _ = jax.jit(flash_attention_lse)(q, k, v, bias=bias)
    _check("ring hop fwd (bias)", o_b, want_o, 5e-2)

    for lossfn, args in ((loss_off, (q, k, v, off)),
                         (loss_bias, (q, k, v))):
        got = jax.jit(jax.value_and_grad(
            lossfn, argnums=(0, 1, 2)))(*args)
        assert np.isfinite(float(got[0]))
        for gg in got[1]:
            assert np.all(np.isfinite(np.asarray(gg, np.float32)))


@case("attention/ulysses-resharded")
def _():
    # the Ulysses all-to-all re-shard: long local sequence, few local
    # heads (16 heads over an 8-way axis -> 2), causal, bf16 — the
    # 1024-tile multi-block causal path at the resharded geometry
    _attn_case(2, 2048, 2048, 2, 64, causal=True, dtype=jnp.bfloat16,
               atol=5e-2)


def _rel(label, got, want, tol):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    assert np.all(np.isfinite(got)) and err <= tol, f"{label}: {err:.3e}"


def _gqa_cell_case(batch=1, heads=16, kv_heads=2, d=256, t=8192,
                   prefix=1024, window=None):
    """Grouped-query attention at a cell's shape (Qwen3-Next's gated
    attention: 16 q heads on 2 k/v heads of 256; causal, bfloat16, the op's
    own tiles), forward and the three gradients, against the dense oracle
    on a prefix: under a causal mask the first rows see nothing of the
    rest. ``window``: the op's band, the oracle's as a dense mask."""
    from apex_tpu.ops.attention import attention_reference, flash_attention
    q = _rand((batch, t, heads, d), 0, jnp.bfloat16, 0.5)
    k = _rand((batch, t, kv_heads, d), 1, jnp.bfloat16, 0.5)
    v = _rand((batch, t, kv_heads, d), 2, jnp.bfloat16, 0.5)
    w = _rand((batch, prefix, heads, d), 3, jnp.float32, 0.5)
    scale = d ** -0.5
    band = {} if window is None else {"window": window}
    loss = lambda fn: lambda q, k, v: jnp.sum(
        fn(q, k, v, None, scale, True, **band)[:, :prefix].astype(
            jnp.float32) * w)
    out, grads = jax.jit(lambda *a: (
        flash_attention(*a, None, scale, True, **band),
        jax.grad(loss(flash_attention), argnums=(0, 1, 2))(*a)))(q, k, v)
    assert out.shape == q.shape and grads[1].shape == k.shape
    head = tuple(x[:, :prefix].astype(jnp.float32) for x in (q, k, v))
    _rel("gqa fwd", out[:, :prefix], attention_reference(
        *head, None, scale, True, **band), 3e-2)
    want = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(*head)
    for name, g, r in zip("qkv", grads, want):
        _rel(f"gqa d{name}", g[:, :prefix], r, 6e-2)
        assert not np.any(np.asarray(g[:, prefix:], np.float32)), name


@case("attention/gqa-d256-cell")
def _():
    _gqa_cell_case()


@case("attention/gqa-d64-causal-s8192-cell")
def _():
    # the cell named: two sequences, 32 q heads on 8 k/v heads of 64: the
    # backward's 1024 x 1024 tiles, two heads a step, under the raised VMEM
    # limit
    _gqa_cell_case(2, 32, 8, 64)


@contextlib.contextmanager
def _no_causal_skip():
    """The native kernels as they were before they skipped a causal tile:
    every grid step runs and fetches (the frontier helper answers None)."""
    from apex_tpu.ops import attention
    real = attention._frontier
    attention._frontier = lambda *a: None
    try:
        yield
    finally:
        attention._frontier = real


def _causal_skip_case(batch, heads, kv_heads, d, t=8192, tiles=(),
                      window=None, d_v=None):
    """A cell's causal call over all its tokens, forward and the three
    gradients, with the tiles above the frontier skipped and not fetched
    against the same kernels running their whole grid: a skipped tile would
    have added ``p = 0`` under ``alpha = 1``, so the two agree bit for bit
    (a zero's sign aside). The oracle cases hold a prefix of the rows; this
    one holds every row, the last q tiles' long k loops among them. Under a
    ``window`` the tiles left of the band are skipped too. ``d_v``: v's head
    size where it is not q's and k's."""
    from apex_tpu.ops.attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                        _causal_tiles, _window_blocks,
                                        flash_attention)
    q = _rand((batch, t, heads, d), 0, jnp.bfloat16, 0.5)
    k = _rand((batch, t, kv_heads, d), 1, jnp.bfloat16, 0.5)
    v = _rand((batch, t, kv_heads, d_v or d), 2, jnp.bfloat16, 0.5)
    w = _rand((batch, t, heads, d_v or d), 3, jnp.float32, 0.5)
    run, grid = _causal_tiles(*(tiles or _window_blocks(
        window, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K)), t, t, True, window)
    assert run < grid, (run, grid)

    def both():
        # a fresh function a variant: the frontier is consulted while the
        # kernels are traced
        def loss(q, k, v, w):
            o = flash_attention(q, k, v, None, d ** -0.5, True, *tiles,
                                window=window)
            return jnp.sum(o.astype(jnp.float32) * w), o
        return jax.jit(lambda *a: jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(*a))(q, k, v, w)

    (_, o), grads = both()
    with _no_causal_skip():
        (_, o_all), grads_all = both()
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o, *grads),
                          (o_all, *grads_all)):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        assert np.all(np.isfinite(a)), name
        assert np.array_equal(a, b), (
            f"{name}: {run} of {grid} tiles differ from the whole grid by "
            f"{float(np.max(np.abs(a - b))):.3e}")


@case("attention/causal-skip-mla-d192-s8192-cell")
def _():
    # both MLA cells' latent attention: 32 heads of 192, v at its own 128,
    # at the 1024 x 256 tiles models/mla.py passes, two heads a step: 144 of
    # 256 tiles run
    _causal_skip_case(1, 32, 32, 192, tiles=(1024, 256), d_v=128)


@case("attention/causal-skip-gqa-d64-s8192-cell")
def _():
    # the cell named: two sequences, 32 q heads on 8 k/v heads of 64, two
    # heads a step: 36 of 64 tiles run
    _causal_skip_case(2, 32, 8, 64)


@case("attention/causal-skip-gqa-d256-s8192-cell")
def _():
    # Qwen3-Next's: 16 q heads on 2 k/v heads of 256, a head a step: 36 of 64
    _causal_skip_case(1, 16, 2, 256)


@case("attention/window-band-d128-s4096-cell")
def _():
    # the window cell's sliding layers: 72 q heads on 8 k/v heads of 128, a
    # window of 512 keys, the op's own tiles for it (512 x 512), four heads
    # a step, against the dense band on the first 1024 rows
    _gqa_cell_case(1, 72, 8, 128, t=4096, window=512)


@case("attention/window-skip-d128-s4096-cell")
def _():
    # the same call over every row: 15 of 64 tiles run and fetch, against
    # the kernels running their whole grid under the same mask
    _causal_skip_case(1, 72, 8, 128, t=4096, window=512)


def _causal_skip_reach_case(t=2048, tile=1024, heads=4, d=64, sharding=None):
    """``attention/causal-skip-no-extra-dispatch``: the skip reaches the
    causal multi-block kernels and nothing else. With the frontier helper
    answering None (the kernels as they were) the lowered program of a
    non-causal multi-block call, of a single-block causal call (the
    single-k forward and the fused backward) and of a single-block
    non-causal one is the same text; the causal multi-block call's is
    not. ``sharding`` lowers for a described device (tests/test_pod_hlo.py:
    the Mosaic payloads compared without a chip)."""
    from apex_tpu.ops.attention import flash_attention

    def lowered(causal, s):
        x = jax.ShapeDtypeStruct((1, s, heads, d), jnp.bfloat16,
                                 sharding=sharding)

        def loss(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, None, None, causal, tile, tile).astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).as_text()

    for causal, s in ((False, t), (True, tile), (False, tile), (True, t)):
        texts = []
        for form in (contextlib.nullcontext, _no_causal_skip):
            with form():
                # ONE call site for both forms: the text records the Python
                # stack it was traced under, down to the Mosaic kernels'
                # payloads
                texts.append(lowered(causal, s))
        if causal and s > tile:
            assert texts[0] != texts[1], (
                "the causal multi-block kernels skip nothing")
        else:
            assert texts[0] == texts[1], (
                f"the causal skip changed causal={causal} at {s} tokens")


@case("attention/causal-skip-no-extra-dispatch")
def _():
    _causal_skip_reach_case()


# --- gated delta rule --------------------------------------------------------

def _gdn_cell_inputs(t):
    """Qwen3-Next's DeltaNet operands at ``t`` tokens: 16 key heads serving
    32 value heads of 128, one decay a head, float32 as the model hands them
    over."""
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(_rand((1, t, 16, 128), 0)) * 128 ** -0.5
    k = unit(_rand((1, t, 16, 128), 1))
    v = _rand((1, t, 32, 128), 2)
    g = -jnp.abs(_rand((1, t, 32), 3, scale=0.5)) - 1e-3
    beta = jax.nn.sigmoid(_rand((1, t, 32), 4))
    return q, k, v, g, beta


def _delta_rule_cell_case(t=8192, prefix=512, precision=None):
    """Qwen3-Next's DeltaNet at the cell's shape: the two kernels of one
    decay a head (``apex_gdn_fwd``, ``apex_gdn_bwd``: the decay as it is,
    the key heads read in place) against the recurrence, one step a token:
    the output at every token, all five gradients on a prefix (the
    recurrence's backward keeps a state a token)."""
    from apex_tpu.ops.delta_rule import (gated_delta_rule,
                                         gated_delta_rule_reference)
    args = _gdn_cell_inputs(t)
    loss = lambda fn: lambda *a: jnp.sum(
        fn(*a)[:, :prefix] * jnp.cos(jnp.arange(128.0)))
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out, grads = jax.jit(lambda *a: (
            gated_delta_rule(*a),
            jax.grad(loss(gated_delta_rule), argnums=range(5))(*a)))(*args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(gated_delta_rule_reference)(*args)
        want = jax.jit(jax.grad(loss(gated_delta_rule_reference),
                                argnums=range(5)))(
            *(x[:, :prefix] for x in args))
    assert out.shape == args[2].shape and out.dtype == jnp.float32
    _rel("delta rule fwd", out, ref, 2e-2)
    for name, x, a, b in zip("q k v g beta".split(), args, grads, want):
        assert a.shape == x.shape, name
        _rel(f"delta rule d{name}", a[:, :prefix], b, 5e-2)


@case("delta_rule/scalar-decay-shared-keys-cell")
def _():
    _delta_rule_cell_case()


@case("delta_rule/scalar-decay-shared-keys-cell-highest")
def _():
    # a suite or a user under ``highest``: Mosaic refuses float32 passes
    # over bfloat16 operands, and the kernels' sums name their precision
    _delta_rule_cell_case(precision="highest")


def _scalar_vs_broadcast_case(t=8192, tol=1e-2):
    """The kernels of one decay a head against the per-channel kernels fed
    what it is short for (the decay broadcast over the 128 key channels, the
    key heads repeated a pair) at the cell's shape: output and all five
    gradients of a weighted sum over every token, to ``tol`` of each one's
    largest magnitude. Both forms run the same sums at float32 and the state
    and output products at the default precision; they differ in the order
    of a chunk's score sums and in where the shared key heads' cotangents
    are summed."""
    from apex_tpu.ops.delta_rule import gated_delta_rule
    args = _gdn_cell_inputs(t)

    def wide(q, k, v, g, beta):
        return (jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v,
                jnp.broadcast_to(g[..., None], v.shape), beta)

    def both(prepare):
        fn = lambda *a: gated_delta_rule(*prepare(*a))
        loss = lambda *a: jnp.sum(fn(*a) * jnp.cos(jnp.arange(128.0)))
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            loss, argnums=range(5))(*a)))(*args)

    (out, grads), (ref, want) = both(lambda *a: a), both(wide)
    _rel("scalar against broadcast fwd", out, ref, tol)
    for name, x, a, b in zip("q k v g beta".split(), args, grads, want):
        assert a.shape == x.shape, name
        _rel(f"scalar against broadcast d{name}", a, b, tol)


@case("delta_rule/scalar-vs-broadcast-cell")
def _():
    _scalar_vs_broadcast_case()


# --- short convolution -------------------------------------------------------

def _short_conv_cell_case(wide, channels, norm, precision=None, t=8192):
    """The delta-rule layers' convolution at a cell's shape (8192 tokens,
    bfloat16 in as the projection hands it over under O1, the cell's
    channels and normalised ranges): the two kernels against the
    ``jax.numpy`` form, output and both gradients."""
    from apex_tpu.ops.short_conv import short_conv, short_conv_reference
    x = _rand((1, t, wide), 0, jnp.bfloat16)
    taps = _rand((4, channels), 1, scale=0.3)
    w = _rand((1, t, channels), 2)
    both = lambda fn: jax.jit(lambda x, taps: (
        fn(x, taps, norm, 128), jax.grad(lambda x, taps: jnp.sum(
            fn(x, taps, norm, 128) * w), argnums=(0, 1))(x, taps)))(x, taps)
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out, (d_x, d_taps) = both(short_conv)
    ref, (r_x, r_taps) = both(short_conv_reference)
    assert out.shape == (1, t, channels) and out.dtype == jnp.float32
    assert d_x.shape == x.shape and d_x.dtype == x.dtype
    _rel("short conv fwd", out, ref, 1e-5)
    # d x leaves as bfloat16 on both sides: a last bit of it, at most
    _rel("short conv dx", d_x, r_x, 2 ** -7)
    _rel("short conv dtaps", d_taps, r_taps, 1e-4)


_KIMI_CONV = (4096, 4096, ((0, 4096, 128 ** -0.5),))
# one convolution over q, k, v of a projection that holds z too
_QWEN_CONV = (12288, 8192, ((0, 2048, 128 ** -0.5), (2048, 4096, 1.0)))


@case("short_conv/kimi-cell")
def _():
    _short_conv_cell_case(*_KIMI_CONV)
    _short_conv_cell_case(4096, 4096, ())       # v: no head normalised


@case("short_conv/qwen-cell")
def _():
    _short_conv_cell_case(*_QWEN_CONV)


@case("short_conv/kimi-cell-highest")
def _():
    _short_conv_cell_case(*_KIMI_CONV, precision="highest")


@case("short_conv/qwen-cell-highest")
def _():
    _short_conv_cell_case(*_QWEN_CONV, precision="highest")


@case("short_conv/gated-k3-cell")
def _():
    """A gated short-convolution mixer's middle at its cell's shape: two
    sequences of 8192 tokens, ``[B; C; z]`` of 3 x 2048 channels in
    bfloat16, three taps, no activation: the gated kernels against the
    ``jax.numpy`` form, output and both gradients (``d [B; C; z]`` one
    array)."""
    from apex_tpu.ops.short_conv import short_conv, short_conv_reference
    x = _rand((2, 8192, 6144), 0, jnp.bfloat16)
    taps = _rand((3, 2048), 1, scale=0.3)
    w = _rand((2, 8192, 2048), 2)
    form = lambda fn: lambda x, taps: fn(x, taps, (), 128, (4096, 2048))
    both = lambda fn: jax.jit(lambda x, taps: (
        fn(x, taps), jax.grad(lambda x, taps: jnp.sum(
            fn(x, taps).astype(jnp.float32) * w), argnums=(0, 1))(x, taps)))(
                x, taps)
    out, (d_x, d_taps) = both(form(short_conv))
    ref, (r_x, r_taps) = both(form(short_conv_reference))
    assert out.shape == (2, 8192, 2048) and out.dtype == jnp.bfloat16
    assert d_x.shape == x.shape and d_x.dtype == x.dtype
    # both leave as bfloat16 on both sides: a last bit, at most
    _rel("gated conv fwd", out, ref, 2 ** -7)
    _rel("gated conv dx", d_x, r_x, 2 ** -7)
    _rel("gated conv dtaps", d_taps, r_taps, 1e-4)


# --- routed experts ----------------------------------------------------------

def _experts_cell_case(tokens, top_k, routed, n_held, hidden, width):
    """One rank's expert layer at a cell's shape, bfloat16 as under O1, a
    router that spreads the rows evenly: the sorted rows, ``apex_gmm`` (its
    three forms) and ``apex_tgmm`` against a loop of dense experts over
    every row under a 0/1 mask, value and all five gradients; then every
    assignment held, which is the most rows the kernels can be sent."""
    from apex_tpu.ops import moe
    held = tuple(range(n_held))
    x = _rand((tokens, hidden), 0, jnp.bfloat16)
    w_gate, w_up = (_rand((n_held, hidden, width), s, jnp.bfloat16, 0.02)
                    for s in (1, 2))
    w_down = _rand((n_held, width, hidden), 3, jnp.bfloat16, 0.02)
    out_w = _rand((tokens, hidden), 4)

    def plain(x, w, a, b, c, chosen):
        y = jnp.zeros((tokens, hidden), jnp.float32)
        for n, e in enumerate(held):
            h = jax.nn.silu(x @ a[n]) * (x @ b[n])
            y += jnp.sum(jnp.where(chosen == e, w, 0.0), -1)[:, None] * (
                h @ c[n]).astype(jnp.float32)
        return y

    ours = lambda x, w, a, b, c, chosen: moe.held_experts(
        x, w, chosen, a, b, c, held, routed)
    for every_row_held in (False, True):
        scores = jax.random.uniform(jax.random.PRNGKey(5), (tokens, routed))
        if every_row_held and n_held >= top_k:
            scores = scores.at[:, :n_held].add(2.0)
        w, chosen = jax.lax.top_k(scores, top_k)
        chosen = chosen.astype(jnp.int32)
        both = lambda fn: jax.jit(lambda *a: (
            fn(*a, chosen), jax.grad(lambda *a: jnp.sum(
                fn(*a, chosen) * out_w), argnums=range(5))(*a)))(
                    x, w, w_gate, w_up, w_down)
        got, d_got = both(ours)
        want, d_want = both(plain)
        assert got.shape == (tokens, hidden) and got.dtype == jnp.float32
        # both sides round each matmul's float32 sum to bfloat16 once
        _rel("experts fwd", got, want, 2e-2)
        for name, a, b in zip(("x", "w", "gate", "up", "down"), d_got, d_want):
            assert a.shape == b.shape and a.dtype == b.dtype, name
            _rel(f"experts d{name}", a, b, 3e-2)


@case("moe/4-of-64-cell")
def _():
    # two sequences of 8192, 4 of 64 chosen, 8 held, 2048 x 1536
    _experts_cell_case(16384, 4, 64, 8, 2048, 1536)


@case("moe/8-of-256-cell")
def _():
    _experts_cell_case(8192, 8, 256, 8, 2304, 1024)


@case("moe/10-of-512-cell")
def _():
    _experts_cell_case(8192, 10, 512, 32, 2048, 512)


# --- layer norm --------------------------------------------------------------

def _ln_case(n, h, dtype=jnp.float32, atol=1e-4):
    from apex_tpu.ops.layer_norm import (fused_layer_norm_affine,
                                         layer_norm_reference)
    x = _rand((n, h), 0, dtype)
    w = _rand((h,), 1) * 0.5 + 1.0
    b = _rand((h,), 2) * 0.1
    g = _rand((n, h), 3, dtype)

    got = jax.jit(fused_layer_norm_affine)(x, w, b)
    want = layer_norm_reference(x, w, b)
    _check("ln fwd", got, want, atol)

    def loss(x, w, b):
        return jnp.sum(fused_layer_norm_affine(x, w, b).astype(jnp.float32)
                       * g.astype(jnp.float32))

    def loss_ref(x, w, b):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, -1, keepdims=True)
        var = jnp.var(x32, -1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + 1e-5) * w + b
        return jnp.sum(y * g.astype(jnp.float32))

    got_g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, w, b)
    want_g = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for name, gg, ww in zip(["dx", "dw", "db"], got_g, want_g):
        _check(f"ln {name}", gg, ww, atol * 20, rtol=1e-3)


@case("layer_norm/1024")
def _():
    _ln_case(257, 1024)


@case("layer_norm/odd-769")
def _():
    _ln_case(64, 769)


@case("layer_norm/narrow-48")
def _():
    _ln_case(33, 48)


@case("layer_norm/bf16-1024")
def _():
    _ln_case(128, 1024, dtype=jnp.bfloat16, atol=2e-2)


# --- MLP ---------------------------------------------------------------------

@case("mlp/3-layer-odd-widths")
def _():
    from apex_tpu.ops.mlp import fused_mlp, mlp_reference
    x = _rand((96, 224), 0)
    ws = [_rand((224, 200), 1, scale=0.1), _rand((200, 136), 2, scale=0.1),
          _rand((136, 10), 3, scale=0.1)]
    bs = [_rand((200,), 4, scale=0.1), _rand((136,), 5, scale=0.1),
          _rand((10,), 6, scale=0.1)]
    got = jax.jit(functools.partial(fused_mlp, activation="relu"))(x, ws, bs)
    want = mlp_reference(x, ws, bs, activation="relu")
    _check("mlp fwd", got, want, 1e-4)

    g = _rand((96, 10), 7)

    def loss(x, ws, bs):
        return jnp.sum(fused_mlp(x, ws, bs, activation="relu") * g)

    def loss_ref(x, ws, bs):
        return jnp.sum(mlp_reference(x, ws, bs, activation="relu") * g)

    got_g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, ws, bs)
    want_g = jax.grad(loss_ref, argnums=(0, 1, 2))(x, ws, bs)
    for gg, ww in zip(jax.tree_util.tree_leaves(got_g),
                      jax.tree_util.tree_leaves(want_g)):
        _check("mlp grad", gg, ww, 1e-3, rtol=1e-3)


# --- xentropy ----------------------------------------------------------------

def _xent_case(n, v, smoothing):
    from apex_tpu.ops.xentropy import (softmax_cross_entropy_loss,
                                       softmax_cross_entropy_reference)
    x = _rand((n, v), 0, scale=2.0)
    labels = jnp.asarray(np.random.RandomState(1).randint(0, v, n),
                         jnp.int32)
    f = functools.partial(softmax_cross_entropy_loss, smoothing=smoothing)
    got = jax.jit(f)(x, labels)
    want = softmax_cross_entropy_reference(x, labels, smoothing=smoothing)
    _check("xent fwd", got, want, 1e-4)

    g = _rand((n,), 2)

    def loss(x):
        return jnp.sum(f(x, labels) * g)

    def loss_ref(x):
        return jnp.sum(softmax_cross_entropy_reference(
            x, labels, smoothing=smoothing) * g)

    got_g = jax.jit(jax.grad(loss))(x)
    want_g = jax.grad(loss_ref)(x)
    _check("xent dx", got_g, want_g, 1e-4, rtol=1e-4)


@case("xentropy/odd-vocab-1003")
def _():
    _xent_case(37, 1003, 0.0)


@case("xentropy/bert-vocab-smoothing")
def _():
    _xent_case(64, 30528, 0.1)


# --- multi-tensor arena kernels ---------------------------------------------

def _arena_buf(n_logical, seed, dtype=jnp.float32):
    """A flat arena buffer: n_logical live values, zero tail padding up
    to the launcher's 64Ki multiple (the tail-partition case)."""
    from apex_tpu.ops._dispatch import BLOCK_ROWS, LANES
    mult = BLOCK_ROWS * LANES
    n = -(-n_logical // mult) * mult
    vals = np.zeros(n, np.float32)
    vals[:n_logical] = np.random.RandomState(seed).randn(n_logical)
    return jnp.asarray(vals, dtype)


@case("multi_tensor/scale-axpby-norms")
def _():
    from apex_tpu.ops.multi_tensor import (
        multi_tensor_axpby, multi_tensor_l2norm, multi_tensor_maxnorm,
        multi_tensor_scale)
    x = _arena_buf(100_003, 0)
    y = _arena_buf(100_003, 1)
    out, finite = jax.jit(lambda x: multi_tensor_scale(x, 0.25))(x)
    _check("scale", out, np.asarray(x) * 0.25, 1e-6)
    assert bool(finite)
    out, finite = jax.jit(
        lambda x, y: multi_tensor_axpby(2.0, x, -0.5, y))(x, y)
    _check("axpby", out, 2.0 * np.asarray(x) - 0.5 * np.asarray(y), 1e-5)
    nrm = jax.jit(multi_tensor_l2norm)(x)
    _check("l2norm", nrm, np.linalg.norm(np.asarray(x)), 1e-2)
    mx = jax.jit(multi_tensor_maxnorm)(x)
    _check("maxnorm", mx, np.abs(np.asarray(x)).max(), 1e-6)
    # overflow flag fires on inf
    bad = x.at[17].set(jnp.inf)
    _, finite = jax.jit(lambda b: multi_tensor_scale(b, 1.0))(bad)
    assert not bool(finite)


# --- fused optimizer kernels -------------------------------------------------

def _vs_interpret(fn, *args):
    """Run ``fn`` compiled and in interpret mode; compare all outputs."""
    got = jax.jit(fn)(*args)
    with _interpret_oracle():
        want = jax.jit(fn).lower(*args).compile()(*args)
    for i, (gg, ww) in enumerate(zip(jax.tree_util.tree_leaves(got),
                                     jax.tree_util.tree_leaves(want))):
        _check(f"out[{i}]", gg, ww, 1e-5, rtol=1e-5)


@case("optim/adam")
def _():
    from apex_tpu.ops.optim_kernels import adam_update
    p, g = _arena_buf(70_001, 0), _arena_buf(70_001, 1)
    m, v = _arena_buf(70_001, 2) * 0.1, jnp.abs(_arena_buf(70_001, 3)) * 0.1

    def step(p, g, m, v):
        return adam_update(p, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999,
                           eps=1e-8, weight_decay=0.01, step=3,
                           param_copy_dtype=jnp.bfloat16)

    _vs_interpret(step, p, g, m, v)


@case("optim/sgd-nesterov-copy")
def _():
    from apex_tpu.ops.optim_kernels import sgd_update
    p, g, m = _arena_buf(70_001, 0), _arena_buf(70_001, 1), \
        _arena_buf(70_001, 2) * 0.1

    def step(p, g, m):
        return sgd_update(p, g, m, lr=0.1, momentum=0.9, weight_decay=1e-4,
                          nesterov=True, param_copy_dtype=jnp.bfloat16)

    _vs_interpret(step, p, g, m)


@case("optim/adagrad")
def _():
    from apex_tpu.ops.optim_kernels import adagrad_update
    p, g = _arena_buf(70_001, 0), _arena_buf(70_001, 1)
    h = jnp.abs(_arena_buf(70_001, 2)) * 0.1

    def step(p, g, h):
        return adagrad_update(p, g, h, lr=0.01, weight_decay=1e-4)

    _vs_interpret(step, p, g, h)


@case("optim/lamb-two-stage")
def _():
    from apex_tpu.ops.optim_kernels import lamb_stage1, lamb_stage2
    p, g = _arena_buf(70_001, 0), _arena_buf(70_001, 1)
    m, v = _arena_buf(70_001, 2) * 0.1, jnp.abs(_arena_buf(70_001, 3)) * 0.1
    ratio = jnp.abs(_arena_buf(70_001, 4)) * 0.01 + 1.0

    def step(p, g, m, v, ratio):
        u, m2, v2 = lamb_stage1(p, g, m, v, beta1=0.9, beta2=0.999,
                                eps=1e-6, weight_decay=0.01, step=2)
        return lamb_stage2(p, u, ratio, lr=1e-3), m2, v2

    _vs_interpret(step, p, g, m, v, ratio)


@case("optim/novograd")
def _():
    from apex_tpu.ops.optim_kernels import novograd_update
    p, g = _arena_buf(70_001, 0), _arena_buf(70_001, 1)
    m = _arena_buf(70_001, 2) * 0.1
    vnorm = jnp.abs(_arena_buf(70_001, 3)) + 0.1

    def step(p, g, m, vnorm):
        return novograd_update(p, g, m, vnorm, lr=1e-3, beta1=0.95,
                               beta2=0.98, eps=1e-8, weight_decay=1e-3,
                               step=2)

    _vs_interpret(step, p, g, m, vnorm)


# --- fused BN unit -----------------------------------------------------------

@case("bn_act/relu-grads")
def _():
    from apex_tpu.ops.bn_act import (bn_act_reference, bn_act_train,
                                     make_cfg)
    # odd spatial (14x14) and the C=64 sub-lane channel case
    x = _rand((16, 14, 14, 64), 0, jnp.bfloat16)
    s = _rand((64,), 2) * 0.5 + 1.0
    b = _rand((64,), 3) * 0.1
    g = _rand((16, 14, 14, 64), 4)
    cfg = make_cfg(relu=True)

    def loss(x, s, b):
        z, *_ = bn_act_train(x, s, b, cfg)
        return jnp.sum(z.astype(jnp.float32) * g)

    def loss_ref(x, s, b):
        z, _, _ = bn_act_reference(x, s, b, relu=True)
        return jnp.sum(z.astype(jnp.float32) * g)

    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, s, b)
    want = jax.grad(loss_ref, argnums=(0, 1, 2))(x, s, b)
    for gg, ww in zip(got, want):
        _check("bn_act relu grad", gg, ww, 5e-2, rtol=2e-2)


@case("bn_act/add-relu-grads")
def _():
    from apex_tpu.ops.bn_act import (bn_act_reference, bn_add_act_train,
                                     make_cfg)
    x = _rand((8, 14, 14, 64), 0, jnp.bfloat16)
    r = _rand((8, 14, 14, 64), 1, jnp.bfloat16)
    s = _rand((64,), 2) * 0.5 + 1.0
    b = _rand((64,), 3) * 0.1
    g = _rand((8, 14, 14, 64), 4)
    cfg = make_cfg(relu=True)

    def loss(x, r, s, b):
        z, *_ = bn_add_act_train(x, r, s, b, cfg)
        return jnp.sum(z.astype(jnp.float32) * g)

    def loss_ref(x, r, s, b):
        z, _, _ = bn_act_reference(x, s, b, residual=r, relu=True)
        return jnp.sum(z.astype(jnp.float32) * g)

    got = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(x, r, s, b)
    want = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, r, s, b)
    for gg, ww in zip(got, want):
        _check("bn_act grad", gg, ww, 5e-2, rtol=2e-2)


# --- monitor: zero-dispatch telemetry contract -------------------------------

@case("monitor/no-extra-dispatch")
def _():
    """The in-graph Metrics pytree must ride the existing step program:
    monitored and unmonitored toy train steps compile to the same number
    of HLO modules (one executable each), and the monitored module
    contains no host traffic (outfeed/infeed/host callbacks) — telemetry
    leaves the device only when the host logger flushes. A seeded
    ``jax.debug.print`` twin proves the detectors can fire at all."""
    from apex_tpu import amp
    from apex_tpu.monitor.check import module_count_and_host_ops
    from apex_tpu.optim import FusedSGD

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def build(monitored):
        amp_opt, state = amp.initialize(
            params, FusedSGD(lr=0.1), "O2", half_dtype=jnp.float16,
            verbosity=0, monitor=monitored)

        def train_step(state, x, y):
            def loss_fn(p):
                return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
            state, loss, _ = amp_opt.step(state, loss_fn)
            return state, loss

        return jax.jit(train_step), state

    mon_step, mon_state = build(True)
    plain_step, plain_state = build(False)
    n_mon, host_mon = module_count_and_host_ops(mon_step, mon_state, x, y)
    n_plain, _ = module_count_and_host_ops(plain_step, plain_state, x, y)
    assert n_mon == n_plain, (n_mon, n_plain)
    assert not host_mon, f"monitored step compiled host traffic: {host_mon}"

    # the positive twin every "zero host ops" assert rests on: the same
    # step with one seeded jax.debug.print MUST be seen — by the marker
    # scan and by apexlint's jaxpr (APX004) and HLO (APX103) rules — on
    # whatever spelling this jax/XLA gives a host callback
    from apex_tpu import lint

    def seeded_step(state, x, y):
        state, loss = plain_step(state, x, y)
        jax.debug.print("loss={l}", l=loss)
        return state, loss

    _, host_seeded = module_count_and_host_ops(
        jax.jit(seeded_step), plain_state, x, y)
    assert host_seeded, \
        "seeded jax.debug.print invisible to HOST_TRAFFIC_MARKERS"
    rep = lint.lint_step(jax.jit(seeded_step), plain_state, x, y)
    for rule in ("host-callback-in-step", "host-transfer"):
        assert rep.by_rule(rule), \
            f"seeded jax.debug.print invisible to apexlint {rule}"


# --- trace: span/probe zero-dispatch contract --------------------------------

@case("trace/no-extra-dispatch")
def _():
    """Spans and NaN probes with trace.debug_nans OFF must leave the
    compiled program identical to an unannotated twin: same HLO module
    count, no host traffic. With the mode ON the probes must actually
    appear (host callbacks in the HLO) — proving the guard flips real
    dispatch structure, not a no-op."""
    from apex_tpu import trace
    from apex_tpu.monitor.check import module_count_and_host_ops

    x = _rand((16, 32), 0)
    w = _rand((32, 8), 1, scale=0.1)

    def plain(w, x):
        h = jnp.tanh(x @ w)
        return jnp.sum(h * h)

    def traced(w, x):
        with trace.span("fwd"):
            h = jnp.tanh(x @ w)
        h = trace.nan_probe("fwd", h)
        with trace.span("loss"):
            return trace.nan_probe("loss", jnp.sum(h * h))

    n_t, host_t = module_count_and_host_ops(jax.jit(traced), w, x)
    n_p, _ = module_count_and_host_ops(jax.jit(plain), w, x)
    assert n_t == n_p, (n_t, n_p)
    assert not host_t, f"passive spans compiled host traffic: {host_t}"

    with trace.debug_nans():
        # the flag is trace-time and jax caches traces per function
        # object — drop the off-mode trace before recompiling
        jax.clear_caches()
        _, host_on = module_count_and_host_ops(jax.jit(traced), w, x)
    assert host_on, "debug_nans probes missing from the compiled HLO"
    trace.reset_nan_state()
    jax.clear_caches()


# --- memory/compile observability: zero-dispatch contract --------------------

@case("memory/no-extra-dispatch")
def _():
    """Memory sampling + compile_watch are pure host-side observers: a
    step driven under a CompileWatcher with allocator sampling and an
    attached MemoryReport must compile BIT-IDENTICAL HLO to an
    unwatched twin (same guarantee monitor/trace already pin), with no
    host traffic and exactly one trace in steady state."""
    import io

    from apex_tpu import monitor, prof
    from apex_tpu.monitor.check import module_count_and_host_ops

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        g = jax.grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    plain = jax.jit(train_step)
    hlo_plain = plain.lower(params, x, y).compile().as_text()

    watcher = prof.CompileWatcher()
    logger = monitor.MetricsLogger(
        sinks=[], memory_sink=monitor.JSONLSink(io.StringIO()))
    watcher.subscribe(logger.record_memory)
    watched = watcher.watch(train_step, name="train_step")

    watched(params, x, y)
    logger.sample_memory(step=0)
    rep = prof.memory_report(watched.jitted, params, x, y)
    logger.attach_memory_report(rep)
    watched(params, x, y)                      # steady state
    logger.close()

    hlo_watched = watched.jitted.lower(params, x, y).compile().as_text()
    assert hlo_watched == hlo_plain, \
        "watching/sampling changed the compiled program"
    assert watcher["train_step"].n_traces == 1, \
        watcher["train_step"].n_traces
    _n, host = module_count_and_host_ops(watched.jitted, params, x, y)
    assert not host, f"observed step compiled host traffic: {host}"
    assert rep.total_bytes > 0


# --- apexlint: strictly-AOT contract + kernel sweep --------------------------

@case("lint/no-extra-dispatch")
def _():
    """Linting a step is pure observation: a step compiled under
    apexlint (jaxpr trace + AOT compile inside lint_step) must leave
    the step's own compiled HLO BIT-IDENTICAL to the unobserved twin —
    lint never mutates the function, the trace cache, or compiler
    flags. Donated and undonated twins both pinned (the donation rule
    reads aliasing, it must not create it)."""
    from apex_tpu import lint

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        g = jax.grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    for donate in ((), (0,)):
        jitted = jax.jit(train_step, donate_argnums=donate)
        before = jitted.lower(params, x, y).compile().as_text()
        rep = lint.lint_step(jax.jit(train_step, donate_argnums=donate),
                             params, x, y)
        after = jitted.lower(params, x, y).compile().as_text()
        assert after == before, \
            f"lint observation changed the compiled program (donate=" \
            f"{donate})"
        # the lint itself must see a host-clean program
        assert not rep.by_rule("host-transfer"), rep.table()


@case("lint/precision-no-extra-dispatch")
def _():
    """The precision pass (APX3xx) is strictly AOT like its siblings:
    running it — default trace-side rules AND the APX306 fixture join
    (``precision=`` a measured stats dict) — leaves the step's own
    compiled HLO BIT-IDENTICAL, donated and undonated. A scale/unscale
    pair is built into the step so the taint machinery actually
    executes (the pin covers the analysis, not a vacuous walk)."""
    from apex_tpu import lint

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    scale = jnp.float32(1024.0)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y, s):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y)) * s
        g = jax.grad(loss_fn)(p)
        inv = (1.0 / s).astype(jnp.float32)
        g = jax.tree_util.tree_map(lambda a: a * inv, g)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    # a tiny synthetic measured fixture (the columnar
    # numerics.stats_from_json layout): one well-behaved site whose
    # exponents sit in a single mid-range binade — fp8-safe
    import numpy as _np
    from apex_tpu.monitor import numerics as nx
    hist = _np.zeros((1, nx.HIST_BINS))
    hist[0, nx.HIST_BINS // 2] = 1.0
    stats = {"sites": ("amp/cast/['w']",),
             "amax": [1.0], "amax_ema": [1.0],
             "amin": [0.5], "amin_ema": [0.5],
             "exp_hist": hist, "zero_frac": [0.0],
             "nonfinite_frac": [0.0], "uw_ratio": [-1.0]}
    assert nx.precision_report(stats).rows, "synthetic fixture invalid"

    for donate in ((), (0,)):
        jitted = jax.jit(train_step, donate_argnums=donate)
        before = jitted.lower(params, x, y, scale).compile().as_text()
        for precision in (None, stats):
            rep = lint.lint_step(
                jax.jit(train_step, donate_argnums=donate),
                params, x, y, scale, precision=precision)
            assert not [f for f in rep.findings
                        if f.rule in ("unscaled-narrow-cast",
                                      "scale-leak")], rep.table()
        after = jitted.lower(params, x, y, scale).compile().as_text()
        assert after == before, \
            f"precision pass changed the compiled program (donate=" \
            f"{donate})"


@case("lint/kernel-sweep")
def _():
    """apexlint HLO sweep over the kernel families the pinned cases
    above compile: every family's compiled module must carry zero
    error-severity findings (no host callbacks, no stray collectives,
    no un-aliased carried state) — the kernels are lint-clean by
    construction, and a regression that compiles host traffic into a
    kernel fails here before it costs a run."""
    from apex_tpu import lint
    from apex_tpu.ops.layer_norm import fused_layer_norm_affine
    from apex_tpu.ops.mlp import fused_mlp
    from apex_tpu.ops.multi_tensor import multi_tensor_scale
    from apex_tpu.ops.optim_kernels import adam_update
    from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
    from apex_tpu.prof import hlo as _hlo

    x = _rand((64, 256), 0)
    w = _rand((256,), 1) * 0.5 + 1.0
    b = _rand((256,), 2) * 0.1
    buf = _arena_buf(70_001, 3)
    labels = jnp.asarray(np.random.RandomState(4).randint(0, 1000, 64),
                         jnp.int32)
    sweep = {
        "layer_norm": (fused_layer_norm_affine, (x, w, b)),
        "mlp": (lambda a: fused_mlp(a, [_rand((256, 128), 5, scale=0.1)],
                                    [_rand((128,), 6, scale=0.1)]), (x,)),
        "xentropy": (lambda a: softmax_cross_entropy_loss(
            a, labels), (_rand((64, 1000), 7),)),
        "multi_tensor": (lambda v: multi_tensor_scale(v, 0.5), (buf,)),
        "optim_adam": (lambda p, g, m, v: adam_update(
            p, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.0, step=2),
            (buf, _arena_buf(70_001, 8), _arena_buf(70_001, 9) * 0.1,
             jnp.abs(_arena_buf(70_001, 10)) * 0.1)),
    }
    for name, (fn, args) in sweep.items():
        text = _hlo.compiled_hlo(fn, *args)
        findings = lint.lint_hlo_text(text)
        errors = [f for f in findings if f.severity == "error"]
        assert not errors, (
            f"kernel family {name} has error-severity lint findings: "
            + "; ".join(f"{f.rule}: {f.message}" for f in errors))
        print(f"  lint-swept {name}: {len(findings)} finding(s), "
              f"0 errors")


# --- ckpt: host-side-only snapshot contract ----------------------------------

@case("ckpt/no-extra-dispatch")
def _():
    """Checkpointing attached to a train loop must leave the step's
    compiled HLO BIT-IDENTICAL — donated and undonated: the snapshot is
    device copies + host-side writes BETWEEN dispatches, never ops
    inside the step program (the claim behind the <5%-of-step async
    overhead bound: only the copy dispatch rides the step path). Also
    pins the donation-safety contract itself: the state saved right
    before a donating dispatch restores bitwise after that dispatch
    invalidated the original buffers."""
    import tempfile

    from apex_tpu import amp, ckpt
    from apex_tpu.monitor.check import module_count_and_host_ops
    from apex_tpu.optim import FusedSGD

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}
    amp_opt, state0 = amp.initialize(
        params, FusedSGD(lr=0.1), "O2", half_dtype=jnp.float16,
        verbosity=0)

    def train_step(state, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        state, loss, _ = amp_opt.step(state, loss_fn)
        return state, loss

    for donate in ((), (0,)):
        jitted = jax.jit(train_step, donate_argnums=donate)
        before = jitted.lower(state0, x, y).compile().as_text()
        with tempfile.TemporaryDirectory() as tmp:
            mgr = ckpt.CheckpointManager(tmp)
            state = state0
            for i in range(3):
                state, loss = jitted(state, x, y)
                if i == 1:
                    mgr.save(i, state)      # the NEXT dispatch donates
            mgr.wait()                      # `state`'s buffers away
            after = jitted.lower(state0, x, y).compile().as_text()
            assert after == before, \
                f"checkpointing changed the compiled step (donate=" \
                f"{donate})"
            _n, host = module_count_and_host_ops(
                jax.jit(train_step, donate_argnums=donate), state0, x, y)
            assert not host, f"step compiled host traffic: {host}"
            if donate:
                # the donation-safety half: the original `saved` buffers
                # were invalidated by the i=2 dispatch, yet the
                # checkpoint restores the step-1 state bitwise
                restored, _m = mgr.restore(state0)
                rs = jax.tree_util.tree_leaves(restored.params)
                assert all(np.isfinite(np.asarray(l)).all() for l in rs)
                assert int(restored.step) == 2, int(restored.step)

# --- guard: in-graph detection zero-dispatch contract -------------------------

@case("guard/no-extra-dispatch")
def _():
    """Two halves of the guard's observability contract: (1) the
    in-graph detectors ride the existing step program — a guarded step
    compiles to the same number of HLO modules as its unguarded twin
    (one executable) with no host traffic (detection costs no extra
    dispatches); (2) attaching the HOST side — an observe-only
    GuardPolicy polling every step into a guard_sink — leaves the
    guarded step's compiled HLO BIT-IDENTICAL: observation is pure
    host-side reads, never ops."""
    import io

    from apex_tpu import guard, monitor
    from apex_tpu.monitor.check import module_count_and_host_ops

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}
    cfg = guard.GuardConfig(window=8, min_history=3)

    def loss_fn(p):
        return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

    def plain_step(p):
        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g), \
            loss

    def guarded_step(p, gs):
        loss, g = jax.value_and_grad(loss_fn)(p)
        gs = guard.guard_observe(gs, cfg, loss=loss, grads=g, params=p)
        new_p = jax.tree_util.tree_map(
            lambda a, b: a - 0.1 * gs.lr_scale * b, p, g)
        return guard.guard_commit(gs, new_p, p, cfg), gs, loss

    gs0 = guard.guard_init(cfg)
    n_g, host_g = module_count_and_host_ops(jax.jit(guarded_step),
                                            params, gs0)
    n_p, _ = module_count_and_host_ops(jax.jit(plain_step), params)
    assert n_g == n_p, (n_g, n_p)
    assert not host_g, f"guarded step compiled host traffic: {host_g}"

    # half 2: observe-only host policy + sink attached — bit-identical
    jitted = jax.jit(guarded_step)
    before = jitted.lower(params, gs0).compile().as_text()
    logger = monitor.MetricsLogger(
        sinks=[], guard_sink=monitor.JSONLSink(io.StringIO()))
    policy = guard.GuardPolicy(observe_only=True,
                               event_sink=logger.record_guard)
    p, gs = params, gs0
    for i in range(3):
        p, gs, loss = jitted(p, gs)
        act = policy.update(i, gs)
        assert act.kind == "none", act
    logger.close()
    after = jitted.lower(params, gs0).compile().as_text()
    assert after == before, \
        "observe-only guard observation changed the compiled program"


@case("integrity/no-extra-dispatch")
def _():
    """The silent-divergence defense's observability contract: (1) the
    fingerprint fold + cross-replica compare ride the existing step
    program — the instrumented step compiles to ONE executable with no
    host traffic (off-steps take the empty ``lax.cond`` branch: no
    fold, no collective, and the host polls only cumulative counters);
    (2) attaching the HOST side — a GuardPolicy polling GuardState AND
    IntegrityState every step into guard/integrity sinks — leaves the
    compiled HLO BIT-IDENTICAL, donated and undonated (observation is
    pure host-side reads, never ops). Same guarantee the
    monitor/guard/goodput/cluster cases pin for their layers."""
    import io

    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import guard, monitor, parallel
    from apex_tpu.monitor.check import module_count_and_host_ops
    from apex_tpu.trace.spans import span

    devs = jax.devices()
    if len(devs) < 2:
        print("  (skip: <2 local devices — no dp axis to fingerprint "
              "across)")
        return
    mesh = Mesh(np.array(devs), ("data",))
    world = len(devs)
    cfg = guard.GuardConfig(window=8, min_history=3)
    icfg = guard.IntegrityConfig(check_every=4)   # steps 1-3 are OFF

    n = 16 * world
    x = _rand((n, 32), 0)
    y = _rand((n, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def body(p, gs, ist, x, y, fingerprinted):
        if fingerprinted:
            ist = guard.integrity_check(ist, icfg, p, axis_name="data")

        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))

        loss, g = jax.value_and_grad(loss_fn)(p)
        with span("ddp/sync_gradients", kind="collective"):
            g = parallel.sync_gradients(g, "data")
        with span("ddp/loss_pmean", kind="collective"):
            loss = jax.lax.pmean(loss, "data")
        gs = guard.guard_observe(
            gs, cfg, loss=loss, grads=g, params=p,
            replica_ok=guard.integrity_ok(ist) if fingerprinted
            else None)
        new_p = jax.tree_util.tree_map(
            lambda a, b: a - 0.1 * gs.lr_scale * b, p, g)
        return guard.guard_commit(gs, new_p, p, cfg), gs, ist, loss

    def build(fingerprinted, donate):
        fn = functools.partial(body, fingerprinted=fingerprinted)
        mapped = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P()), check_vma=False)
        kw = {"donate_argnums": (0, 1, 2)} if donate else {}
        return jax.jit(mapped, **kw)

    gs0 = guard.guard_init(cfg)
    ist0 = guard.integrity_init(icfg, world=world)

    # half 1: one executable, no host ops (module-count parity with
    # the fingerprint-less guarded twin)
    n_i, host_i = module_count_and_host_ops(build(True, False),
                                            params, gs0, ist0, x, y)
    n_g, _ = module_count_and_host_ops(build(False, False),
                                       params, gs0, ist0, x, y)
    assert n_i == n_g, (n_i, n_g)
    assert not host_i, \
        f"fingerprinted step compiled host traffic: {host_i}"

    # half 2: host polling (guard + integrity, every step — three of
    # four being off-steps) leaves the program bit-identical, donated
    # and undonated
    for donate in (False, True):
        jitted = build(True, donate)
        before = jitted.lower(params, gs0, ist0, x, y) \
            .compile().as_text()
        logger = monitor.MetricsLogger(
            sinks=[], guard_sink=monitor.JSONLSink(io.StringIO()),
            integrity_sink=monitor.JSONLSink(io.StringIO()))
        policy = guard.GuardPolicy(
            observe_only=True, event_sink=logger.record_guard,
            integrity_sink=logger.record_integrity)
        # fresh unaliased buffers: the zero-scalar counters of a
        # freshly-init'd GuardState/IntegrityState share one cached
        # device constant, which a donating jit would refuse to donate
        # twice
        p, gs, ist = jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), (params, gs0, ist0))
        for i in range(4):
            p, gs, ist, loss = jitted(p, gs, ist, x, y)
            act = policy.update(i, gs)
            iact = policy.update_integrity(i, ist)
            assert act.kind == "none" and iact.kind == "none"
        logger.close()
        after = jitted.lower(params, gs0, ist0, x, y) \
            .compile().as_text()
        assert after == before, (
            f"integrity observation changed the compiled program "
            f"(donate={donate})")


@case("goodput/no-extra-dispatch")
def _():
    """The goodput observatory is pure host-side observation: a step
    driven under a Tracer with per-phase spans, a GoodputLedger folding
    every step, a heartbeat writer beating the shared-fs straggler
    files, and a compile watcher feeding recompile spans must compile
    BIT-IDENTICAL HLO to the unobserved twin (same guarantee the
    monitor/trace/memory/guard cases pin), with no host traffic — and
    the ledger's bucket sum must close over each step's measured wall
    time (the attribution-closure contract
    ``scripts/goodput_audit.py --cpu8`` pins at 5%)."""
    import io
    import tempfile

    from apex_tpu import monitor, prof, trace
    from apex_tpu.monitor.check import module_count_and_host_ops

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        g = jax.grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    plain = jax.jit(train_step)
    hlo_plain = plain.lower(params, x, y).compile().as_text()

    watcher = prof.CompileWatcher()
    logger = monitor.MetricsLogger(
        sinks=[], goodput_sink=monitor.JSONLSink(io.StringIO()))
    tracer = trace.Tracer()
    ledger = monitor.GoodputLedger(tracer, tolerance=0.05)
    ledger.subscribe(logger.record_goodput)
    watched = watcher.watch(train_step, name="train_step")
    p = params
    with tempfile.TemporaryDirectory() as tmp:
        hb = trace.HeartbeatWriter(tmp, rank=0)
        tracer.subscribe(hb.on_step)
        with tracer:
            for i in range(4):
                with trace.step(i):
                    with trace.span("dispatch"):
                        p = watched(p, x, y)
                    with trace.span("fetch"):
                        jax.block_until_ready(p)
        assert hb.n_written == 4 and hb.n_dropped == 0
    logger.close()

    hlo_obs = watched.jitted.lower(params, x, y).compile().as_text()
    assert hlo_obs == hlo_plain, \
        "goodput observation changed the compiled program"
    _n, host = module_count_and_host_ops(watched.jitted, params, x, y)
    assert not host, f"observed step compiled host traffic: {host}"
    assert len(ledger.steps) == 4
    ok, worst = ledger.check_closure()
    assert ok, f"attribution closure broke: worst error {worst:.4f}"
    # step 0 folded the trace+compile: its back-dated compile span must
    # land in the recompile bucket, and steady state must not
    assert ledger.steps[0].buckets["recompile"] > 0
    assert ledger.steps[-1].buckets["recompile"] == 0


@case("sharding/no-extra-dispatch")
def _():
    """Per-axis sharding attribution is pure AOT observation: building
    the :func:`apex_tpu.prof.shard_report` (HLO sharding annotations +
    memory report join) and the per-axis wire split
    (:func:`apex_tpu.monitor.collective_bytes_by_axis`) off a compiled
    step, then attaching both to the sharding event channel, must
    leave the compiled HLO BIT-IDENTICAL — donated and undonated —
    with zero host ops in the observed module (same guarantee the
    monitor/memory/goodput cases pin for their layers). The grad
    sync's wire bytes must land on the ``data`` axis row (the registry
    join), never silently in ``unknown``."""
    import io

    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu import monitor, parallel, prof
    from apex_tpu.lint.mesh_model import parse_mesh_spec
    from apex_tpu.monitor.check import module_count_and_host_ops
    from apex_tpu.trace.spans import span

    devs = jax.devices()
    if len(devs) < 2:
        print("  (skip: <2 local devices — no data axis to attribute)")
        return
    world = len(devs)
    mesh = Mesh(np.array(devs), ("data",))
    mm = parse_mesh_spec(f"ici{world}")

    n = 16 * world
    x = _rand((n, 32), 0)
    y = _rand((n, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        loss, g = jax.value_and_grad(loss_fn)(p)
        with span("ddp/sync_gradients", kind="collective"):
            g = parallel.sync_gradients(g, "data")
        new_p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
        return new_p, loss

    def build(donate):
        mapped = jax.shard_map(
            train_step, mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=(P(), P()), check_vma=False)
        kw = {"donate_argnums": (0,)} if donate else {}
        return jax.jit(mapped, **kw)

    for donate in (False, True):
        jitted = build(donate)
        compiled = jitted.lower(params, x, y).compile()
        before = compiled.as_text()
        sr = prof.shard_report(compiled, mm)
        wire = {ax: sum(per.values()) for ax, per in
                monitor.collective_bytes_by_axis(before).items()}
        logger = monitor.MetricsLogger(
            sinks=[], sharding_sink=monitor.JSONLSink(io.StringIO()))
        logger.attach_shard_report(sr, wire_by_axis=wire)
        logger.close()
        after = jitted.lower(params, x, y).compile().as_text()
        assert after == before, (
            f"sharding attribution changed the compiled program "
            f"(donate={donate})")
        assert wire.get("data", 0) > 0, (
            f"grad sync not attributed to the data axis: {wire}")
        ok, worst = sr.closure()
        assert ok, f"per-axis HBM closure broke: {worst:.4f}"
        assert sr.axis_bytes("data")["sharded_bytes"] > 0, (
            "nothing attributed sharded over the data axis")
    _n, host = module_count_and_host_ops(build(False), params, x, y)
    assert not host, f"observed step compiled host traffic: {host}"


@case("roofline/no-extra-dispatch")
def _():
    """Roofline observation is AOT + offline: compiling the step for
    the analytic side, capturing a profiler trace around it, parsing
    the xplane, building the roofline report, and attaching it to a
    logger must leave the compiled HLO BIT-IDENTICAL (donated and
    undonated) — the report reads the module and the trace, never the
    program. Same guarantee the monitor/trace/memory/goodput cases
    pin."""
    import io
    import tempfile

    from apex_tpu import monitor, prof

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        g = jax.grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    for donate in (False, True):
        kw = {"donate_argnums": (0,)} if donate else {}
        plain = jax.jit(train_step, **kw)
        hlo_plain = plain.lower(params, x, y).compile().as_text()

        observed = jax.jit(train_step, **kw)
        compiled = observed.lower(params, x, y).compile()
        with tempfile.TemporaryDirectory() as tmp:
            with prof.trace(tmp):
                p2 = observed(params, x, y)
                jax.block_until_ready(p2)
            profile = prof.parse_trace(tmp)     # no device plane on CPU
        rep = prof.roofline_report(compiled=compiled, profile=profile
                                   if profile.ops else None)
        logger = monitor.MetricsLogger(
            sinks=[], roofline_sink=monitor.JSONLSink(io.StringIO()))
        logger.attach_roofline_report(rep)
        logger.close()
        assert rep.rows, "roofline report attributed no ops"

        hlo_obs = observed.lower(params, x, y).compile().as_text()
        assert hlo_obs == hlo_plain, (
            f"roofline observation changed the compiled program "
            f"(donate={donate})")


@case("cluster/no-extra-dispatch")
def _():
    """The cluster control plane is host-side only: a step driven
    under full membership instrumentation — a joined
    ClusterMembership renewing its lease every step, a
    generation-fenced CheckpointManager saving mid-loop, a
    RecoveryCoordinator polling for peer intents, and a
    CollectiveDeadline watching the tracer's collective spans — must
    compile BIT-IDENTICAL HLO to the uninstrumented twin, donated and
    undonated (membership is lease files + fence checks BETWEEN
    dispatches, never ops). Same guarantee the ckpt/guard/goodput
    cases pin for their layers."""
    import tempfile

    from apex_tpu import ckpt, cluster, trace

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}

    def train_step(p, x, y):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        g = jax.grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)

    for donate in ((), (0,)):
        plain = jax.jit(train_step, donate_argnums=donate)
        hlo_plain = plain.lower(params, x, y).compile().as_text()

        jitted = jax.jit(train_step, donate_argnums=donate)
        tracer = trace.Tracer()
        with tempfile.TemporaryDirectory() as tmp:
            member = cluster.ClusterMembership(
                os.path.join(tmp, "cluster"), rank=0)
            member.join()
            coord = cluster.RecoveryCoordinator(member,
                                                barrier_timeout_s=0.2)
            deadline = cluster.CollectiveDeadline(
                tracer, deadline_s=60.0, generation=member.refresh)
            mgr = ckpt.CheckpointManager(os.path.join(tmp, "ck"),
                                         fence=member, rank=0,
                                         process_count=1)
            p = params
            with tracer:
                for i in range(3):
                    with trace.step(i):
                        p = jitted(p, x, y)
                        jax.block_until_ready(p)
                    member.heartbeat()
                    assert deadline.poll_once() is None
                    assert not coord.peer_requested()
                    if i == 1:
                        mgr.save(i, p)
            mgr.wait()
            assert member.check("commit") == 0    # fence valid: gen 0
            member.leave()
        hlo_obs = jitted.lower(params, x, y).compile().as_text()
        assert hlo_obs == hlo_plain, (
            f"cluster membership instrumentation changed the compiled "
            f"step (donate={donate})")


@case("numerics/no-extra-dispatch")
def _():
    """The numerics observatory's observability contract: (1) the
    per-site fold (amax/amin EMAs, exponent histograms, uw ratios) and
    the in-graph ScaleHistory update ride the existing step program —
    the instrumented step compiles to ONE executable with no host
    traffic (off-steps take the empty ``lax.cond`` branch: no fold, no
    scatter-add); (2) the HOST side — polling NumericsState into
    ``check_events`` / ``precision_report`` / ``scale_update_events``
    through a ``numerics_sink`` every step — leaves the compiled HLO
    BIT-IDENTICAL, donated and undonated (observation is pure
    host-side reads, never ops). Same guarantee the
    monitor/guard/integrity cases pin for their layers."""
    import io

    from apex_tpu import amp, monitor
    from apex_tpu.monitor import numerics as _nx
    from apex_tpu.monitor.check import module_count_and_host_ops

    x = _rand((16, 32), 0)
    y = _rand((16, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}
    ncfg = _nx.NumericsConfig(check_every=4)   # steps 1-3 are OFF
    scfg = amp.ScaleHistoryConfig(window=4)
    sites = _nx.site_names({"grads": params, "params": params})
    n_sites = len(sites)
    grad_rows = [i for i, s in enumerate(sites)
                 if s.startswith("grads/")]

    def body(p, ns, sh, x, y, observed):
        def loss_fn(p):
            return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
        g = jax.grad(loss_fn)(p)
        if observed:
            ns = _nx.numerics_observe(ns, ncfg,
                                      {"grads": g, "params": p})
            sh = amp.scale_history_update(
                sh, scfg, _nx.scale_amax(ns, grad_rows))
        new_p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
        return new_p, ns, sh, jnp.float32(0)

    def build(observed, donate):
        fn = functools.partial(body, observed=observed)
        kw = {"donate_argnums": (0, 1, 2)} if donate else {}
        return jax.jit(fn, **kw)

    ns0 = _nx.numerics_init(ncfg, sites=sites)
    sh0 = amp.scale_history_init(scfg, n_sites=len(grad_rows))

    # half 1: one executable, no host ops (module-count parity with
    # the unobserved twin)
    n_o, host_o = module_count_and_host_ops(build(True, False),
                                            params, ns0, sh0, x, y)
    n_p, _ = module_count_and_host_ops(build(False, False),
                                       params, ns0, sh0, x, y)
    assert n_o == n_p, (n_o, n_p)
    assert not host_o, \
        f"numerics-observed step compiled host traffic: {host_o}"

    # half 2: host polling every step (three of four being off-steps)
    # leaves the program bit-identical, donated and undonated
    for donate in (False, True):
        jitted = build(True, donate)
        before = jitted.lower(params, ns0, sh0, x, y) \
            .compile().as_text()
        logger = monitor.MetricsLogger(
            sinks=[], numerics_sink=monitor.JSONLSink(io.StringIO()))
        # fresh unaliased buffers: freshly-init'd states share cached
        # zero-scalar constants a donating jit would refuse to donate
        # twice
        p, ns, sh = jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), (params, ns0, sh0))
        for i in range(4):
            # fetch BEFORE the (possibly donating) dispatch — donation
            # invalidates the input buffers, the same hazard
            # MetricsLogger(donation_safe=) covers for metrics
            prev_sh = jax.device_get(sh)
            p, ns, sh, _loss = jitted(p, ns, sh, x, y)
            for ev in _nx.check_events(ns, sites,
                                       current_dtype="bfloat16"):
                logger.record_numerics(ev)
            for ev in amp.scale_update_events(
                    prev_sh, sh, tuple(sites[i] for i in grad_rows)):
                logger.record_numerics(ev)
            rep = _nx.precision_report(ns, sites,
                                       current_dtypes="float32")
            for ev in rep.to_events():
                logger.record_numerics(ev)
        logger.close()
        assert int(jax.device_get(ns.check_count)) == 1
        after = jitted.lower(params, ns0, sh0, x, y) \
            .compile().as_text()
        assert after == before, (
            f"numerics observation changed the compiled program "
            f"(donate={donate})")


@case("dynamics/no-extra-dispatch")
def _():
    """The training-dynamics observatory's observability contract:
    (1) the fold — GNS/geometry probe collectives included (the
    ``ddp/dynamics_gns`` psum and ``ddp/dynamics_geom`` all-gather ride
    inside the step's shard_map next to the gradient pmean) — compiles
    to ONE executable with no host traffic, module-count parity with
    the unobserved twin (off-steps take the empty ``lax.cond`` branch);
    (2) the HOST side — polling DynamicsState into ``check_events`` /
    ``dynamics_report`` through a ``dynamics_sink`` every step — leaves
    the compiled HLO BIT-IDENTICAL, donated and undonated. Same
    guarantee the monitor/guard/integrity/numerics cases pin for their
    layers."""
    import io

    from jax.sharding import PartitionSpec as P

    from apex_tpu import monitor
    from apex_tpu.monitor import dynamics as _dx
    from apex_tpu.monitor.check import module_count_and_host_ops
    from apex_tpu.parallel import distributed as _dist

    devs = jax.devices()
    world = len(devs)
    mesh = jax.sharding.Mesh(np.array(devs), ("data",))
    local_batch = 8
    x = _rand((local_batch * world, 32), 0)
    y = _rand((local_batch * world, 8), 1)
    params = {"w": _rand((32, 8), 2, scale=0.1),
              "b": jnp.zeros((8,), jnp.float32)}
    dcfg = _dx.DynamicsConfig(check_every=4,        # steps 1-3 are OFF
                              local_batch=local_batch)
    sites = _dx.site_names({"dynamics/update": params})

    def body(p, ds, x, y, observed):
        def inner(p, ds, x, y):
            def loss_fn(p):
                return jnp.mean(jnp.square(x @ p["w"] + p["b"] - y))
            g_local = jax.grad(loss_fn)(p)
            g = jax.tree_util.tree_map(
                lambda a: jax.lax.pmean(a, "data"), g_local)
            new_p = jax.tree_util.tree_map(
                lambda a, b: a - 0.1 * b, p, g)
            if observed:
                ds = _dx.dynamics_observe(
                    ds, dcfg,
                    lambda: {"dynamics/update": jax.tree_util.tree_map(
                        lambda n, o: n - o, new_p, p)},
                    probe=lambda: _dist.dynamics_probe(g_local, g,
                                                       "data"),
                    grads={"dynamics/update": g},
                    weights={"dynamics/update": p})
            return new_p, ds, jnp.float32(0)
        return jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P()),
            check_vma=False)(p, ds, x, y)

    def build(observed, donate):
        fn = functools.partial(body, observed=observed)
        kw = {"donate_argnums": (0, 1)} if donate else {}
        return jax.jit(fn, **kw)

    ds0 = _dx.dynamics_init(dcfg, sites=sites, world=world)

    # half 1: one executable, no host ops (module-count parity with
    # the unobserved twin)
    n_o, host_o = module_count_and_host_ops(build(True, False),
                                            params, ds0, x, y)
    n_p, _ = module_count_and_host_ops(build(False, False),
                                       params, ds0, x, y)
    assert n_o == n_p, (n_o, n_p)
    assert not host_o, \
        f"dynamics-observed step compiled host traffic: {host_o}"

    # half 2: host polling every step (three of four being off-steps)
    # leaves the program bit-identical, donated and undonated
    for donate in (False, True):
        jitted = build(True, donate)
        before = jitted.lower(params, ds0, x, y).compile().as_text()
        logger = monitor.MetricsLogger(
            sinks=[], dynamics_sink=monitor.JSONLSink(io.StringIO()))
        # fresh unaliased buffers: freshly-init'd states share cached
        # zero-scalar constants a donating jit would refuse to donate
        # twice
        p, ds = jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), (params, ds0))
        for _ in range(4):
            p, ds, _loss = jitted(p, ds, x, y)
            for ev in _dx.check_events(ds, sites,
                                       local_batch=local_batch):
                logger.record_dynamics(ev)
            _dx.dynamics_report(ds, sites, local_batch=local_batch)
        logger.close()
        assert int(jax.device_get(ds.check_count)) == 1
        after = jitted.lower(params, ds0, x, y).compile().as_text()
        assert after == before, (
            f"dynamics observation changed the compiled program "
            f"(donate={donate})")


def _pod_budget():
    """Import scripts.pod_comm_budget (the shared HLO audit helpers)
    regardless of cwd — the module lives next to the package root."""
    try:
        from scripts import pod_comm_budget
    except ImportError:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        if root not in sys.path:
            sys.path.insert(0, root)
        from scripts import pod_comm_budget
    return pod_comm_budget


def _ddp_toy_step(mesh, n, **ddp_kw):
    """A small stacked-matmul DDP step, lowered with avals (works on
    abstract AOT topology devices and real meshes alike). Returns the
    compiled HLO text and the grad-leaf avals."""
    from jax.sharding import PartitionSpec as P
    from apex_tpu import parallel

    ddp = parallel.DistributedDataParallel(mesh, **ddp_kw)
    names = [f"w{i}" for i in range(8)]

    def loss_fn(p, x):
        h = x
        for k in names:
            h = jnp.tanh(h @ p[k])
        return jnp.sum(h * h)

    def step(p, x):
        l, g = jax.value_and_grad(loss_fn)(p, x)
        g = ddp.sync(g)
        p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
        return p, jax.lax.pmean(l, parallel.DATA_AXIS)

    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(parallel.DATA_AXIS)),
        out_specs=(P(), P()), check_vma=False))
    p_s = {k: jax.ShapeDtypeStruct((128, 128), jnp.float32)
           for k in names}
    x_s = jax.ShapeDtypeStruct((4 * n, 128), jnp.float32)
    hlo = mapped.lower(p_s, x_s).compile().as_text()
    return hlo, list(p_s.values())


@case("ddp/overlap-start-done")
def _():
    """Bucketed DDP sync must compile to one all-reduce PER BUCKET (the
    chained barriers keep the combiner from re-merging them into a
    terminal collective); on a TPU-scheduled module the pairs must be
    async ``all-reduce-start``/``-done`` with real compute scheduled
    inside at least one window — the overlap the latency-hiding
    scheduler is given to exploit. Prefers a real multi-chip AOT target
    (async pairs only exist in TPU-scheduled modules); falls back to
    the local device mesh (CI: 8 virtual CPU devices) for the
    structural bucket-count half of the claim."""
    from jax.sharding import Mesh
    from apex_tpu.parallel import comm
    overlap_audit = _pod_budget().overlap_audit

    devs = None
    if jax.default_backend() == "tpu":
        # only probe AOT topologies where a TPU runtime is actually
        # attached — off-TPU the libtpu metadata fetch retries for
        # minutes before failing
        try:
            from jax.experimental import topologies
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            devs = np.array(topo.devices)
        except Exception:
            devs = None
    if devs is None:
        local = jax.devices()
        if len(local) < 2:
            print("  (skip: no AOT topology support and <2 local "
                  "devices — collectives would fold away)")
            return
        devs = np.array(local)
    n = devs.size
    mesh = Mesh(devs, ("data",))
    message_size = 40_000              # 128x128 leaves -> ~2 per bucket
    hlo, leaves = _ddp_toy_step(mesh, n, bucket_allreduce=True,
                                message_size=message_size)
    n_buckets = len(comm.bucket_plan(leaves, message_size))
    assert n_buckets >= 3, f"toy plan degenerate: {n_buckets} buckets"
    n_ar = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    # the scalar loss pmean adds one small all-reduce
    assert n_ar >= n_buckets, (
        f"buckets merged: {n_ar} all-reduces < {n_buckets} buckets")
    pairs = overlap_audit(hlo)
    if pairs:   # TPU-scheduled module: the async-overlap half
        assert any(p["compute_between"] > 0 for p in pairs), (
            "no compute scheduled between any start/done pair: "
            f"{pairs}")


@case("ddp/no-compress-bitident")
def _():
    """The default (no-bucket, no-compress) DDP sync must compile to a
    program structurally identical to a direct sync_gradients call —
    same instruction opcodes in the same order, same collectives. The
    new comm modes are strictly opt-in: that includes the hierarchical
    ``comm_plan`` — ``comm_plan=None`` (explicit or defaulted) must
    leave the compiled text BIT-identical to the default path."""
    from jax.sharding import Mesh
    from apex_tpu import parallel
    collectives = _pod_budget().collectives

    local = jax.devices()
    if len(local) < 2:
        print("  (skip: <2 local devices — sync collectives fold away)")
        return
    n = len(local)
    mesh = Mesh(np.array(local), ("data",))
    # compiled from ONE call site: the module text records the Python
    # stack it was traced under (StackFrames tables; on a TPU also inside
    # each Mosaic kernel's payload), so two lines give two texts
    hlo_ddp, hlo_none = (_ddp_toy_step(mesh, n, **kw)[0]
                         for kw in ({}, {"comm_plan": None}))
    assert hlo_none == hlo_ddp, (
        "comm_plan=None changed the compiled default DDP program")

    # the manual twin: same step body, sync_gradients under the same
    # collective span DDP.sync uses
    from jax.sharding import PartitionSpec as P
    from apex_tpu.trace.spans import span as _span
    names = [f"w{i}" for i in range(8)]

    def loss_fn(p, x):
        h = x
        for k in names:
            h = jnp.tanh(h @ p[k])
        return jnp.sum(h * h)

    def step(p, x):
        l, g = jax.value_and_grad(loss_fn)(p, x)
        with _span("ddp/sync_gradients", kind="collective"):
            g = parallel.sync_gradients(g, parallel.DATA_AXIS)
        p = jax.tree_util.tree_map(lambda a, b: a - 0.1 * b, p, g)
        return p, jax.lax.pmean(l, parallel.DATA_AXIS)

    mapped = jax.jit(jax.shard_map(
        step, mesh=mesh, in_specs=(P(), P(parallel.DATA_AXIS)),
        out_specs=(P(), P()), check_vma=False))
    p_s = {k: jax.ShapeDtypeStruct((128, 128), jnp.float32)
           for k in names}
    x_s = jax.ShapeDtypeStruct((4 * n, 128), jnp.float32)
    hlo_ref = mapped.lower(p_s, x_s).compile().as_text()

    def _opcode_seq(hlo):
        import re
        return [m.group(1) for m in re.finditer(
            r"= (?:\(?[\w\[\]{},: ]*\)?) ([\w-]+)\(", hlo)]

    assert collectives(hlo_ddp) == collectives(hlo_ref), (
        collectives(hlo_ddp), collectives(hlo_ref))
    assert _opcode_seq(hlo_ddp) == _opcode_seq(hlo_ref), (
        "default DDP sync compiled a structurally different program")


@case("autotune/no-extra-dispatch")
def _():
    """The tuning-DB consult is a trace-time table lookup with an
    exact-key contract: a shape that MISSES the DB must compile HLO
    BIT-IDENTICAL to ``APEX_TPU_AUTOTUNE=off`` (donated and undonated)
    — no extra dispatch, no reordered ops, nothing. And the positive
    twin: an exact-key HIT on a seeded DB must actually change the
    realized block (a different grid → a different program), proving
    the consult happens at trace time rather than being dead code."""
    import os

    from apex_tpu import ops
    from apex_tpu.ops import autotune

    x = _rand((96, 72), 0)
    w = jnp.ones((72,), jnp.float32)
    b = jnp.zeros((72,), jnp.float32)
    # 2x BUFFER_MULTIPLE: a legal arena length whose fingerprint is
    # NOT in the committed DB (the committed optimizer entry is the
    # 1x-BUFFER_MULTIPLE sweep shape)
    buf = _rand((2 * 512 * 128,), 1)

    def make_step():
        # a fresh function object per compile — jit's trace cache is
        # keyed on identity, and the env/DB consult happens at trace
        # time, so a shared object would reuse the first trace
        def step(x_, w_, b_, buf_):
            y = ops.fused_layer_norm_affine(x_, w_, b_)
            scaled, ok = ops.multi_tensor_scale(buf_, 0.5)
            return y.sum() + scaled.sum() + ok.astype(jnp.float32)
        return step

    # positive twin: an exact-key hit changes the realized block, hence
    # the program
    entry = autotune.TuningEntry(
        family="layer_norm", dims=(96, 72), dtype="float32",
        chip=autotune.chip_kind(), block={"block_rows": 32})
    seeded = autotune.TuningDB({entry.fingerprint: entry})
    # off: no consult. db: the committed DB, which these shapes are not
    # in — exact-key miss, defaults. hit: the seeded DB.
    variants = (("off", "off", None), ("db", "db", None),
                ("hit", "db", seeded))

    prev = os.environ.get("APEX_TPU_AUTOTUNE")
    try:
        for donate in (False, True):
            kw = {"donate_argnums": (0,)} if donate else {}
            hlo, counts = {}, {}
            for name, mode, db in variants:
                os.environ["APEX_TPU_AUTOTUNE"] = mode
                with (autotune.use_db(db) if db is not None
                      else contextlib.nullcontext()):
                    autotune.reset_counters()
                    # ONE call site for every variant: the text records
                    # the Python stack it was traced under, down to the
                    # Mosaic kernels' payloads
                    hlo[name] = jax.jit(make_step(), **kw).lower(
                        x, w, b, buf).compile().as_text()
                    counts[name] = autotune.counters()
            assert hlo["db"] == hlo["off"], (
                f"DB-miss path compiled a different program than "
                f"APEX_TPU_AUTOTUNE=off (donate={donate})")
            c = counts["db"]
            assert c["misses"] >= 2 and c["hits"] == 0, (
                f"expected pure trace-time misses, got {c}")
            assert counts["hit"]["hits"] == 1, counts["hit"]
            assert hlo["hit"] != hlo["off"], (
                "an exact-key tuned hit left the program unchanged — "
                "the consult is not reaching the dispatch seam")
    finally:
        if prev is None:
            os.environ.pop("APEX_TPU_AUTOTUNE", None)
        else:
            os.environ["APEX_TPU_AUTOTUNE"] = prev


# --- driver ------------------------------------------------------------------

def run(pattern: Optional[str] = None,
        json_path: Optional[str] = None) -> bool:
    backend = jax.default_backend()
    device = getattr(jax.devices()[0], "device_kind", "?")
    results: List[Dict] = []
    ok = True
    for name, fn in CASES:
        if pattern and pattern not in name:
            continue
        try:
            fn()
            results.append({"case": name, "ok": True})
            print(f"  ok    {name}", flush=True)
        except Exception as e:
            ok = False
            err = "".join(traceback.format_exception_only(type(e), e))[:2000]
            results.append({"case": name, "ok": False, "error": err})
            print(f"  FAIL  {name}\n{traceback.format_exc()}", flush=True)
    summary = {
        "backend": backend, "device": device,
        "compiled": backend == "tpu",
        "ok": ok, "n_cases": len(results),
        "n_failed": sum(1 for r in results if not r["ok"]),
        "results": results,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(summary, f, indent=1)
    print(f"compile-check: {summary['n_cases'] - summary['n_failed']}/"
          f"{summary['n_cases']} ok on {device} "
          f"({'compiled' if summary['compiled'] else 'interpret'})")
    return ok


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    pattern = None
    json_path = None
    interpret = False
    it = iter(argv)
    for a in it:
        if a == "--json":
            json_path = next(it)
        elif a in ("-k", "--filter"):
            pattern = next(it)
        elif a == "--interpret":
            interpret = True
        elif a == "--compile-check":
            pass
        else:
            print(f"usage: python -m apex_tpu.ops [--compile-check] "
                  f"[-k PATTERN] [--json PATH] [--interpret]")
            return 2
    # the point of this command is to COMPILE the kernels; off a TPU the
    # library interprets them all, which proves nothing about Mosaic —
    # that run has to be asked for by name
    if jax.default_backend() != "tpu" and not interpret:
        print(f"python -m apex_tpu.ops: no TPU (backend is "
              f"{jax.default_backend()!r}), so every kernel would run in "
              f"interpret mode and none would be compiled; pass "
              f"--interpret if that is what you want", file=sys.stderr)
        return 2
    from apex_tpu.utils import enable_compile_cache
    enable_compile_cache()
    return 0 if run(pattern, json_path) else 1


if __name__ == "__main__":
    sys.exit(main())
