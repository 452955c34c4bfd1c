"""Fused multihead attention — blockwise (flash) Pallas kernels.

TPU-native rebuild of the `fast_multihead_attn` family
(`apex/contrib/csrc/multihead_attn/*`: fused QKV GEMM → CUTLASS strided-
batched GEMM → warp softmax(+mask)(+dropout) → batched GEMM, headers
`softmax.h`, `strided_batched_gemm.h`). Those kernels materialize the full
(S, S) attention matrix per head and run fixed-max-seq warp softmax; the
TPU design is strictly stronger: **blockwise softmax with online
renormalization** (flash attention), so the score matrix never exists in
HBM, memory is O(S·D) instead of O(S²), and long sequences are natural —
which is exactly why it also becomes the per-shard compute of ring
sequence parallelism (apex_tpu.parallel.ring).

Layout: (B, S, H, D) inputs, kernel works on (B·H, S, D). Forward saves
(out, lse) residuals; backward recomputes probabilities blockwise (two
kernels: dq over q-blocks, dk/dv over k-blocks), the standard
recompute-over-store trade that wins on HBM bandwidth. The forward rule
names ``o`` and ``lse`` ``ops.KEPT_ATTN`` (``checkpoint_name``; the identity
outside a checkpoint): a ``jax.checkpoint`` or ``nn.remat`` round the op
whose policy is ``save_only_these_names(*ops.KEPT_NAMES)`` keeps them, and
its rerun of the forward holds no ``apex_attn_fwd``.

Additive bias (the reference's additive-mask variants), causal masking,
and softmax dropout all run inside the kernel. Dropout — fused in the
reference via in-kernel Philox (`dropout.h`) — uses a counter-based hash
RNG (see ``_keep_mask``): the mask is a pure function of the score
element's coordinates, so forward and backward regenerate it exactly and
no mask tensor ever exists in HBM.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._dispatch import KEPT_ATTN, pallas_call, use_interpret

LANES = 128
# Grid-step overhead on TPU dwarfs the per-tile MXU work at 128-blocks
# (a 128x128x64 tile is ~4 MFLOP ≈ 20 ns of MXU time). Sequences at or
# below the default clamp to a single block (so S=512 behaves exactly
# as the round-2 512-tile default, measured 13x faster backward than
# 128); longer sequences run 1024-tiles — the PERF.md sweep measured
# S=8192/d=64 fwd 27.3 → 51.6 TFLOP/s and fwd+bwd 1.35x vs 512-tiles
# (a 1024² fp32 score tile is 4 MiB, still VMEM-comfortable on the
# plain path). Long sequences stream blockwise — this only sets the
# tile, not the memory complexity.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
#: tile cap for bias/dropout-carrying kernels. ONE definition: the
#: dropout keep-mask hash is a function of block coordinates, so
#: _block_cap, the dense replica, AND ring_attention's shard-alignment
#: check + block-offset units must all agree on this number.
DROPOUT_TILE = 512


def _block_cap(block_q, block_k, has_bias, dropout_rate):
    """Tile cap for kernel paths that hold extra full-tile temporaries.

    A (bq, bk) fp32 bias block at 1024-tiles is 4 MiB (double-buffered:
    8), and dropout adds keep-mask/hash uint32 temporaries of the same
    footprint — either pushes the kernels past the 16 MiB scoped VMEM
    on long sequences, so both paths stay on the proven 512 tile.

    ONE definition, used by the forward wrapper, the backward wrapper
    AND the dense dropout-mask replica (`_bias_grad`): the counter-based
    mask is a function of block coordinates, so any divergence in the
    cap silently changes the dropout mask between kernels and the dense
    replica."""
    if has_bias or dropout_rate > 0.0:
        return min(block_q, DROPOUT_TILE), min(block_k, DROPOUT_TILE)
    return block_q, block_k


def _choose_block(pref, s, lane: bool = False):
    """Tile size for a sequence dim: clamp to the sequence, keep it
    8-sublane aligned (the lse output block `(bq, LANES)` tiles a
    `(B·H·nq·bq, LANES)` buffer, so bq must be a multiple of 8 whenever
    there is more than one block — interpret mode does not check this),
    and halve while padding waste exceeds half a tile (a 520-long
    sequence should pad to 640, not 1024).

    ``lane=True`` marks the key dimension, which lands in the *lane*
    position of the bias block: with more than one block Mosaic requires
    a multiple of 128 there, so halved/odd preferences (e.g. block_k=384
    over Sk=400 halving to 96) are rounded back up to 128-multiples; a
    single block covering the whole padded dim is always legal."""
    b = -(-min(pref, max(16, s)) // 8) * 8
    while b > 128 and (-(-s // b)) * b - s > b // 2:
        b //= 2
    if lane and -(-s // b) > 1 and b % LANES:
        b = -(-b // LANES) * LANES
    return b


def _g_pack(bh, nq, has_bias, dropout_rate, bq, bk, dp, itemsize=2):
    """Batch·head rows per grid step for the flash kernels.

    One-row steps leave the core waiting on per-step DMA setup (~2.3us
    measured vs ~0.7us of MXU work at S=512, D=64); packing g rows
    amortizes it. Only on the fast path: per-row bias blocks and the
    dropout hash's program_id coordinates assume one row per step, and
    the lse block layout needs a single q-block — so bias/dropout/nq>1
    keep g=1. Bounded by a ~9 MiB VMEM estimate (in-blocks double-
    buffered + f32 accumulators); ``itemsize`` is the q/k/v element
    size — the kernels keep inputs in their native dtype, so fp32
    inputs halve the attainable packing (ADVICE r3 item 1)."""
    if has_bias or dropout_rate > 0.0 or nq != 1:
        return 1
    for g in (4, 2):
        if bh % g:
            continue
        half_bufs = g * (bq + 2 * bk) * dp * 2 * itemsize
        scratch = g * bq * (2 * LANES + 2 * dp) * 4
        if half_bufs + scratch <= 9 * 2 ** 20:
            return g
    return 1


def _causal_mask(iq, ik, bq, bk, offset, window=None):
    """Bottom-right-aligned causal mask: query i attends keys
    0..i+(Sk-Sq), matching the oracle's tril(k=sk-sq) for cross lengths;
    under a ``window`` only the newest ``window`` of them, keys
    i+(Sk-Sq)-window+1..i+(Sk-Sq) (the band's lower edge)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
    if window is None:
        return rows + offset >= cols
    lag = rows + offset - cols
    return jnp.logical_and(lag >= 0, lag < window)


def _kv_valid(ik, bk, kv_len, bq):
    cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
    return cols < kv_len


def _tile_valid(iq, ik, bq, bk, kv_len, q_len, causal, off, *,
                need_rows, window=None):
    """Validity mask for one (bq, bk) score tile, or None when it is
    statically all-true. kv_len/q_len/bq/bk are Python ints, so each
    term elides independently at trace time: the kv-pad term exists iff
    ``kv_len % bk``, the q-row-pad term iff ``need_rows and q_len %
    bq``; only the causal frontier is inherently dynamic. ONE
    definition for all four native-layout kernels — each retained term
    costs a full-tile iota/compare/AND VPU sweep.

    The multi-block kernels call this only for a tile that
    :class:`_Frontier` lets run (one that the frontier crosses or that
    lies below it); a tile wholly above the frontier, where this mask
    would be all-false, is skipped before it. A tile wholly below the
    frontier still builds its all-true causal term here. Under a
    ``window`` the causal term is the band's, and a tile wholly left of
    the band is skipped too."""
    valid = None

    def land(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if kv_len % bk:
        valid = land(valid, _kv_valid(ik, bk, kv_len, bq))
    if causal:
        valid = land(valid, _causal_mask(iq, ik, bq, bk, off, window))
    if need_rows and q_len % bq:
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
        valid = land(valid, rows < q_len)
    return valid


class _Frontier(NamedTuple):
    """The causal frontier of an (nq, nk) grid of (bq, bk) tiles, in tile
    units: tile ``(iq, ik)`` holds an entry that :func:`_causal_mask`
    keeps iff its first column is at or left of its last row's frontier,
    ``ik·bk <= iq·bq + bq − 1 + off``. ``off`` is the Python int ``kv_len −
    q_len`` for a static frontier, or the traced ``causal_offset`` read
    from SMEM inside a kernel. Every other tile is masked whole: the
    kernels run no arithmetic for it (:func:`_when_tile_runs`) and, where
    ``off`` is static, the BlockSpec maps name no new block for it
    (``k_block`` / ``q_block``), so nothing is fetched either.

    Under a ``window`` (a static int: row ``r`` keeps the keys ``r + off −
    window + 1 … r + off``) the band has a lower edge too: a tile runs iff
    also its last column reaches its first row's oldest key, ``ik·bk + bk −
    1 >= iq·bq + off − window + 1``. The tiles a q row runs are then
    ``first_k … last_k`` and those a k column runs ``first_q … last_q``;
    the index maps clamp from both sides, so a skipped step before the band
    names the block its first running step fetches, one after it the block
    its last running step held."""
    bq: int
    bk: int
    nq: int
    nk: int
    off: object
    window: Optional[int] = None

    def runs(self, iq, ik):
        below = ik * self.bk <= iq * self.bq + (self.bq - 1) + self.off
        if self.window is None:
            return below
        return below & (ik * self.bk + (self.bk - 1)
                        >= iq * self.bq + self.off - self.window + 1)

    def last_k(self, iq):
        """The last k tile that q tile ``iq`` runs (below 0: none)."""
        return (iq * self.bq + (self.bq - 1) + self.off) // self.bk

    def first_k(self, iq):
        """The first k tile that q tile ``iq`` runs: 0 without a window."""
        if self.window is None:
            return 0
        return (iq * self.bq + self.off - self.window + 1) // self.bk

    def first_q(self, ik):
        """The first q tile that k tile ``ik`` runs (``nq`` or more:
        none)."""
        return (ik * self.bk - self.off) // self.bq

    def last_q(self, ik):
        """The last q tile that k tile ``ik`` runs: ``nq − 1`` without a
        window (below 0: none)."""
        if self.window is None:
            return self.nq - 1
        return (ik * self.bk + self.bk - 2 - self.off + self.window) // self.bq

    def k_block(self, iq, ik):
        """Index-map form, k innermost: the skipped steps trail a row, and
        name the block its last running step holds (no DMA); under a
        window they lead it too, and name the block its first running step
        will fetch."""
        last = jnp.maximum(self.last_k(iq), 0)
        if self.window is None:
            return jnp.minimum(ik, last)
        first = jnp.minimum(self.first_k(iq), self.nk - 1)
        return jnp.minimum(jnp.maximum(ik, first), last)

    def q_block(self, iq, ik):
        """Index-map form, q innermost: the skipped steps lead a column,
        and name the block its first running step will fetch; under a
        window they trail it too, and name the block its last running step
        held."""
        first = jnp.minimum(self.first_q(ik), self.nq - 1)
        if self.window is None:
            return jnp.maximum(iq, first)
        return jnp.minimum(jnp.maximum(iq, first),
                           jnp.maximum(self.last_q(ik), 0))

    def tiles_run(self):
        """Tiles of the grid that run, a head group (static ``off``)."""
        return sum(max(min(self.last_k(iq), self.nk - 1)
                       - max(self.first_k(iq), 0) + 1, 0)
                   for iq in range(self.nq))


def _frontier(causal, bq, bk, q_len, kv_len, off,
              window=None) -> Optional[_Frontier]:
    """The frontier the native multi-block kernels skip by, or None where
    nothing is skipped: no causal mask, or one tile in all (the forward's
    single-k form and the fused backward never ask). ONE definition for
    the kernels' predicate, the wrappers' index maps and the count of
    tiles run (tests/test_attention.py disables the skip by patching
    this to return None)."""
    nq, nk = -(-q_len // bq), -(-kv_len // bk)
    if not causal or nq * nk == 1:
        return None
    return _Frontier(bq, bk, nq, nk, off, window)


def _when_tile_runs(fr, iq, ik):
    """Decorator for a tile's arithmetic: under the one ``pl.when`` of the
    skip where there is a frontier, called as it is where there is
    none."""
    if fr is None:
        return lambda body: body()
    return pl.when(fr.runs(iq, ik))


def _kernel_frontier(causal, q_ref, k_ref, q_len, kv_len, off_ref,
                     window=None):
    """A kernel's frontier, from its q and k blocks; ``off_ref`` is the
    traced offset's SMEM ref (causal calls alone have one), or None for
    the static ``kv_len − q_len``."""
    off = kv_len - q_len if off_ref is None else off_ref[0]
    return _frontier(causal, q_ref.shape[1], k_ref.shape[1], q_len, kv_len,
                     off, window)


def _group_id_outside(fr, dropout_rate):
    """Grid axis 0's id for the dropout hash, read ahead of the skip's
    branch (a program id is not read inside one); None where there is no
    branch, and :func:`_group_id` reads it in place as it always has."""
    if fr is None or dropout_rate == 0.0:
        return None
    return pl.program_id(0)


def _group_id(t):
    return pl.program_id(0) if t is None else t


def _causal_tiles(bq, bk, q_len, kv_len, causal, window=None):
    """(tiles run, tiles in the grid) a head group, for a call without a
    traced ``causal_offset``: what ``_BwdPlan.tiles`` and the forward's
    grid are read by."""
    fr = _frontier(causal, bq, bk, q_len, kv_len, kv_len - q_len, window)
    grid = -(-q_len // bq) * -(-kv_len // bk)
    return (grid if fr is None else fr.tiles_run()), grid


def _keep_mask(seed, iq, ik, bq, bk, rate, gb=None):
    """In-kernel softmax-dropout keep mask — the TPU analogue of the
    reference's Philox dropout fused into the softmax kernel
    (`apex/contrib/csrc/multihead_attn/dropout.h:1-308`).

    Counter-based (lowbias32 avalanche over the score element's grid
    coordinates), so it is a pure function of (seed, batch·head, q-block,
    k-block, row, col): the forward and both backward kernels regenerate
    bitwise-identical masks regardless of grid iteration order, and
    compiled/interpret modes agree exactly (unlike ``pltpu.prng_*``,
    which has no interpret lowering). ``gb`` overrides the batch·head
    coordinate for kernels whose grid packs several heads per step (the
    native-layout path); default is one bh-row per step.
    """
    if gb is None:
        gb = pl.program_id(0)
    rows = jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
    return _mix_keep(seed, gb, iq, ik, rows, cols, rate)


def _dbo_shift(iq, ik, dbo_ref, has_dbo):
    """Apply the traced (q-block, k-block) dropout offsets — ONE
    definition for all four kernels: a drifted copy would silently
    change the mask in exactly one of fwd/bwd."""
    if not has_dbo:
        return iq, ik
    return iq + dbo_ref[0], ik + dbo_ref[1]


def _mix_keep(seed, gb, iq, ik, rows, cols, rate):
    """The shared coordinate hash: block seed + per-element lowbias32
    avalanche → keep bool. ONE definition used by both the kernels and
    the dense replica (`_keep_mask_dense`) — their bitwise agreement is
    what makes the bias-gradient dropout mask exact."""
    x = (seed.astype(jnp.uint32)
         + jnp.asarray(gb).astype(jnp.uint32) * np.uint32(0x9E3779B9)
         + jnp.asarray(iq).astype(jnp.uint32) * np.uint32(0x85EBCA6B)
         + jnp.asarray(ik).astype(jnp.uint32) * np.uint32(0xC2B2AE35)
         + rows * np.uint32(0x27D4EB2F) + cols * np.uint32(0x165667B1))
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # drop iff x < rate·2^32 ⇒ P(keep) = 1 - rate
    return x >= np.uint32(int(rate * 4294967296.0))


# --- forward ----------------------------------------------------------------

def _fwd_kernel(scale, causal, kv_len, q_len, has_bias, dropout_rate,
                g_pack, refs):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    pos = 3
    b_ref = None
    if has_bias:
        b_ref = refs[pos]
        pos += 1
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref = refs[pos]
        pos += 1
    o_ref, lse_ref, m_scr, l_scr, acc = refs[pos:]
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc[:] = jnp.zeros_like(acc)

    # ``g_pack`` batch·head rows per grid step (statically unrolled):
    # one-head steps at S=512 measured ~2.3us against ~0.7us of MXU
    # work — per-step DMA setup dominates; packing amortizes it. Only
    # used on the no-bias/no-dropout/single-q-block path (wrapper
    # gates), so bias/dropout below always see g_pack == 1.
    for h in range(g_pack):
        # dot operands stay in the INPUT dtype (bf16 multiplies + f32
        # MXU accumulate via preferred_element_type); softmax stays f32
        q, k, v = q_ref[h], k_ref[h], v_ref[h]
        bq, bk = q.shape[0], k.shape[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0].astype(jnp.float32)
        valid = _kv_valid(ik, bk, kv_len, bq)
        if causal:
            valid = jnp.logical_and(
                valid, _causal_mask(iq, ik, bq, bk, kv_len - q_len))
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[h][:, :1]
        l_prev = l_scr[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        # softmax dropout: the normalizer l uses the *undropped* sum
        # (dropout acts on the normalized probabilities, after the
        # softmax), so only the accumulator sees the mask
        pd = p
        if dropout_rate > 0.0:
            keep = _keep_mask(seed_ref[0], iq, ik, bq, bk, dropout_rate)
            pd = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc[h] = acc[h] * alpha + jax.lax.dot_general(
            pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

        @pl.when(ik == nk - 1)
        def _(h=h):
            l = l_scr[h][:, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[h] = (acc[h] / safe_l).astype(o_ref.dtype)
            # lse = m + log l; fully-masked rows get -inf-ish lse →
            # p=0 in bwd
            bq_ = o_ref.shape[1]
            lse_ref[h * bq_:(h + 1) * bq_] = \
                (m_scr[h][:, :1] + jnp.log(safe_l)) \
                + jnp.zeros((bq_, lse_ref.shape[1]), jnp.float32)


def _flash_fwd(q3, k3, v3, bias_g, bidx, scale, causal, block_q, block_k,
               dropout_rate=0.0, seed=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    dp = -(-d // LANES) * LANES
    block_q, block_k = _block_cap(block_q, block_k, bias_g is not None,
                                  dropout_rate)
    bq = _choose_block(block_q, sq)
    bk = _choose_block(block_k, sk, lane=True)
    sqp = -(-sq // bq) * bq
    skp = -(-sk // bk) * bk

    pad3 = lambda t, s_, d_: jnp.pad(
        t, ((0, 0), (0, s_ - t.shape[1]), (0, d_ - t.shape[2])))
    qp, kp, vp = pad3(q3, sqp, dp), pad3(k3, skp, dp), pad3(v3, skp, dp)
    nq, nk = sqp // bq, skp // bk

    has_bias = bias_g is not None
    g = _g_pack(bh, nq, has_bias, dropout_rate, bq, bk, dp,
                q3.dtype.itemsize)
    in_specs = [
        pl.BlockSpec((g, bq, dp), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((g, bk, dp), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((g, bk, dp), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [qp, kp, vp]
    if has_bias:
        bias_p = jnp.pad(bias_g, ((0, 0), (0, sqp - sq), (0, skp - sk)))
        in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b, i, j: (bidx(b), i, j),
            memory_space=pltpu.VMEM))
        args.append(bias_p)
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)

    kernel = functools.partial(_fwd_kernel, scale, causal, sk, sq,
                               has_bias, dropout_rate, g)
    o, lse = pallas_call(
        lambda *refs: kernel(refs),
        grid=(bh // g, nq, nk),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((g, bq, dp), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((g * bq, LANES), lambda b, i, j: (b * nq + i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, sqp, dp), q3.dtype),
            jax.ShapeDtypeStruct((bh * nq * bq, LANES), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((g, bq, LANES), jnp.float32),
            pltpu.VMEM((g, bq, LANES), jnp.float32),
            pltpu.VMEM((g, bq, dp), jnp.float32),
        ],
        name="apex_attn_fwd_packed",
    )(*args)
    lse = lse[:, 0].reshape(bh, sqp)[:, :sq]
    return o[:, :sq, :d], lse


# --- backward ---------------------------------------------------------------

def _bwd_dq_kernel(scale, causal, kv_len, q_len, has_bias, dropout_rate,
                   g_pack, refs):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    pos = 3
    b_ref = None
    if has_bias:
        b_ref = refs[pos]
        pos += 1
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref = refs[pos]
        pos += 1
    do_ref, lse_ref, dl_ref, dq_ref, dq_acc = refs[pos:]
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    for h in range(g_pack):
        # dots in input dtype + f32 accumulate (see _fwd_kernel note)
        q, k, v, do = q_ref[h], k_ref[h], v_ref[h], do_ref[h]
        bq, bk = q.shape[0], k.shape[0]
        lse = lse_ref[h * bq:(h + 1) * bq, :1]
        delta = dl_ref[h * bq:(h + 1) * bq, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0].astype(jnp.float32)
        valid = _kv_valid(ik, bk, kv_len, bq)
        if causal:
            valid = jnp.logical_and(
                valid, _causal_mask(iq, ik, bq, bk, kv_len - q_len))
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # gradient flows only through kept entries: dP = mask·dp̃/
            # keep. delta = rowsum(do·o) already equals Σ_j dp̃_j·P̃_j
            # (see _flash_bwd), so only dp needs the mask applied here
            keep = _keep_mask(seed_ref[0], iq, ik, bq, bk, dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_acc[h] = dq_acc[h] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        @pl.when(ik == nk - 1)
        def _(h=h):
            dq_ref[h] = dq_acc[h].astype(dq_ref.dtype)


def _bwd_dkv_kernel(scale, causal, kv_len, q_len, has_bias, dropout_rate,
                    g_pack, refs):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    pos = 3
    b_ref = None
    if has_bias:
        b_ref = refs[pos]
        pos += 1
    seed_ref = None
    if dropout_rate > 0.0:
        seed_ref = refs[pos]
        pos += 1
    do_ref, lse_ref, dl_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs[pos:]
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    for h in range(g_pack):
        # dots in input dtype + f32 accumulate (see _fwd_kernel note)
        q, k, v, do = q_ref[h], k_ref[h], v_ref[h], do_ref[h]
        bq, bk = q.shape[0], k.shape[0]
        lse = lse_ref[h * bq:(h + 1) * bq, :1]
        delta = dl_ref[h * bq:(h + 1) * bq, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[0].astype(jnp.float32)
        valid = _kv_valid(ik, bk, kv_len, bq)
        if causal:
            valid = jnp.logical_and(
                valid, _causal_mask(iq, ik, bq, bk, kv_len - q_len))
        # also mask padded query rows
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
        valid = jnp.logical_and(valid, rows < q_len)
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)

        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pv = p
        if dropout_rate > 0.0:
            # dv sees the dropped probabilities p̃ = mask·p/keep; dp gets
            # the same mask (gradient only through kept entries) —
            # identical mask to the forward because _keep_mask is
            # counter-based on (iq, ik)
            keep = _keep_mask(seed_ref[0], iq, ik, bq, bk, dropout_rate)
            inv_keep = 1.0 / (1.0 - dropout_rate)
            pv = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        dv_acc[h] = dv_acc[h] + jax.lax.dot_general(
            pv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[h] = dk_acc[h] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(iq == nq - 1)
    def _():
        for h in range(g_pack):
            dk_ref[h] = dk_acc[h].astype(dk_ref.dtype)
            dv_ref[h] = dv_acc[h].astype(dv_ref.dtype)


def _flash_bwd(q3, k3, v3, bias_g, bidx, o3, lse, do3, scale, causal,
               block_q, block_k, delta_shift=None, dropout_rate=0.0,
               seed=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    dp = -(-d // LANES) * LANES
    block_q, block_k = _block_cap(block_q, block_k, bias_g is not None,
                                  dropout_rate)
    bq = _choose_block(block_q, sq)
    bk = _choose_block(block_k, sk, lane=True)
    sqp = -(-sq // bq) * bq
    skp = -(-sk // bk) * bk
    nq, nk = sqp // bq, skp // bk

    pad3 = lambda t, s_, d_: jnp.pad(
        t, ((0, 0), (0, s_ - t.shape[1]), (0, d_ - t.shape[2])))
    qp, kp, vp = pad3(q3, sqp, dp), pad3(k3, skp, dp), pad3(v3, skp, dp)
    dop = pad3(do3, sqp, dp)

    # delta_i = rowsum(do * o) — flash backward's precomputed correction;
    # an lse cotangent shifts it (ds = p*(dp - delta) + p*dlse)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    if delta_shift is not None:
        delta = delta - delta_shift.astype(jnp.float32)
    # lay lse/delta out as (bh*nq*bq, LANES) lane-broadcast rows
    def lanes(x):
        xpad = jnp.pad(x, ((0, 0), (0, sqp - sq)))
        return jnp.broadcast_to(
            xpad.reshape(bh * sqp, 1), (bh * sqp, LANES))

    lse_l, delta_l = lanes(lse), lanes(delta)

    has_bias = bias_g is not None
    bias_p = None
    if has_bias:
        bias_p = jnp.pad(bias_g, ((0, 0), (0, sqp - sq), (0, skp - sk)))

    g = _g_pack(bh, nq, has_bias, dropout_rate, bq, bk, dp,
                q3.dtype.itemsize)
    q_spec_q = pl.BlockSpec((g, bq, dp), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    k_spec_q = pl.BlockSpec((g, bk, dp), lambda b, i, j: (b, j, 0),
                            memory_space=pltpu.VMEM)
    lane_spec_q = pl.BlockSpec((g * bq, LANES),
                               lambda b, i, j: (b * nq + i, 0),
                               memory_space=pltpu.VMEM)

    in_specs = [q_spec_q, k_spec_q, k_spec_q]
    args = [qp, kp, vp]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (1, bq, bk), lambda b, i, j: (bidx(b), i, j),
            memory_space=pltpu.VMEM))
        args.append(bias_p)
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    in_specs += [q_spec_q, lane_spec_q, lane_spec_q]
    args += [dop, lse_l, delta_l]

    dq = pallas_call(
        lambda *refs: functools.partial(
            _bwd_dq_kernel, scale, causal, sk, sq, has_bias,
            dropout_rate, g)(refs),
        grid=(bh // g, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec_q,
        out_shape=jax.ShapeDtypeStruct((bh, sqp, dp), q3.dtype),
        scratch_shapes=[pltpu.VMEM((g, bq, dp), jnp.float32)],
        name="apex_attn_bwd_dq_packed",
    )(*args)

    # dk/dv: grid loops q innermost
    q_spec_k = pl.BlockSpec((g, bq, dp), lambda b, j, i: (b, i, 0),
                            memory_space=pltpu.VMEM)
    k_spec_k = pl.BlockSpec((g, bk, dp), lambda b, j, i: (b, j, 0),
                            memory_space=pltpu.VMEM)
    lane_spec_k = pl.BlockSpec((g * bq, LANES),
                               lambda b, j, i: (b * nq + i, 0),
                               memory_space=pltpu.VMEM)
    in_specs2 = [q_spec_k, k_spec_k, k_spec_k]
    args2 = [qp, kp, vp]
    if has_bias:
        in_specs2.append(pl.BlockSpec(
            (1, bq, bk), lambda b, j, i: (bidx(b), i, j),
            memory_space=pltpu.VMEM))
        args2.append(bias_p)
    if dropout_rate > 0.0:
        in_specs2.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args2.append(seed)
    in_specs2 += [q_spec_k, lane_spec_k, lane_spec_k]
    args2 += [dop, lse_l, delta_l]

    dk, dv = pallas_call(
        lambda *refs: functools.partial(
            _bwd_dkv_kernel, scale, causal, sk, sq, has_bias,
            dropout_rate, g)(refs),
        grid=(bh // g, nk, nq),
        in_specs=in_specs2,
        out_specs=(k_spec_k, k_spec_k),
        out_shape=(jax.ShapeDtypeStruct((bh, skp, dp), k3.dtype),) * 2,
        scratch_shapes=[pltpu.VMEM((g, bk, dp), jnp.float32)] * 2,
        name="apex_attn_bwd_dkv_packed",
    )(*args2)

    return dq[:, :sq, :d], dk[:, :sk, :d], dv[:, :sk, :d]


# --- native-layout kernels ---------------------------------------------------
#
# The wrappers above take (B·H, S, D) operands, which costs a transpose
# copy per tensor at the custom-call boundary (measured 10.6 ms/step on
# the BERT bench — `{3,0,2,1}`-style relayouts XLA cannot fuse into a
# pallas call) plus a zero-pad of D up to the 128-lane tile. The
# native-layout path keeps the model's (B, S, H) activations AS the
# kernel operands: the grid still enumerates batch·head rows, but the
# BlockSpec index maps slice each head's D columns out of the lane axis
# (g heads per step so the block width g·D is lane-aligned — for the
# ubiquitous D=64 two heads share one 128-lane tile, removing the
# zero-pad too). Dropout stays bitwise-identical: the hash's batch·head
# coordinate is reconstructed as ``t·g + h``, exactly the bh-row the
# transposed path would have used.


def _native_g0(nh: int, d: int,
               d_v: Optional[int] = None) -> Optional[int]:
    """Smallest head-group g with g·d and g·d_v (v's head size, ``d`` when
    None) both lane-aligned; None = no native path (heads not groupable
    into a lane-aligned block)."""
    if d <= 0:
        return None
    g0 = int(np.lcm(*(128 // np.gcd(x, 128) for x in (d, d_v or d))))
    if nh % g0:
        return None
    return g0


def _native_g(nh, d, dropout_rate, bq, bk, itemsize, *, bias_isz=0,
              bias_per_head=False, carry_scratch=True, d_v=None):
    """Heads per grid step on the native path: at least g0 (lane
    alignment), more when the forward kernel's VMEM ledger fits the
    16 MiB scoped budget (in-blocks, scratch, score tile, out-blocks;
    packing amortizes per-step DMA setup). Dropout adds a (bq, bk)
    keep-mask/hash temporary; a bias adds its double-buffered
    (g|1, bq, bk) in-block. ``d_v`` (v's head size, ``d`` when None)
    sizes the v block, the accumulator and the o block: the backward's
    plan asks at its own v, the forward kernel takes v padded to ``d``.
    Always one of g0·{4, 2, 1} that divides ``nh``."""
    d_v = d if d_v is None else d_v
    g0 = _native_g0(nh, d, d_v)
    # full ledger of what the fwd kernel keeps in scoped VMEM: the
    # double-buffered q/k/v in-blocks, the m/l/acc scratch, the f32
    # score tile, the o and lse out-blocks (also double-buffered), and
    # dropout's keep-mask temporary. Calibrated against the measured
    # ceiling: S=2048 nh=16 OOM'd at g=4 (17.9 MiB actual) while
    # S=512 g=8 and fp32 S=1024 g=2 compile.
    mask_tmp = bq * bk * 8 if dropout_rate > 0.0 else 0
    for mult in (4, 2, 1):
        g = g0 * mult
        if nh % g:
            continue
        gd, gd_v = g * d, g * d_v
        half_bufs = ((bq + bk) * gd + bk * gd_v) * itemsize * 2
        # single-k (no carry): the m/l/acc scratch disappears, but the
        # fp32 PV result and its divided copy live as stack temps (the
        # acc role, twice) and up to three score-class tiles coexist
        # (s, p, and the masked/dropout product). Calibrated against a
        # measured 16.73 MiB OOM at fp32 S=512 g=8 (the ledger must
        # reject g=8 there — a single-temp estimate lands at exactly
        # the 16 MiB boundary and slips through; g=4 fits).
        scratch = (g * bq * 2 * LANES * 4 + bq * gd_v * 4
                   if carry_scratch else 2 * bq * gd_v * 4)
        score = bq * bk * 4 * (1 if carry_scratch else 3)
        outs = bq * gd_v * itemsize * 2 + g * bq * LANES * 4 * 2
        bias_buf = ((g if bias_per_head else 1) * bq * bk * bias_isz * 2
                    if bias_isz else 0)
        if (half_bufs + scratch + score + outs + mask_tmp + bias_buf
                <= 16 * 2 ** 20):
            return g
    return g0


def _unpack_common(refs, pos, has_bias, dropout_rate, has_dbo,
                   has_off):
    """ONE optional-operand order for every native kernel:
    bias, dropout seed, dropout block offsets, causal offset. The
    wrapper-side mirror is :func:`_append_common` — a new optional
    operand is added in exactly these two places."""
    b_ref = seed_ref = dbo_ref = off_ref = None
    if has_bias:
        b_ref = refs[pos]
        pos += 1
    if dropout_rate > 0.0:
        seed_ref = refs[pos]
        pos += 1
    if has_dbo:
        dbo_ref = refs[pos]
        pos += 1
    if has_off:
        off_ref = refs[pos]
        pos += 1
    return b_ref, seed_ref, dbo_ref, off_ref, pos


def _append_common(in_specs, args, *, bias_p, bias_mode, g, hg,
                   bias_dims, bias_idx, dropout_rate, seed, dbo,
                   causal_off):
    """Wrapper-side mirror of :func:`_unpack_common`: appends the
    optional operands' specs/args in the shared order. ``bias_idx``
    maps the dim-0 row function to this grid's index map."""
    if bias_p is not None:
        blk0, row = _bias_blk_nl(bias_mode, g, hg)
        in_specs.append(pl.BlockSpec((blk0,) + tuple(bias_dims),
                                     bias_idx(row),
                                     memory_space=pltpu.VMEM))
        args.append(bias_p)
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if dbo is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(dbo)
    if causal_off is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(causal_off)


def _fwd_kernel_nl(scale, causal, kv_len, q_len, dropout_rate, d, g,
                   has_off, has_bias, bias_per_head, has_dbo, refs,
                   window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    b_ref, seed_ref, dbo_ref, off_ref, pos = _unpack_common(
        refs, 3, has_bias, dropout_rate, has_dbo, has_off)
    # single k-block (kv fits one tile, the S<=1024 regime): the online
    # running-max carry is dead weight — the wrapper passes no scratch,
    # and there is no init, no alpha rescale, no carry broadcasts, no
    # separate epilogue division pass
    single_k = kv_len <= k_ref.shape[1]
    if single_k:
        o_ref, lse_ref = refs[pos:]
        m_scr = l_scr = acc = None
    else:
        o_ref, lse_ref, m_scr, l_scr, acc = refs[pos:]
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    if not single_k:
        @pl.when(ik == 0)
        def _():
            m_scr[:] = jnp.full_like(m_scr, NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc[:] = jnp.zeros_like(acc)

    fr = None if single_k else _kernel_frontier(
        causal, q_ref, k_ref, q_len, kv_len, off_ref, window)
    t = _group_id_outside(fr, dropout_rate)

    def tile(h, sl):
        q, k, v = q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl]
        bq, bk = q.shape[0], k.shape[0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[h if bias_per_head else 0].astype(jnp.float32)
        off = ((off_ref[0] if has_off else kv_len - q_len)
               if causal else None)
        valid = _tile_valid(iq, ik, bq, bk, kv_len, q_len, causal, off,
                            need_rows=False, window=window)
        masked = valid is not None
        if masked:
            s = jnp.where(valid, s, NEG_INF)

        if single_k:
            m_new = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m_new)
            if masked:
                # fully-masked rows: m == NEG_INF ⇒ p rows of exp(0)=1
                # garbage; zero them so l lands at 0 (ring contract)
                p = jnp.where(valid, p, 0.0)
            l = jnp.sum(p, axis=1, keepdims=True)
            pd = p
            if dropout_rate > 0.0:
                iqo, iko = _dbo_shift(iq, ik, dbo_ref, has_dbo)
                keep = _keep_mask(seed_ref[0], iqo, iko, bq, bk,
                                  dropout_rate,
                                  gb=pl.program_id(0) * g + h)
                pd = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)),
                               0.0)
            safe_l = jnp.where(l == 0.0, 1.0, l) if masked else l
            o_ref[0, :, sl] = (jax.lax.dot_general(
                pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) / safe_l).astype(
                    o_ref.dtype)
            lse_ref[h * bq:(h + 1) * bq] = \
                (m_new + jnp.log(safe_l)) \
                + jnp.zeros((bq, lse_ref.shape[1]), jnp.float32)
            return

        m_prev = m_scr[h][:, :1]
        l_prev = l_scr[h][:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        pd = p
        if dropout_rate > 0.0:
            iqo, iko = _dbo_shift(iq, ik, dbo_ref, has_dbo)
            keep = _keep_mask(seed_ref[0], iqo, iko, bq, bk, dropout_rate,
                              gb=_group_id(t) * g + h)
            pd = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc[0, :, sl] = acc[0][:, sl] * alpha + jax.lax.dot_general(
            pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    for h in range(g):
        sl = slice(h * d, (h + 1) * d)
        # a row's skipped tiles trail it (and under a window lead it too):
        # the init above and the write-out below stay outside the skip
        _when_tile_runs(fr, iq, ik)(functools.partial(tile, h, sl))
        if single_k:
            continue

        @pl.when(ik == nk - 1)
        def _(h=h, sl=sl):
            l = l_scr[h][:, :1]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            bq_ = o_ref.shape[1]
            o_ref[0, :, sl] = (acc[0][:, sl] / safe_l).astype(o_ref.dtype)
            lse_ref[h * bq_:(h + 1) * bq_] = \
                (m_scr[h][:, :1] + jnp.log(safe_l)) \
                + jnp.zeros((bq_, lse_ref.shape[1]), jnp.float32)


def _head_specs(nh, g, bq, bk, gd, gd_v, fr=None):
    """(q, k, v, o, bias index map) over (B, S, H) with head columns in
    the lane axis, v and o (and dO) ``gd_v`` wide; grid dim 0 enumerates
    (batch, head-group) pairs group-minor, k innermost. Under a static
    frontier ``fr`` a skipped step's k / v / bias block is the one its
    row's last running step holds: no fetch."""
    hg = nh // g
    kb = (lambda i, j: j) if fr is None else fr.k_block
    q_map = lambda t, i, j: (t // hg, i, t % hg)
    k_map = lambda t, i, j: (t // hg, kb(i, j), t % hg)
    spec = lambda rows, w, index: pl.BlockSpec((1, rows, w), index,
                                               memory_space=pltpu.VMEM)
    bias_idx = lambda row: lambda t, i, j: (row(t), i, kb(i, j))
    return (spec(bq, gd, q_map), spec(bk, gd, k_map), spec(bk, gd_v, k_map),
            spec(bq, gd_v, q_map), bias_idx)


def _lse_reorder(lse_rows, bh, g, nq, bq):
    """Kernel lse row order [group][q-block][head][row] → (bh, sqp).
    With nq == 1 or g == 1 the orders already coincide."""
    x = lse_rows.reshape(bh // g, nq, g, bq)
    if g > 1 and nq > 1:
        x = x.transpose(0, 2, 1, 3)
    return x.reshape(bh, nq * bq)


def _lanes_nl(x, bh, g, nq, bq, sq):
    """(bh, sq) per-row scalars → (bh·sqp, LANES) in the kernels' block
    row order [group][q-block][head][row]."""
    sqp = nq * bq
    xp = jnp.pad(x, ((0, 0), (0, sqp - sq)))
    xp = xp.reshape(bh // g, g, nq, bq)
    if g > 1 and nq > 1:
        xp = xp.transpose(0, 2, 1, 3)
    xp = xp.reshape(bh * sqp, 1)
    return jnp.broadcast_to(xp, (bh * sqp, LANES))


def _bias_group_nl(bias, b, nh, sq, sk):
    """(B|1, H|1, Sq|1, Sk|1) bias → ((G, Sq, Sk), mode) for the native
    grid whose dim 0 enumerates (batch, head-group) pairs group-minor.
    mode picks the dim-0 block shape and index map: 'shared' (G=1) and
    'batch' (G=B) ride (1, bq, bk) blocks; 'head' (G=H) and 'full'
    (G=B·H) need the step's g per-head slabs, (g, bq, bk) blocks.
    The reference's additive-mask MHA variants
    (`setup.py:295-320`, `self_multihead_attn_bias_additive_mask_cuda.cu`)
    are the per-batch/per-head cases."""
    if bias is None:
        return None, None
    bias_g, bb, bh_ = _bias_flat(bias, b, nh, sq, sk)
    mode = {(True, True): "shared", (False, True): "batch",
            (True, False): "head", (False, False): "full"}[
                (bb == 1, bh_ == 1)]
    return bias_g, mode


def _bias_blk_nl(mode, g, hg):
    """(dim-0 block size, grid-step → dim-0 block index) for a native
    bias spec; block index is in units of the block size."""
    return {
        "shared": (1, lambda t: 0),
        "batch": (1, lambda t: t // hg),
        "head": (g, lambda t: t % hg),
        "full": (g, lambda t: t),
    }[mode]


def _pad_bias_nl(bias_g, sqp, skp):
    G, sq, sk = bias_g.shape
    if sq == sqp and sk == skp:
        return bias_g
    return jnp.pad(bias_g, ((0, 0), (0, sqp - sq), (0, skp - sk)))


def _flash_fwd_nl(q2, k2, v2, nh, d, scale, causal, block_q, block_k,
                  dropout_rate=0.0, seed=None, causal_off=None,
                  bias_g=None, bias_mode=None, dbo=None, window=None):
    b, sq, H = q2.shape
    sk = k2.shape[1]
    bh = b * nh
    block_q, block_k = _block_cap(block_q, block_k, bias_g is not None,
                                  dropout_rate)
    bq = _choose_block(block_q, sq)
    bk = _choose_block(block_k, sk, lane=True)
    sqp = -(-sq // bq) * bq
    skp = -(-sk // bk) * bk
    nq, nk = sqp // bq, skp // bk

    pad_s = lambda t, s_: t if t.shape[1] == s_ else jnp.pad(
        t, ((0, 0), (0, s_ - t.shape[1]), (0, 0)))
    qp, kp, vp = pad_s(q2, sqp), pad_s(k2, skp), pad_s(v2, skp)

    bias_per_head = bias_mode in ("head", "full")
    g = _native_g(nh, d, dropout_rate, bq, bk, q2.dtype.itemsize,
                  bias_isz=(bias_g.dtype.itemsize if bias_g is not None
                            else 0),
                  bias_per_head=bias_per_head, carry_scratch=nk > 1)
    gd = g * d
    hg = nh // g
    # the single-k kernel has no k loop to skip in; a traced offset skips
    # the arithmetic alone (the maps cannot read it)
    fr = (None if nk == 1 or causal_off is not None
          else _frontier(causal, bq, bk, sq, sk, sk - sq, window))
    q_spec, k_spec, _, _, bias_idx = _head_specs(nh, g, bq, bk, gd, gd, fr)
    in_specs = [q_spec, k_spec, k_spec]
    args = [qp, kp, vp]
    _append_common(
        in_specs, args,
        bias_p=(None if bias_g is None
                else _pad_bias_nl(bias_g, sqp, skp)),
        bias_mode=bias_mode, g=g, hg=hg, bias_dims=(bq, bk),
        bias_idx=bias_idx,
        dropout_rate=dropout_rate, seed=seed, dbo=dbo,
        causal_off=causal_off)

    kernel = functools.partial(_fwd_kernel_nl, scale, causal, sk, sq,
                               dropout_rate, d, g,
                               causal_off is not None,
                               bias_g is not None, bias_per_head,
                               dbo is not None, window=window)
    o, lse = pallas_call(
        lambda *refs: kernel(refs),
        grid=(bh // g, nq, nk),
        in_specs=in_specs,
        out_specs=(
            q_spec,
            pl.BlockSpec((g * bq, LANES), lambda t, i, j: (t * nq + i, 0),
                         memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, sqp, H), q2.dtype),
            jax.ShapeDtypeStruct((bh * nq * bq, LANES), jnp.float32),
        ),
        scratch_shapes=([] if nk == 1 else [
            pltpu.VMEM((g, bq, LANES), jnp.float32),
            pltpu.VMEM((g, bq, LANES), jnp.float32),
            pltpu.VMEM((1, bq, gd), jnp.float32),
        ]),
        name="apex_attn_fwd",
    )(*args)
    lse = _lse_reorder(lse[:, 0], bh, g, nq, bq)[:, :sq]
    return o[:, :sq, :], lse


def _bwd_dq_kernel_nl(scale, causal, kv_len, q_len, dropout_rate, d, d_v,
                      g, has_off, has_bias, bias_per_head, has_dbo, refs,
                      window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    b_ref, seed_ref, dbo_ref, off_ref, pos = _unpack_common(
        refs, 3, has_bias, dropout_rate, has_dbo, has_off)
    do_ref, lse_ref, dl_ref, dq_ref, dq_acc = refs[pos:]
    iq, ik = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    fr = _kernel_frontier(causal, q_ref, k_ref, q_len, kv_len, off_ref,
                          window)
    t = _group_id_outside(fr, dropout_rate)

    def tile(h, sl, slv):
        q, k, v = q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, slv]
        do = do_ref[0][:, slv]
        bq, bk = q.shape[0], k.shape[0]
        lse = lse_ref[h * bq:(h + 1) * bq, :1]
        delta = dl_ref[h * bq:(h + 1) * bq, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[h if bias_per_head else 0].astype(jnp.float32)
        p = jnp.exp(s - lse)
        off = ((off_ref[0] if has_off else kv_len - q_len)
               if causal else None)
        valid = _tile_valid(iq, ik, bq, bk, kv_len, q_len, causal, off,
                            need_rows=False, window=window)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            iqo, iko = _dbo_shift(iq, ik, dbo_ref, has_dbo)
            keep = _keep_mask(seed_ref[0], iqo, iko, bq, bk, dropout_rate,
                              gb=_group_id(t) * g + h)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_acc[0, :, sl] = dq_acc[0][:, sl] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    for h in range(g):
        sl, slv = slice(h * d, (h + 1) * d), slice(h * d_v, (h + 1) * d_v)
        # as the forward: the skipped tiles trail a row
        _when_tile_runs(fr, iq, ik)(functools.partial(tile, h, sl, slv))

        @pl.when(ik == nk - 1)
        def _(sl=sl):
            dq_ref[0, :, sl] = dq_acc[0][:, sl].astype(dq_ref.dtype)


def _bwd_dkv_kernel_nl(scale, causal, kv_len, q_len, dropout_rate, d, d_v,
                       g, has_off, has_bias, bias_per_head, has_dbo, refs,
                       window=None):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    b_ref, seed_ref, dbo_ref, off_ref, pos = _unpack_common(
        refs, 3, has_bias, dropout_rate, has_dbo, has_off)
    do_ref, lse_ref, dl_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs[pos:]
    ik, iq = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    fr = _kernel_frontier(causal, q_ref, k_ref, q_len, kv_len, off_ref,
                          window)
    t = _group_id_outside(fr, dropout_rate)

    def tile(h, sl, slv):
        q, k, v = q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, slv]
        do = do_ref[0][:, slv]
        bq, bk = q.shape[0], k.shape[0]
        lse = lse_ref[h * bq:(h + 1) * bq, :1]
        delta = dl_ref[h * bq:(h + 1) * bq, :1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[h if bias_per_head else 0].astype(jnp.float32)
        p = jnp.exp(s - lse)
        off = ((off_ref[0] if has_off else kv_len - q_len)
               if causal else None)
        valid = _tile_valid(iq, ik, bq, bk, kv_len, q_len, causal, off,
                            need_rows=True, window=window)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)

        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pv = p
        if dropout_rate > 0.0:
            iqo, iko = _dbo_shift(iq, ik, dbo_ref, has_dbo)
            keep = _keep_mask(seed_ref[0], iqo, iko, bq, bk, dropout_rate,
                              gb=_group_id(t) * g + h)
            inv_keep = 1.0 / (1.0 - dropout_rate)
            pv = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        dv_acc[0, :, slv] = dv_acc[0][:, slv] + jax.lax.dot_general(
            pv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[0, :, sl] = dk_acc[0][:, sl] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    # a column's skipped tiles lead it (and under a window trail it too):
    # the init above and the write-out below stay outside the skip
    @_when_tile_runs(fr, iq, ik)
    def _():
        for h in range(g):
            tile(h, slice(h * d, (h + 1) * d),
                 slice(h * d_v, (h + 1) * d_v))

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel_nl(scale, causal, kv_len, q_len, dropout_rate, d,
                         g, has_off, self_delta, has_bias,
                         bias_per_head, has_dbo, refs, window=None):
    """Single-sweep backward for single-block grids (Sq, Sk each one
    tile): s and p are computed ONCE per head and all three gradients
    come out of the same sweep — the two-kernel split pays a redundant
    QKᵀ and exp pass per kernel, which at short sequence lengths is the
    dominant backward cost (BERT-Large: ~0.8 ms/layer two-kernel vs the
    fused sweep).

    ``self_delta``: with the full row in the tile, the kernel needs NO
    lse/delta operands at all — the softmax normalizer is recomputed
    from ``s`` (same max/sum the forward took) and
    ``delta = Σⱼ dp̃ⱼ·pⱼ ≡ Σⱼ doⱼ·oⱼ`` falls out of the dp tile the
    sweep already holds (with dropout: the masked dp̃, since
    o = (keep⊙p/(1−r))@v makes both sums run over the same terms).
    The lane-broadcast (g·bq, 128) f32 operand layout those inputs
    needed was a 128× HBM inflation — 2×64 MB per BERT-Large layer for
    512 KB of data, ~40% of the backward kernel's input bytes plus a
    materialized broadcast per layer (round-5 profile). Only the
    lse-cotangent path (`_fal_bwd`, the ring merge) still feeds an
    externally shifted delta."""
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    b_ref, seed_ref, dbo_ref, off_ref, pos = _unpack_common(
        refs, 3, has_bias, dropout_rate, has_dbo, has_off)
    if self_delta:
        do_ref, dq_ref, dk_ref, dv_ref = refs[pos:]
        lse_ref = dl_ref = None
    else:
        do_ref, lse_ref, dl_ref, dq_ref, dk_ref, dv_ref = refs[pos:]

    for h in range(g):
        sl = slice(h * d, (h + 1) * d)
        q, k, v = q_ref[0][:, sl], k_ref[0][:, sl], v_ref[0][:, sl]
        do = do_ref[0][:, sl]
        bq, bk = q.shape[0], k.shape[0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if has_bias:
            s = s + b_ref[h if bias_per_head else 0].astype(jnp.float32)
        off = ((off_ref[0] if has_off else kv_len - q_len)
               if causal else None)
        valid = _tile_valid(0, 0, bq, bk, kv_len, q_len, causal, off,
                            need_rows=True, window=window)
        masked = valid is not None
        if self_delta:
            if masked:
                m = jnp.max(jnp.where(valid, s, NEG_INF), axis=1,
                            keepdims=True)
                e = jnp.where(valid, jnp.exp(s - m), 0.0)
                l = jnp.sum(e, axis=1, keepdims=True)
                # fully-masked rows (ring causal hops): l == 0 ⇒ p ≡ 0
                p = e * jnp.where(l > 0.0, 1.0 / l, 0.0)
            else:
                m = jnp.max(s, axis=1, keepdims=True)
                e = jnp.exp(s - m)
                l = jnp.sum(e, axis=1, keepdims=True)
                p = e * (1.0 / l)
        else:
            lse = lse_ref[h * bq:(h + 1) * bq, :1]
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(valid, p, 0.0)

        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        pv = p
        if dropout_rate > 0.0:
            iqo, iko = _dbo_shift(0, 0, dbo_ref, has_dbo)
            keep = _keep_mask(seed_ref[0], iqo, iko, bq, bk, dropout_rate,
                              gb=pl.program_id(0) * g + h)
            inv_keep = 1.0 / (1.0 - dropout_rate)
            pv = jnp.where(keep, p * inv_keep, 0.0)
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        if self_delta:
            delta = jnp.sum(dp * p, axis=1, keepdims=True)
        else:
            delta = dl_ref[h * bq:(h + 1) * bq, :1]
        ds = (p * (dp - delta)).astype(q.dtype)
        dq_ref[0, :, sl] = (jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale).astype(
                dq_ref.dtype)
        dk_ref[0, :, sl] = (jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale).astype(
                dk_ref.dtype)
        dv_ref[0, :, sl] = jax.lax.dot_general(
            pv.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dv_ref.dtype)


def _flash_bwd_fused_nl(qp, kp, vp, dop, lse_l, delta_l, nh, d, g,
                        scale, causal, sq, sk, sqp, skp, bq, bk, seed,
                        dropout_rate, causal_off=None, bias_p=None,
                        bias_mode=None, dbo=None, window=None):
    """``lse_l``/``delta_l`` None ⇒ the kernel self-computes the
    normalizer and delta (the single-block identity, no lane operands)."""
    self_delta = lse_l is None
    b = qp.shape[0]
    H = qp.shape[2]
    bh = b * nh
    gd = g * d
    hg = nh // g
    bias_per_head = bias_mode in ("head", "full")
    q_spec = pl.BlockSpec((1, sqp, gd), lambda t: (t // hg, 0, t % hg),
                          memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((1, skp, gd), lambda t: (t // hg, 0, t % hg),
                          memory_space=pltpu.VMEM)
    in_specs = [q_spec, k_spec, k_spec]
    args = [qp, kp, vp]
    _append_common(
        in_specs, args, bias_p=bias_p, bias_mode=bias_mode, g=g, hg=hg,
        bias_dims=(sqp, skp),
        bias_idx=lambda row: lambda t: (row(t), 0, 0),
        dropout_rate=dropout_rate, seed=seed, dbo=dbo,
        causal_off=causal_off)
    if self_delta:
        in_specs += [q_spec]
        args += [dop]
    else:
        lane_spec = pl.BlockSpec((g * bq, LANES), lambda t: (t, 0),
                                 memory_space=pltpu.VMEM)
        in_specs += [q_spec, lane_spec, lane_spec]
        args += [dop, lse_l, delta_l]

    dq, dk, dv = pallas_call(
        lambda *refs: functools.partial(
            _bwd_fused_kernel_nl, scale, causal, sk, sq, dropout_rate,
            d, g, causal_off is not None, self_delta,
            bias_p is not None, bias_per_head, dbo is not None,
            window=window)(refs),
        grid=(bh // g,),
        in_specs=in_specs,
        out_specs=(q_spec, k_spec, k_spec),
        out_shape=(
            jax.ShapeDtypeStruct((b, sqp, H), qp.dtype),
            jax.ShapeDtypeStruct((b, skp, H), kp.dtype),
            jax.ShapeDtypeStruct((b, skp, H), kp.dtype),
        ),
        name="apex_attn_bwd",
    )(*args)
    return dq[:, :sq, :], dk[:, :sk, :], dv[:, :sk, :]


class _BwdPlan(NamedTuple):
    """What the native backward runs for one static shape."""
    bq: int
    bk: int
    g: int                      # heads per grid step
    vmem_limit: Optional[int]   # None: Mosaic's 16 MiB default
    form: str                   # "fused" | "two_kernel" | "two_kernel_raised"

    def tiles(self, sq, sk, causal, window=None):
        """(tiles_run, tiles_grid) a head group, in each of the backward's
        kernels (the forward's come from :func:`_causal_tiles` at its own
        tiles): under a static causal frontier the tiles above it run
        nothing, and under a window neither do those left of the band."""
        return _causal_tiles(self.bq, self.bk, sq, sk, causal, window)


def _bwd_plan(nh, d, sq, sk, bh, itemsize, block_q, block_k, *,
              dropout_rate=0.0, bias_isz=0, bias_per_head=False,
              delta_shifted=False, d_v=None) -> _BwdPlan:
    """Tiles, head group, VMEM limit and kernel form of the native
    backward, from static values alone (tests/test_attention.py
    ``test_backward_plan`` states one shape on each side of each
    decision). ``bias_isz`` is the bias element size, 0 without a bias;
    ``d_v`` v's head size (``d`` when None), which v, dO, dv and the dv
    accumulator have a head.

    Every head group here is one of g0·{4, 2, 1} dividing ``nh``
    (:func:`_native_g`), so halving one that is above g0 lands on a
    lane-aligned divisor of ``nh`` again: no step below re-checks it."""
    block_q, block_k = _block_cap(block_q, block_k, bias_isz > 0,
                                  dropout_rate)
    bq = _choose_block(block_q, sq)
    bk = _choose_block(block_k, sk, lane=True)
    d_v = d if d_v is None else d_v
    g0 = _native_g0(nh, d, d_v)

    def heads(bq_, bk_):
        return _native_g(nh, d, dropout_rate, bq_, bk_, itemsize,
                         bias_isz=bias_isz, bias_per_head=bias_per_head,
                         d_v=d_v)

    def bias_buf(g_, bufs):
        return ((g_ if bias_per_head else 1) * bq * bk * bias_isz * bufs
                if bias_isz else 0)

    g = heads(bq, bk)
    vmem_limit = None
    if (sq > bq or sk > bk) and bq * bk * 4 >= (1 << 22) and bh > g:
        # multi-block two-kernel path with 1024²-class f32 score tiles:
        # Mosaic multi-buffers the streamed blocks across head-group
        # boundaries when more groups follow (measured: the identical
        # kernel compiles at bh == g and OOMs at 19.6 MiB with 64
        # groups). The 16 MiB scoped-VMEM ceiling is a compiler
        # default, not the hardware's (v5e carries 128 MiB): raise the
        # limit for these two kernels instead of shrinking the tile —
        # the 1024-tile bwd measured 27% faster with serialized grads,
        # and the raised path falls back to the 512 cap whenever its
        # own bwd ledger — in/out blocks with cross-group
        # triple-buffering, both lane arrays, accumulators, and the
        # live f32 score temporaries — would exceed the raised limit.
        gd, gd_v = g * d, g * d_v
        bwd_est = ((bq + bk) * (gd + gd_v) * itemsize * 3
                   + 2 * g * bq * LANES * 4 * 3
                   + bk * (gd + gd_v) * itemsize * 2 + bk * (gd + gd_v) * 4
                   + 3 * bq * bk * 4
                   + bias_buf(g, 3))
        if bwd_est > 32 * 2 ** 20:
            bq = _choose_block(min(block_q, 512), sq)
            bk = _choose_block(min(block_k, 512), sk, lane=True)
            g = min(heads(bq, bk), 2 * g0)
        else:
            vmem_limit = 32 * 2 ** 20  # est 24.1 MiB at the 1024² point
    if sq <= bq and sk <= bk:
        # single-block grids: one fused sweep computes dq/dk/dv from a
        # single s/p evaluation. Its VMEM budget carries all seven
        # blocks (here the padded lengths are bq and bk) + f32 score
        # temporaries — shrink g until it fits, and fall back to the
        # two-kernel split when even the minimum lane-aligned group
        # does not (large-S fp32 shapes).

        def fused_est(g_):
            gd, gd_v = g_ * d, g_ * d_v
            lanes = (2 * g_ * bq * LANES * 4 * 2 if delta_shifted
                     else bq * bk * 4)   # self-delta: one extra f32 tile
            return ((bq + bk) * (gd + gd_v) * itemsize * 2
                    + ((bq + bk) * gd + bk * gd_v) * itemsize * 2
                    + bq * bk * 4 * 3 + lanes + bias_buf(g_, 2))

        gf = g
        while gf > g0 and fused_est(gf) > 13 * 2 ** 20:
            gf //= 2
        if fused_est(gf) <= 13 * 2 ** 20:
            return _BwdPlan(bq, bk, gf, None, "fused")
    return _BwdPlan(bq, bk, g, vmem_limit,
                    "two_kernel" if vmem_limit is None
                    else "two_kernel_raised")


def _flash_bwd_nl(q2, k2, v2, nh, d, lse, delta, do2, scale, causal,
                  block_q, block_k, dropout_rate=0.0, seed=None,
                  causal_off=None, delta_shifted=False, bias_g=None,
                  bias_mode=None, dbo=None, window=None):
    """Native-layout backward: operands/outputs (B, S, H); ``lse`` and
    ``delta`` arrive (B·H, Sq).

    ``delta_shifted``: the caller folded an lse cotangent into delta
    (`_fal_bwd`), so the single-block fused kernel may NOT self-compute
    it and must take the lane operands. In the default unshifted case
    the fused path drops lse/delta entirely (their producing graphs are
    dead-code-eliminated by XLA)."""
    b, sq, H = q2.shape
    sk = k2.shape[1]
    bh = b * nh
    d_v = v2.shape[2] // nh
    bias_per_head = bias_mode in ("head", "full")
    plan = _bwd_plan(
        nh, d, sq, sk, bh, q2.dtype.itemsize, block_q, block_k,
        dropout_rate=dropout_rate,
        bias_isz=bias_g.dtype.itemsize if bias_g is not None else 0,
        bias_per_head=bias_per_head, delta_shifted=delta_shifted, d_v=d_v)
    bq, bk, g = plan.bq, plan.bk, plan.g
    sqp = -(-sq // bq) * bq
    skp = -(-sk // bk) * bk
    nq, nk = sqp // bq, skp // bk

    pad_s = lambda t, s_: t if t.shape[1] == s_ else jnp.pad(
        t, ((0, 0), (0, s_ - t.shape[1]), (0, 0)))
    qp, kp, vp = pad_s(q2, sqp), pad_s(k2, skp), pad_s(v2, skp)
    dop = pad_s(do2, sqp)

    if plan.form == "fused":
        if delta_shifted:
            lse_f = _lanes_nl(lse, bh, g, 1, bq, sq)
            delta_f = _lanes_nl(delta, bh, g, 1, bq, sq)
        else:
            lse_f = delta_f = None
        bias_p = (None if bias_g is None
                  else _pad_bias_nl(bias_g, sqp, skp))
        return _flash_bwd_fused_nl(qp, kp, vp, dop, lse_f, delta_f,
                                   nh, d, g, scale, causal, sq, sk,
                                   sqp, skp, bq, bk, seed,
                                   dropout_rate, causal_off,
                                   bias_p=bias_p,
                                   bias_mode=bias_mode, dbo=dbo,
                                   window=window)

    gd, gd_v = g * d, g * d_v
    lse_l = _lanes_nl(lse, bh, g, nq, bq, sq)
    delta_l = _lanes_nl(delta, bh, g, nq, bq, sq)

    hg = nh // g
    fr = (None if causal_off is not None
          else _frontier(causal, bq, bk, sq, sk, sk - sq, window))
    q_spec, k_spec, v_spec, o_spec, bias_idx = _head_specs(
        nh, g, bq, bk, gd, gd_v, fr)
    lane_spec = pl.BlockSpec((g * bq, LANES),
                             lambda t, i, j: (t * nq + i, 0),
                             memory_space=pltpu.VMEM)

    bias_p = None if bias_g is None else _pad_bias_nl(bias_g, sqp, skp)
    in_specs = [q_spec, k_spec, v_spec]
    args = [qp, kp, vp]
    _append_common(
        in_specs, args, bias_p=bias_p, bias_mode=bias_mode, g=g, hg=hg,
        bias_dims=(bq, bk), bias_idx=bias_idx,
        dropout_rate=dropout_rate, seed=seed, dbo=dbo,
        causal_off=causal_off)
    in_specs += [o_spec, lane_spec, lane_spec]
    args += [dop, lse_l, delta_l]

    extra = {}
    if plan.vmem_limit is not None and not use_interpret():
        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=plan.vmem_limit)
    dq = pallas_call(
        lambda *refs: functools.partial(
            _bwd_dq_kernel_nl, scale, causal, sk, sq, dropout_rate, d,
            d_v, g, causal_off is not None, bias_p is not None,
            bias_per_head, dbo is not None, window=window)(refs),
        grid=(bh // g, nq, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, sqp, H), q2.dtype),
        scratch_shapes=[pltpu.VMEM((1, bq, gd), jnp.float32)],
        name="apex_attn_bwd_dq",
        **extra,
    )(*args)

    # dk/dv: grid loops q innermost; a skipped step's q / do / lse /
    # delta / bias block is the one its column's first running step takes
    qb = (lambda i, j: i) if fr is None else fr.q_block
    q_map_k = lambda t, j, i: (t // hg, qb(i, j), t % hg)
    k_map_k = lambda t, j, i: (t // hg, j, t % hg)
    spec_k = lambda rows, w, index: pl.BlockSpec((1, rows, w), index,
                                                 memory_space=pltpu.VMEM)
    q_spec_k = spec_k(bq, gd, q_map_k)
    k_spec_k = spec_k(bk, gd, k_map_k)
    v_spec_k = spec_k(bk, gd_v, k_map_k)
    do_spec_k = spec_k(bq, gd_v, q_map_k)
    lane_spec_k = pl.BlockSpec((g * bq, LANES),
                               lambda t, j, i: (t * nq + qb(i, j), 0),
                               memory_space=pltpu.VMEM)
    in_specs2 = [q_spec_k, k_spec_k, v_spec_k]
    args2 = [qp, kp, vp]
    _append_common(
        in_specs2, args2, bias_p=bias_p, bias_mode=bias_mode, g=g,
        hg=hg, bias_dims=(bq, bk),
        bias_idx=lambda row: lambda t, j, i: (row(t), qb(i, j), j),
        dropout_rate=dropout_rate, seed=seed, dbo=dbo,
        causal_off=causal_off)
    in_specs2 += [do_spec_k, lane_spec_k, lane_spec_k]
    args2 += [dop, lse_l, delta_l]

    dk, dv = pallas_call(
        lambda *refs: functools.partial(
            _bwd_dkv_kernel_nl, scale, causal, sk, sq, dropout_rate, d,
            d_v, g, causal_off is not None, bias_p is not None,
            bias_per_head, dbo is not None, window=window)(refs),
        grid=(bh // g, nk, nq),
        in_specs=in_specs2,
        out_specs=(k_spec_k, v_spec_k),
        out_shape=(jax.ShapeDtypeStruct((b, skp, H), k2.dtype),
                   jax.ShapeDtypeStruct((b, skp, nh * d_v), k2.dtype)),
        scratch_shapes=[pltpu.VMEM((1, bk, gd), jnp.float32),
                        pltpu.VMEM((1, bk, gd_v), jnp.float32)],
        name="apex_attn_bwd_dkv",
        **extra,
    )(*args2)

    return dq[:, :sq, :], dk[:, :sk, :], dv[:, :sk, :]


# --- public op --------------------------------------------------------------

def flash_attention(q, k, v, bias=None, scale=None, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    dropout_rate=0.0, dropout_seed=None,
                    causal_offset=None, window=None):
    """Blockwise softmax attention.

    q: (B, Sq, H, D); k/v: (B, Sk, H, D), or (B, Sk, H_kv, D) with H_kv a
    divisor of H (grouped-query attention: q head ``h`` reads k/v head
    ``h // (H / H_kv)``); bias: optional additive
    (B|1, H|1, Sq|1, Sk|1) — the additive-mask variants of the reference
    (`self_multihead_attn_func.py` additive mask path). Returns
    (B, Sq, H, D_v). ``bias`` is differentiable (learned relative-position
    biases work); its gradient path materializes O(S²) scores, computed
    only when actually requested (see ``_bias_grad``).

    ``v`` may have a smaller head size ``D_v`` than q and k (latent
    attention's 128 beside 192). The two native multi-block backward
    kernels read ``v`` and ``dO`` and write ``dv`` at ``D_v``
    (:func:`_v_as_it_is`), and the forward's ``o`` is kept at ``D_v``; the
    forward kernel, and every other path, gets ``v`` zero-padded to ``D``
    and ``o`` sliced back, which is the same result.

    Shared k/v heads reach the kernels repeated a group (in HBM, outside
    the ``custom_vjp``: the repeat's own transpose sums ``dk`` and ``dv``
    over a group); the kernels see one k/v head a q head, as before.

    ``dropout_rate > 0`` applies *softmax* dropout (on the normalized
    probabilities) inside the kernel — the fused Philox dropout of the
    reference (`dropout.h:1-308`) — seeded by ``dropout_seed`` (int32
    scalar, typically drawn fresh per step from the training rng). The
    backward kernels regenerate the identical mask from the same seed;
    no mask tensor ever exists in HBM.

    ``causal_offset`` (int32 scalar, may be TRACED — e.g. derived from
    ``axis_index`` inside a ring hop) shifts the causal frontier: query
    i attends key j iff ``i + causal_offset >= j``. With ``None`` the
    frontier is bottom-right aligned (``Sk − Sq``). On the native-layout
    path the offset rides SMEM into the kernels so ring hops need no
    O(S²) additive bias; geometries that fall back to the bias path
    build the mask from the offset internally.

    A causal call over more than one tile runs, on the native path, only
    the tiles at or below the frontier: tile ``(iq, ik)`` of ``(block_q,
    block_k)`` entries runs iff ``ik·block_k <= iq·block_q + block_q − 1 +
    offset`` (:class:`_Frontier`); the others do no arithmetic and, with
    the static offset ``Sk − Sq``, fetch no block (the index maps repeat
    the neighbouring running step's). A traced ``causal_offset`` skips
    the arithmetic and keeps its fetches. The results are the full grid's
    bit for bit; a q tile that runs no k tile (a ring hop wholly in the
    future) reads ``o = 0`` and ``lse ≈ −1e30``.

    ``window`` (a static int, causal calls only) is sliding-window
    attention: query ``i`` attends the ``window`` newest keys at or before
    its frontier, ``i + Sk − Sq − window + 1 … i + Sk − Sq``. The native
    multi-block kernels then also skip, and fetch nothing for, the tiles
    wholly left of that band; a tile the band's edge crosses is masked
    inside (:class:`_Frontier`). Left at the defaults, the tiles are then
    no wider than the window (:func:`_window_blocks`): at 4096 tokens and
    ``window`` 512 the band is 15 of 64 tiles of 512 × 512, where causal
    attention runs 36 of 64 (10 of 16 of 1024 × 1024). A window of ``Sk``
    or more is causal attention. It takes the native path alone (the
    heads' columns lane-aligned in groups), and no bias, dropout or
    ``causal_offset``.
    """
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], 2) for x in (k, v))
    window = _band(window, q, k, bias, causal, dropout_rate, causal_offset)
    block_q, block_k = _window_blocks(window, block_q, block_k)
    d_v = v.shape[-1]
    if d_v != q.shape[-1] and not _v_as_it_is(q, k, v, bias, block_q,
                                              block_k, dropout_rate):
        v = _v_padded(v, q.shape[-1])
    o = _flash_attention(q, k, v, bias, scale, causal, block_q, block_k,
                         dropout_rate, dropout_seed, causal_offset, window)
    return o if o.shape[-1] == d_v else o[..., :d_v]


def _v_padded(v, d):
    """``v`` zero-padded to the q/k head size ``d``, for the paths that
    take one head size: the zero columns give ``o`` zero columns, which
    the caller slices off."""
    if v.shape[-1] > d:
        raise ValueError(f"v's head size {v.shape[-1]} is above q's {d}")
    return jnp.pad(v, [(0, 0)] * 3 + [(0, d - v.shape[-1])])


def _v_as_it_is(q, k, v, bias, block_q, block_k, dropout_rate):
    """Whether a ``v`` narrower than q and k reaches the backward kernels
    at its own head size: on the native path, without a bias, where the
    backward takes the two multi-block kernels (which read v and dO and
    write dv ``g·D_v`` lanes wide). The single-block fused backward, the
    transposed kernels and the bias gradient take one head size."""
    b, sq, h, d = q.shape
    if bias is not None or _native_g0(h, d, v.shape[-1]) is None:
        return False
    block_q, block_k = _tuned_qk(q, k, block_q, block_k, dropout_rate)
    return _bwd_plan(h, d, sq, k.shape[1], b * h, q.dtype.itemsize, block_q,
                     block_k, dropout_rate=dropout_rate,
                     d_v=v.shape[-1]).form != "fused"


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 11))
def _flash_attention(q, k, v, bias, scale, causal, block_q, block_k,
                     dropout_rate, dropout_seed, causal_offset, window):
    o, _ = _flash_attention_fwd_res(q, k, v, bias, dropout_seed, scale,
                                    causal, block_q, block_k,
                                    dropout_rate, causal_offset,
                                    window=window)
    return o


def _band(window, q, k, bias, causal, dropout_rate, causal_offset):
    """``window`` checked against the call, and None where it keeps every
    causal key (``window >= Sk``): then the call is causal attention's."""
    if window is None or window >= k.shape[1]:
        return None
    if not causal or window < 1:
        raise ValueError(f"window={window} needs causal=True and a window "
                         "of at least one key")
    if (bias is not None or dropout_rate > 0.0 or causal_offset is not None
            or _native_g0(q.shape[2], q.shape[3]) is None):
        raise NotImplementedError(
            "window= runs on the native attention path (lane-groupable "
            "heads), without a bias, dropout or a causal_offset")
    return int(window)


def _window_blocks(window, block_q, block_k):
    """The tiles of a windowed call that left them at the defaults: the
    power of two at or above the window, at least a lane and at most the
    default. A tile much wider than the band evaluates pairs it masks: at
    4096 tokens and a window of 512, 512 x 512 tiles run 15 of 64 (2.0 x
    the band's pairs), 1024 x 1024 ones 7 of 16 (3.7 x). Explicit tiles and
    calls without a window keep theirs."""
    if window is None or (block_q, block_k) != (DEFAULT_BLOCK_Q,
                                                DEFAULT_BLOCK_K):
        return block_q, block_k
    tile = max(LANES, 1 << (window - 1).bit_length())
    return min(tile, block_q), min(tile, block_k)


def _to3(q, k, v):
    b, sq, h, d = q.shape
    tr = lambda t: jnp.swapaxes(t, 1, 2).reshape(b * h, t.shape[1], d)
    return tr(q), tr(k), tr(v)


def _bias_flat(bias, b, h, sq, sk):
    """Shared validate/broadcast/flatten for both bias groupings:
    (B|1, H|1, Sq|1, Sk|1) → ((bb·bh, Sq, Sk), bb, bh). Size-1
    *sequence* dims can't ride the index map (blocks tile them) and
    are materialized to (Sq, Sk)."""
    bb, bh_ = bias.shape[0], bias.shape[1]
    if bb not in (1, b) or bh_ not in (1, h):
        raise ValueError(f"bias dims {bias.shape[:2]} must broadcast "
                         f"against (B={b}, H={h})")
    if bias.shape[2] not in (1, sq) or bias.shape[3] not in (1, sk):
        raise ValueError(f"bias dims {bias.shape[2:]} must broadcast "
                         f"against (Sq={sq}, Sk={sk})")
    bias = jnp.broadcast_to(bias, (bb, bh_, sq, sk))
    return bias.reshape(bb * bh_, sq, sk), bb, bh_


def _bias_group(bias, b, h, sq, sk):
    """(B|1, H|1, Sq|1, Sk|1) bias → ((G, Sq, Sk), idx_fn).

    The kernels index the bias through ``idx_fn(grid_b)`` in their
    BlockSpecs, so a (1, 1, Sq, Sk) causal bias (the ring-attention
    per-hop case) occupies exactly one copy in HBM instead of B·H
    score-sized buffers.
    """
    if bias is None:
        return None, None
    bias_g, bb, bh_ = _bias_flat(bias, b, h, sq, sk)
    if bb == 1 and bh_ == 1:
        idx = lambda g: 0
    elif bb == 1:                       # (1, H, ...) — per-head bias
        idx = lambda g: g % h
    elif bh_ == 1:                      # (B, 1, ...) — per-batch mask
        idx = lambda g: g // h
    else:
        idx = lambda g: g
    return bias_g, idx


def _seed_arr(dropout_seed, dropout_rate):
    if dropout_rate == 0.0:
        return None
    if not 0.0 < dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got "
                         f"{dropout_rate}")
    if dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    return jnp.asarray(dropout_seed, jnp.int32).reshape(-1)[:1]


def _off_arr(causal_offset, causal):
    if causal_offset is None:
        return None
    if not causal:
        raise ValueError("causal_offset requires causal=True")
    return jnp.asarray(causal_offset, jnp.int32).reshape(-1)[:1]


def _offset_bias(off_arr, sq, sk):
    """Fallback additive mask for geometries off the native path:
    built from the (possibly traced) offset scalar."""
    rows = jnp.arange(sq, dtype=jnp.int32)[:, None]
    cols = jnp.arange(sk, dtype=jnp.int32)[None, :]
    return jnp.where(rows + off_arr[0] >= cols, 0.0,
                     NEG_INF).reshape(1, 1, sq, sk)


def _tuned_qk(q, k, block_q, block_k, dropout_rate):
    """Trace-time tuning-DB consult for the attention family.

    Applies only when the caller left (block_q, block_k) at the
    defaults — an explicit override always wins — and never under
    dropout (the Philox mask hash is a function of block coordinates;
    tuned blocks would be a *different* mask than the one the dropout
    contract documents). Called identically from the fwd residual path
    and ``_fa_bwd`` so both directions realize the same tuned blocks.
    Exact-key miss returns the defaults untouched: bit-identical HLO,
    pinned by the ``autotune/no-extra-dispatch`` compile-check case.
    """
    if (block_q, block_k) != (DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K):
        return block_q, block_k
    if dropout_rate > 0.0:
        return block_q, block_k
    from apex_tpu.ops import autotune
    b, sq, h, d = q.shape
    blocks = autotune.lookup_blocks(
        "attention", (b, sq, k.shape[1], h, d), q.dtype)
    if not blocks:
        return block_q, block_k
    return (int(blocks.get("block_q", block_q)),
            int(blocks.get("block_k", block_k)))


def _kept(o, lse):
    """What the forward kernel wrote and the backward reads, under the name
    a checkpoint policy keeps it by: the identity anywhere else."""
    return checkpoint_name(o, KEPT_ATTN), checkpoint_name(lse, KEPT_ATTN)


def _flash_attention_fwd_res(q, k, v, bias, dropout_seed, scale, causal,
                             block_q, block_k, dropout_rate,
                             causal_offset=None, dbo=None, window=None):
    block_q, block_k = _tuned_qk(q, k, block_q, block_k, dropout_rate)
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    seed = _seed_arr(dropout_seed, dropout_rate)
    off = _off_arr(causal_offset, causal)
    if off is not None and bias is not None:
        raise ValueError("causal_offset cannot combine with a bias")
    if dbo is not None:
        if bias is not None or _native_g0(h, d) is None:
            # block offsets shift the dropout hash's global
            # coordinates; the dense bias-grad replica and the
            # transposed fallback do not reconstruct them — fail
            # loudly rather than silently diverge from the
            # single-device mask (docs/parallel.md)
            raise ValueError("dropout_block_offset requires the "
                             "native attention path and no bias")
        cq, ck = _block_cap(block_q, block_k, False, dropout_rate)
        bq_r = _choose_block(cq, sq)
        bk_r = _choose_block(ck, k.shape[1], lane=True)
        if (bq_r, bk_r) != (DROPOUT_TILE, DROPOUT_TILE):
            # the offsets are expressed in DROPOUT_TILE units; a
            # geometry whose realized blocks differ (short shards,
            # overridden block sizes) would apply them in the wrong
            # units and silently draw a different mask
            raise ValueError(
                f"dropout_block_offset requires {DROPOUT_TILE}-sized "
                f"kernel blocks; this geometry realizes "
                f"({bq_r}, {bk_r}) — shard lengths must be multiples "
                f"of {DROPOUT_TILE}")
    d_v = v.shape[-1]
    if _native_g0(h, d, d_v) is not None:
        # native-layout path: (B, S, H) operands straight through — no
        # transpose copies, no D zero-pad (see the native-kernel block).
        # An additive bias rides the native grid as (g|1, bq, bk)
        # blocks (round-5; biased MHA no longer pays the transpose tax)
        bias_nl, bias_mode = _bias_group_nl(bias, b, h, sq, k.shape[1])
        q2 = q.reshape(b, sq, h * d)
        k2 = k.reshape(b, k.shape[1], h * d)
        # the forward kernel takes v at q's head size: on the v5e it ran
        # slower with v at its own 128 beside q's 192 than with v
        # zero-padded (23.3 against 19.2 ms a call at the MLA cells'
        # shape), where the two backward kernels ran 15% faster
        v2 = (v if d_v == d else _v_padded(v, d)).reshape(
            b, v.shape[1], h * d)
        o2, lse = _flash_fwd_nl(q2, k2, v2, h, d, scale, causal,
                                block_q, block_k, dropout_rate, seed,
                                causal_off=off, bias_g=bias_nl,
                                bias_mode=bias_mode, dbo=dbo, window=window)
        o = o2.reshape(b, sq, h, d)
        o, lse = _kept(o if d_v == d else o[..., :d_v], lse)
        return o, (q, k, v, bias, dropout_seed, o, lse, causal_offset)
    eff_bias, eff_causal = bias, causal
    if off is not None:
        # no native path for this geometry: the offset becomes an
        # additive mask (exactly what a caller would have built)
        eff_bias, eff_causal = _offset_bias(off, sq, k.shape[1]), False
    q3, k3, v3 = _to3(q, k, v)
    bias_g, bidx = _bias_group(eff_bias, b, h, sq, k.shape[1])
    o3, lse = _flash_fwd(q3, k3, v3, bias_g, bidx, scale, eff_causal,
                         block_q, block_k, dropout_rate, seed)
    o, lse = _kept(jnp.swapaxes(o3.reshape(b, h, sq, d), 1, 2), lse)
    return o, (q, k, v, bias, dropout_seed, o, lse, causal_offset)


def _fa_fwd(q, k, v, bias, scale, causal, block_q, block_k, dropout_rate,
            dropout_seed, causal_offset, window):
    o, res = _flash_attention_fwd_res(q, k, v, bias, dropout_seed, scale,
                                      causal, block_q, block_k,
                                      dropout_rate, causal_offset,
                                      window=window)
    return o, res


def _fa_bwd(scale, causal, block_q, block_k, dropout_rate, window, res, do):
    q, k, v, bias, dropout_seed, o, lse, causal_offset = res
    block_q, block_k = _tuned_qk(q, k, block_q, block_k, dropout_rate)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale_ = scale if scale is not None else 1.0 / np.sqrt(d)
    seed = _seed_arr(dropout_seed, dropout_rate)
    d_v = v.shape[-1]
    if _native_g0(h, d, d_v) is not None:
        bias_nl, bias_mode = _bias_group_nl(bias, b, h, sq, sk)
        q2 = q.reshape(b, sq, h * d)
        k2 = k.reshape(b, sk, h * d)
        v2 = v.reshape(b, sk, h * d_v)
        do2 = do.reshape(b, sq, h * d_v)
        # delta = rowsum(do·o) per head, straight from the (B, S, H)
        # layout: only the tiny (B, S, nh) per-head sums transpose
        delta = jnp.sum(
            (do.astype(jnp.float32) * o.astype(jnp.float32)), axis=-1)
        delta = jnp.swapaxes(delta, 1, 2).reshape(b * h, sq)
        dq2, dk2, dv2 = _flash_bwd_nl(
            q2, k2, v2, h, d, lse, delta, do2, scale_, causal,
            block_q, block_k, dropout_rate=dropout_rate, seed=seed,
            causal_off=_off_arr(causal_offset, causal),
            bias_g=bias_nl, bias_mode=bias_mode, window=window)
        dbias = None if bias is None else _bias_grad(
            q, k, v, bias, o, lse, do, scale_, causal,
            dropout_rate=dropout_rate, seed=seed,
            block_q=block_q, block_k=block_k)
        return (dq2.reshape(b, sq, h, d), dk2.reshape(b, sk, h, d),
                dv2.reshape(b, sk, h, d_v), dbias, None, None)
    eff_bias, eff_causal = bias, causal
    off = _off_arr(causal_offset, causal)
    if off is not None:
        eff_bias, eff_causal = _offset_bias(off, sq, sk), False
    q3, k3, v3 = _to3(q, k, v)
    bias_g, bidx = _bias_group(eff_bias, b, h, sq, k.shape[1])
    o3 = jnp.swapaxes(o, 1, 2).reshape(b * h, sq, d)
    do3 = jnp.swapaxes(do, 1, 2).reshape(b * h, sq, d)
    dq3, dk3, dv3 = _flash_bwd(q3, k3, v3, bias_g, bidx, o3, lse, do3,
                               scale_, eff_causal, block_q, block_k,
                               dropout_rate=dropout_rate, seed=seed)
    un = lambda t, s_: jnp.swapaxes(t.reshape(b, h, s_, d), 1, 2)
    dbias = None if bias is None else _bias_grad(
        q, k, v, bias, o, lse, do, scale_, causal,
        dropout_rate=dropout_rate, seed=seed,
        block_q=block_q, block_k=block_k)
    return un(dq3, sq), un(dk3, sk), un(dv3, sk), dbias, None, None


def _keep_mask_dense(seed, b, h, sq, sk, bq, bk, rate):
    """Host-side (dense) replica of :func:`_keep_mask` over the full
    (B·H, Sq, Sk) score tensor — bitwise identical to what the kernels
    generate, reconstructed from global coordinates via the block
    decomposition. Only used by the bias-gradient path, which is dense
    anyway."""
    gb = jax.lax.broadcasted_iota(jnp.uint32, (b * h, sq, sk), 0)
    qr = jax.lax.broadcasted_iota(jnp.uint32, (b * h, sq, sk), 1)
    kc = jax.lax.broadcasted_iota(jnp.uint32, (b * h, sq, sk), 2)
    return _mix_keep(seed, gb, qr // bq, kc // bk, qr % bq, kc % bk, rate)


def _bias_grad(q, k, v, bias, o, lse, do, scale, causal, *,
               dropout_rate=0.0, seed=None, block_q=DEFAULT_BLOCK_Q,
               block_k=DEFAULT_BLOCK_K, delta_shift=None):
    """Cotangent for a learned additive bias (e.g. relative-position
    biases): ds = p * (dp - delta), reduced to the bias's broadcast
    shape. Recomputes p from the saved lse so no extra softmax pass is
    needed — but it DOES materialize the (B, H, Sq, Sk) score matrix, the
    very thing flash attention avoids. That is inherent to producing a
    dense dbias; XLA dead-code-eliminates this whole computation whenever
    the caller does not differentiate w.r.t. the bias, so pure-mask users
    pay nothing."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    s = s + bias.astype(jnp.float32)
    if causal:
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse.reshape(b, h, sq)[..., None])
    dp = jnp.einsum("bqhd,bkhd->bhqk", do.astype(jnp.float32),
                    v.astype(jnp.float32))
    if dropout_rate > 0.0:
        # mirror the kernels' block choice exactly via the SHARED cap
        # (the mask hash is a function of block coordinates — a
        # different bq/bk is a different mask)
        cq, ck = _block_cap(block_q, block_k, True, dropout_rate)
        bq = _choose_block(cq, sq)
        bk = _choose_block(ck, sk, lane=True)
        keep = _keep_mask_dense(seed[0], b, h, sq, sk, bq, bk,
                                dropout_rate).reshape(b, h, sq, sk)
        dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                       # (b, sq, h)
    delta = jnp.swapaxes(delta, 1, 2)              # (b, h, sq)
    if delta_shift is not None:
        # the lse-cotangent fold: ds = p*(dp - (delta - dlse))
        delta = delta - delta_shift.astype(jnp.float32)
    ds = p * (dp - delta[..., None])
    for axis in range(4):
        if bias.shape[axis] == 1:
            ds = jnp.sum(ds, axis=axis, keepdims=True)
    return ds.astype(bias.dtype)


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def attention_reference(q, k, v, bias=None, scale=None, causal=False,
                        window=None):
    """Pure-jnp oracle — the reference's ``impl='default'`` python path
    (`self_multihead_attn_func.py:6-232`). Runs with the O1 raw-op patch
    suspended: its fp32 einsums are the point of the oracle. ``window``:
    :func:`flash_attention`'s band, as a dense mask."""
    from apex_tpu.amp.functional_patch import suspend
    with suspend():
        return _attention_reference(q, k, v, bias, scale, causal, window)


def _attention_reference(q, k, v, bias, scale, causal, window=None):
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    if k.shape[2] != q.shape[2]:    # grouped-query: a k/v head a group
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], 2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        sq, sk = s.shape[-2:]
        mask = np.tril(np.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~np.tril(np.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def mask_softmax_dropout(scores, mask=None, dropout_rate=0.0,
                         rng=None, deterministic=True):
    """Standalone (masked) softmax(+dropout) on explicit scores —
    ``fast_mask_softmax_dropout_func``
    (`apex/contrib/multihead_attn/fast_mask_softmax_dropout_func.py`)."""
    s = scores.astype(jnp.float32)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0 and not deterministic:
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return p.astype(scores.dtype)


# --- lse-returning variant (sequence-parallel building block) ---------------

def flash_attention_lse(q, k, v, bias=None, scale=None, causal=False,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        *, dropout_rate=0.0, dropout_seed=None,
                        causal_offset=None, dropout_block_offset=None,
                        window=None):
    """Like :func:`flash_attention` but returns ``(out, lse)`` with
    ``lse`` (B, H, Sq) differentiable — the building block ring attention
    needs to merge partial results across sequence shards.
    ``causal_offset`` shifts the causal frontier like
    :func:`flash_attention`'s (ring hops pass their traced global
    offset so no O(S²) hop bias is ever built on the native path).
    ``dropout_block_offset`` — a traced (2,) int32 of global
    (q-block, k-block) offsets — shifts the counter-based dropout
    hash's block coordinates, so a sequence shard reproduces exactly
    the keep mask the single-device call would have generated at the
    same global coordinates (ring hops pass their ring position; the
    reference's fused dropout has no distributed counterpart,
    `apex/contrib/csrc/multihead_attn/dropout.h:1-308`).

    ``dropout_rate``/``dropout_seed``/``causal_offset``/
    ``dropout_block_offset`` are keyword-only: they were inserted ahead
    of ``causal_offset`` historically, so a positional caller would
    silently bind an offset to ``dropout_rate`` — now it fails loudly
    at the call site (ADVICE r5). ``window`` is :func:`flash_attention`'s
    (q and k with the same heads here). A ``v`` narrower than q and k is
    zero-padded to their head size and ``o`` sliced back.
    """
    window = _band(window, q, k, bias, causal, dropout_rate, causal_offset)
    block_q, block_k = _window_blocks(window, block_q, block_k)
    d_v = v.shape[-1]
    if d_v != q.shape[-1]:
        v = _v_padded(v, q.shape[-1])
    o, lse = _flash_attention_lse(q, k, v, bias, scale, causal, block_q,
                                  block_k, dropout_rate, dropout_seed,
                                  causal_offset, dropout_block_offset, window)
    return (o if o.shape[-1] == d_v else o[..., :d_v]), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 12))
def _flash_attention_lse(q, k, v, bias, scale, causal, block_q, block_k,
                         dropout_rate, dropout_seed, causal_offset,
                         dropout_block_offset, window):
    # positional custom_vjp core — custom_vjp cannot resolve
    # keyword-only parameters, hence the public wrapper above
    (o, lse), _ = _fal_fwd(q, k, v, bias, scale, causal, block_q,
                           block_k, dropout_rate, dropout_seed,
                           causal_offset, dropout_block_offset, window)
    return o, lse


def _fal_fwd(q, k, v, bias, scale, causal, block_q, block_k,
             dropout_rate, dropout_seed, causal_offset,
             dropout_block_offset, window):
    if dropout_rate > 0.0 and _native_g0(q.shape[2], q.shape[3]) is None:
        # the lse variant's backward has no transposed dropout path —
        # fail at trace time, not at the first jax.grad deep in a step
        raise NotImplementedError(
            "flash_attention_lse dropout requires the native attention "
            "path (lane-groupable heads)")
    dbo = (None if dropout_block_offset is None
           else jnp.asarray(dropout_block_offset, jnp.int32).reshape(2))
    o, res = _flash_attention_fwd_res(q, k, v, bias, dropout_seed,
                                      scale, causal, block_q, block_k,
                                      dropout_rate, causal_offset, dbo,
                                      window)
    b, sq, h, _ = q.shape
    return (o, res[6].reshape(b, h, sq)), res + (dbo,)


def _fal_bwd(scale, causal, block_q, block_k, dropout_rate, window, res,
             cot):
    do, dlse = cot
    q, k, v, bias, dropout_seed, o, lse, causal_offset, dbo = res
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale_ = scale if scale is not None else 1.0 / np.sqrt(d)
    seed = _seed_arr(dropout_seed, dropout_rate)
    # d lse/d s = p, so the lse cotangent folds into the delta term:
    # ds = p*(dp - delta) + p*dlse = p*(dp - (delta - dlse))
    if _native_g0(h, d) is not None:
        bias_nl, bias_mode = _bias_group_nl(bias, b, h, sq, sk)
        q2 = q.reshape(b, sq, h * d)
        k2 = k.reshape(b, sk, h * d)
        v2 = v.reshape(b, sk, h * d)
        do2 = do.reshape(b, sq, h * d)
        delta = jnp.sum(
            (do.astype(jnp.float32) * o.astype(jnp.float32)), axis=-1)
        delta = jnp.swapaxes(delta, 1, 2).reshape(b * h, sq)
        delta = delta - dlse.reshape(b * h, sq).astype(jnp.float32)
        dq2, dk2, dv2 = _flash_bwd_nl(
            q2, k2, v2, h, d, lse, delta, do2, scale_, causal,
            block_q, block_k, dropout_rate=dropout_rate, seed=seed,
            causal_off=_off_arr(causal_offset, causal),
            delta_shifted=True, bias_g=bias_nl, bias_mode=bias_mode,
            dbo=dbo, window=window)
        dbias = None if bias is None else _bias_grad(
            q, k, v, bias, o, lse, do, scale_, causal,
            dropout_rate=dropout_rate, seed=seed,
            block_q=block_q, block_k=block_k,
            delta_shift=dlse.reshape(b, h, sq))
        return (dq2.reshape(b, sq, h, d), dk2.reshape(b, sk, h, d),
                dv2.reshape(b, sk, h, d), dbias, None, None, None)
    if dropout_rate > 0.0:
        raise NotImplementedError(
            "flash_attention_lse dropout requires the native attention "
            "path (lane-groupable heads); this geometry fell back to "
            "the transposed kernels")
    eff_bias, eff_causal = bias, causal
    off = _off_arr(causal_offset, causal)
    if off is not None:
        eff_bias, eff_causal = _offset_bias(off, sq, sk), False
    q3, k3, v3 = _to3(q, k, v)
    bias_g, bidx = _bias_group(eff_bias, b, h, sq, k.shape[1])
    o3 = jnp.swapaxes(o, 1, 2).reshape(b * h, sq, d)
    do3 = jnp.swapaxes(do, 1, 2).reshape(b * h, sq, d)
    dlse3 = dlse.reshape(b * h, sq)
    dq3, dk3, dv3 = _flash_bwd(q3, k3, v3, bias_g, bidx, o3, lse, do3,
                               scale_, eff_causal, block_q, block_k,
                               delta_shift=dlse3)
    un = lambda t, s_: jnp.swapaxes(t.reshape(b, h, s_, d), 1, 2)
    dbias = None if bias is None else _bias_grad(
        q, k, v, bias, o, lse, do, scale_, causal,
        block_q=block_q, block_k=block_k,
        delta_shift=dlse.reshape(b, h, sq))
    return (un(dq3, sq), un(dk3, sk), un(dv3, sk), dbias, None, None,
            None)


_flash_attention_lse.defvjp(_fal_fwd, _fal_bwd)
