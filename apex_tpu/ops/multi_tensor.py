"""Arena-wide fused elementwise kernels: the `amp_C` trio.

TPU-native rebuild of the reference's multi-tensor-apply family
(`csrc/multi_tensor_scale_kernel.cu`, `multi_tensor_axpby_kernel.cu`,
`multi_tensor_l2norm_kernel.cu`, launcher `csrc/multi_tensor_apply.cuh`):
instead of packing ≤110 tensor pointers into kernel-arg structs per launch,
the tensors already live in one flat arena buffer (apex_tpu.arena) and a
single Pallas kernel walks it in (512, 128) VMEM blocks via the shared
launcher (apex_tpu.ops._dispatch.launch).

Every op keeps the reference's overflow-flag contract: `scale`/`axpby` also
produce a scalar "all finite" flag computed in the same pass (the CUDA
kernels write a `noop_flag` on inf/nan, `multi_tensor_scale_kernel.cu:30-70`),
except the flag stays on device.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._dispatch import launch


# --- multi_tensor_scale ------------------------------------------------------

def _scale_kernel(scalars, x_ref, out_ref, flag_ref):
    i = pl.program_id(0)
    scale = scalars[0]
    y = x_ref[:].astype(jnp.float32) * scale

    @pl.when(i == 0)
    def _():
        flag_ref[0, 0] = 0.0

    # inf/nan in the *output* sets the noop flag (reference checks the
    # converted value, multi_tensor_scale_kernel.cu:57-63)
    bad = jnp.logical_not(jnp.all(jnp.isfinite(y)))
    flag_ref[0, 0] = flag_ref[0, 0] + jnp.where(bad, 1.0, 0.0)
    out_ref[:] = y.astype(out_ref.dtype)


def multi_tensor_scale(buf, scale, *, out_dtype=None):
    """``out = buf * scale`` over a flat arena buffer, with overflow flag.

    Returns ``(out, all_finite)``. Used for grad unscale (model→master copy
    with 1/loss_scale) and master→model copy-back, exactly the two places
    the reference launches `amp_C.multi_tensor_scale`
    (`apex/amp/scaler.py:94-125`, `_process_optimizer.py:354-364`).
    """
    out_dtype = jnp.dtype(out_dtype) if out_dtype else buf.dtype
    out, flag = launch(
        _scale_kernel, [buf],
        outs=[("block", out_dtype), ("scalar", jnp.float32)],
        scalars=[scale], name="apex_rows_scale")
    return out, flag[0, 0] == 0.0


# --- multi_tensor_axpby ------------------------------------------------------

def _axpby_kernel(scalars, x_ref, y_ref, out_ref, flag_ref):
    i = pl.program_id(0)
    a, b = scalars[0], scalars[1]
    r = (a * x_ref[:].astype(jnp.float32)
         + b * y_ref[:].astype(jnp.float32))

    @pl.when(i == 0)
    def _():
        flag_ref[0, 0] = 0.0

    bad = jnp.logical_not(jnp.all(jnp.isfinite(r)))
    flag_ref[0, 0] = flag_ref[0, 0] + jnp.where(bad, 1.0, 0.0)
    out_ref[:] = r.astype(out_ref.dtype)


def multi_tensor_axpby(a, x, b, y, *, out_dtype=None):
    """``out = a*x + b*y`` with overflow flag — stashed-gradient
    accumulation (`apex/amp/scaler.py:152-190`,
    `csrc/multi_tensor_axpby_kernel.cu:28-90`). Returns (out, all_finite)."""
    out_dtype = jnp.dtype(out_dtype) if out_dtype else x.dtype
    out, flag = launch(
        _axpby_kernel, [x, y],
        outs=[("block", out_dtype), ("scalar", jnp.float32)],
        scalars=[a, b], name="apex_rows_axpby")
    return out, flag[0, 0] == 0.0


# --- multi_tensor_l2norm -----------------------------------------------------

def _l2norm_kernel(x_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0, 0] = 0.0

    x = x_ref[:].astype(jnp.float32)
    acc_ref[0, 0] = acc_ref[0, 0] + jnp.sum(x * x)


def multi_tensor_l2norm(buf):
    """Global L2 norm of a flat arena buffer (fp32 accumulate).

    One-kernel version of the two-stage partial+cleanup reduction
    (`csrc/multi_tensor_l2norm_kernel.cu:28-113`): TPU grids run
    sequentially on-core, so the partial sums accumulate in a revisited
    (1,1) SMEM scalar. Arena padding is zero, so no masking is needed.
    """
    acc = launch(_l2norm_kernel, [buf], outs=[("scalar", jnp.float32)],
                 name="apex_rows_l2norm")
    return jnp.sqrt(acc[0, 0])


def _maxnorm_kernel(x_ref, acc_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[0, 0] = 0.0

    acc_ref[0, 0] = jnp.maximum(
        acc_ref[0, 0], jnp.max(jnp.abs(x_ref[:].astype(jnp.float32))))


def multi_tensor_maxnorm(buf):
    """Global max-abs (Linf) — `MaxNormFunctor`
    (`multi_tensor_l2norm_kernel.cu:113-160`)."""
    acc = launch(_maxnorm_kernel, [buf], outs=[("scalar", jnp.float32)],
                 name="apex_rows_maxnorm")
    return acc[0, 0]


def per_tensor_l2norm(buf, segment_ids, num_tensors):
    """Per-tensor L2 norms over the arena in one pass (`multi_tensor_l2norm`
    with ``per_tensor=True``). ``segment_ids`` maps arena position → tensor
    index (-1 padding); returns (num_tensors,) f32 norms.

    NOTE: ``segment_sum`` lowers to scatter-add, which TPU serializes
    (hundreds of ms on large arenas). When the segment layout is static —
    it always is for the arena, whose offsets are Python ints — use
    :func:`per_tensor_l2norm_ranges` instead; this traced-ids version
    remains for callers whose boundaries are genuinely dynamic (e.g.
    ZeRO shard-local spans that depend on ``axis_index``)."""
    sq = jnp.square(buf.astype(jnp.float32))
    sums = jax.ops.segment_sum(sq, jnp.maximum(segment_ids, 0),
                               num_segments=num_tensors)
    # padding contributes zeros (buf padding is 0), so no correction needed
    return jnp.sqrt(sums)


def per_tensor_l2norm_ranges(buf, offsets, sizes):
    """Per-tensor L2 norms from STATIC arena ranges — no scatter.

    ``offsets``/``sizes`` are the arena partition's Python-int tuples, so
    each tensor becomes one contiguous slice-reduce; XLA fuses the lot
    into a single pass over the buffer. This is the TPU-idiomatic form
    of ``multi_tensor_l2norm(per_tensor=True)``
    (`multi_tensor_l2norm_kernel.cu:28-113`)."""
    b32 = buf.astype(jnp.float32)
    sums = [jnp.sum(jnp.square(jax.lax.slice_in_dim(b32, off, off + sz)))
            for off, sz in zip(offsets, sizes)]
    return jnp.sqrt(jnp.stack(sums))


def per_tensor_maxnorm_ranges(buf, offsets, sizes):
    """Per-tensor max-abs (Linf) norms from static arena ranges — the
    per-tensor ``MaxNormFunctor`` without scatter."""
    b32 = jnp.abs(buf.astype(jnp.float32))
    maxs = [jnp.max(jax.lax.slice_in_dim(b32, off, off + sz))
            for off, sz in zip(offsets, sizes)]
    return jnp.stack(maxs)


def per_tensor_sq_shard(buf, offsets, sizes, shard_start,
                        block: int = 64 * 1024):
    """Per-tensor sums of squares over ONE shard of the arena — the
    sharded-norm building block of DistributedFusedLAMB
    (`distributed_fused_lamb.py:453-472`), scatter-free.

    ``buf`` is this device's contiguous shard; ``shard_start`` its
    (traced) global offset; tensor ``offsets``/``sizes`` are the static
    arena layout. Each tensor's shard-local overlap decomposes into
    whole blocks (summed from one per-block partial-sums vector) plus at
    most two masked boundary blocks, read via ``dynamic_slice`` — no
    scatter, no gather over the buffer, and no cumsum-difference
    cancellation (every element is added exactly once in fp32).
    Returns (num_tensors,) partial sq-sums; ``psum`` them across shards
    for the exact global per-tensor norms.
    """
    s = buf.shape[0]
    nb = -(-s // block)
    sq = jnp.square(buf.astype(jnp.float32))
    sqp = jnp.pad(sq, (0, nb * block - s))
    bsums = jnp.sum(sqp.reshape(nb, block), axis=1)
    ib = jax.lax.iota(jnp.int32, nb)
    lane = jax.lax.iota(jnp.int32, block)
    start = jnp.asarray(shard_start, jnp.int32)

    def one(off, sz):
        lo = jnp.clip(off - start, 0, s)
        hi = jnp.clip(off + sz - start, 0, s)
        bl = (lo + block - 1) // block      # first whole block
        bh = hi // block                    # one past last whole block
        interior = jnp.sum(jnp.where((ib >= bl) & (ib < bh), bsums, 0.0))
        # left partial: [lo, left_end) inside block lo//block
        left_end = jnp.minimum(bl * block, hi)
        lblk = jax.lax.dynamic_slice_in_dim(sqp, (lo // block) * block,
                                            block)
        lpos = (lo // block) * block + lane
        left = jnp.sum(jnp.where((lpos >= lo) & (lpos < left_end),
                                 lblk, 0.0))
        # right partial: [max(bh*block, left_end), hi)
        rstart = jnp.maximum(bh * block, left_end)
        rblk = jax.lax.dynamic_slice_in_dim(sqp, bh * block, block)
        rpos = bh * block + lane
        right = jnp.sum(jnp.where((rpos >= rstart) & (rpos < hi),
                                  rblk, 0.0))
        return interior + left + right

    return jnp.stack([one(off, sz) for off, sz in zip(offsets, sizes)])


def spread_per_tensor_shard(values, offsets, sizes, shard_start, per,
                            fill=0.0):
    """Shard-local inverse of :func:`per_tensor_sq_shard`: broadcast a
    (num_tensors,) vector over this shard's slice of the arena layout —
    ``values[segment_ids]`` without the serialized per-element gather.

    Each (static-size) tensor writes its shard overlap with one
    ``dynamic_update_slice`` of a windowed read-modify-write: the window
    of length ``min(size, per)`` always covers the overlap, and the
    ``where`` keeps existing content at window positions outside the
    tensor's span, so clamping at shard edges cannot clobber neighbours.
    One pass of reads+writes over the shard in total.
    """
    start = jnp.asarray(shard_start, jnp.int32)
    out = jnp.full((per,), fill, values.dtype)
    for j, (off, sz) in enumerate(zip(offsets, sizes)):
        ln = min(sz, per)
        cl = jnp.clip(off - start, 0, per - ln)
        cur = jax.lax.dynamic_slice_in_dim(out, cl, ln)
        gpos = cl + start + jax.lax.iota(jnp.int32, ln)
        valid = (gpos >= off) & (gpos < off + sz)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.where(valid, values[j], cur), cl, axis=0)
    return out


def spread_per_tensor(values, offsets, padded, total, fill=0.0):
    """Broadcast a (num_tensors,) vector back over the arena layout —
    the inverse gather ``values[segment_ids]`` without the 100M-index
    gather: static concatenation of broadcasts (``fill`` in alignment
    gaps and tail padding)."""
    pieces = []
    pos = 0
    for j, (off, sz_pad) in enumerate(zip(offsets, padded)):
        if off > pos:
            pieces.append(jnp.full((off - pos,), fill, values.dtype))
        pieces.append(jnp.broadcast_to(values[j], (sz_pad,)))
        pos = off + sz_pad
    if pos < total:
        pieces.append(jnp.full((total - pos,), fill, values.dtype))
    return jnp.concatenate(pieces)
