"""Backend dispatch for Pallas kernels.

Compiled Mosaic kernels require a real TPU; every kernel in apex_tpu runs in
Pallas interpret mode on other backends (the CI CPU mesh), preserving
semantics bit-for-bit at jnp precision. This mirrors the reference's
"Python-only build degrades gracefully" contract
(`apex/amp/scaler.py:39-52`) — except nothing is unavailable here, only
uncompiled.

``APEX_TPU_FORCE_INTERPRET=1`` forces interpret mode everywhere (debugging).

Every kernel is launched through :func:`pallas_call` here, under a name
from :data:`KERNEL_NAMES`: the name is the kernel's in the Mosaic module
and the ``jax.named_scope`` the call is bound under, which is how the device
trace tells one kernel from another (``prof.xplane.own_scope``) whatever a
user calls the flax module it runs in.
"""

from __future__ import annotations

import functools
import os

import jax


def use_interpret() -> bool:
    if os.environ.get("APEX_TPU_FORCE_INTERPRET") == "1":
        return True
    return jax.default_backend() != "tpu"


#: every kernel of the library, one name for each ``pallas_call`` site
#: (tests/test_kernel_names.py walks the sources against this table)
KERNEL_NAMES = (
    # attention.py: native (B, S, H) layout, then the packed (B*H, S, D)
    "apex_attn_fwd", "apex_attn_bwd", "apex_attn_bwd_dq",
    "apex_attn_bwd_dkv", "apex_attn_fwd_packed", "apex_attn_bwd_dq_packed",
    "apex_attn_bwd_dkv_packed",
    "apex_layer_norm_fwd", "apex_layer_norm_bwd",
    "apex_xentropy_fwd", "apex_xentropy_bwd",
    "apex_mlp_fwd",
    # delta_rule.py: a chunk's terms, state step and output, and their
    # backward; a decay a key channel, then one a head
    "apex_kda_fwd", "apex_kda_bwd", "apex_gdn_fwd", "apex_gdn_bwd",
    # short_conv.py: convolution + SiLU + l2-norm in the scan's layout
    "apex_short_conv_fwd", "apex_short_conv_bwd",
    # grouped_matmul.py: rows sorted by group, a matrix a group (ops/moe.py)
    "apex_gmm", "apex_tgmm", "apex_unwritten",
    # flat-buffer row kernels through launch(): multi_tensor, optim_kernels
    "apex_rows_scale", "apex_rows_axpby", "apex_rows_l2norm",
    "apex_rows_maxnorm", "apex_rows_adam", "apex_rows_sgd",
    "apex_rows_adagrad", "apex_rows_lamb_stage1", "apex_rows_lamb_stage2",
    "apex_rows_novograd",
)


#: ``jax.ad_checkpoint.checkpoint_name`` tags on what a forward kernel wrote
#: and the backward reads, set inside the ops' ``custom_vjp`` forward rules,
#: one name a kernel family: attention's ``o`` and ``lse``; the delta rule's
#: output, chunk-start states and ``(I + A)^-1``. A ``jax.checkpoint`` or
#: ``nn.remat`` with ``policy=save_only_these_names(*KEPT_NAMES)`` keeps them,
#: so that its rerun of the forward holds no kernel (docs/layers.md)
KEPT_ATTN = "apex_attn_kept"
KEPT_KDA = "apex_kda_kept"
KEPT_NAMES = (KEPT_ATTN, KEPT_KDA)


def pallas_call(kernel, *, name, **kwargs):
    """``pl.pallas_call`` for a kernel of this library, interpreted off a
    TPU. ``name`` becomes the kernel's name in the Mosaic module and the
    HLO instruction's (``%apex_attn_fwd.3``), and ``pl.pallas_call`` binds
    the call under a ``jax.named_scope`` of that name, which is what the
    device trace carries (tests/test_kernel_names.py holds it to that).
    Neither adds an op."""
    from jax.experimental import pallas as pl

    if name not in KERNEL_NAMES:
        raise ValueError(f"{name!r} is not in ops._dispatch.KERNEL_NAMES")
    return pl.pallas_call(kernel, name=name, interpret=use_interpret(),
                          **kwargs)


def jit_launcher(fn=None, *, static_argnums=()):
    """``jax.jit`` for a function that launches a kernel, so that the calls
    of one shape share one trace and one lowered function (XLA inlines each
    under its call site's own scope): a model's step holds a kernel once a
    layer and again in a block's rerun, and tracing it anew each time is
    seconds of every run's set-up. The trace is keyed on
    :func:`use_interpret` as well, which ``pallas_call`` reads while it is
    traced."""
    if fn is None:
        return functools.partial(jit_launcher, static_argnums=static_argnums)

    def keyed(interpret, *args):
        return fn(*args)
    keyed.__name__ = keyed.__qualname__ = fn.__name__   # jit(<name>) scopes
    jitted = jax.jit(keyed,
                     static_argnums=(0, *(i + 1 for i in static_argnums)))
    return functools.wraps(fn)(lambda *args: jitted(use_interpret(), *args))


def kernel_calls(lowered_text):
    """How often each kernel runs in a lowered step: ``lowered_text`` is
    ``jax.jit(f).lower(...).as_text(debug_info=True)`` of a program lowered
    for the TPU, and the result maps a name of :data:`KERNEL_NAMES` to its
    calls from ``@main`` on. A kernel launched through a jitted wrapper
    (``ops/delta_rule.py``, ``ops/short_conv.py``) is in the text once, in a
    private function that every call site of that shape calls
    (:func:`jit_launcher`): the calls are counted, not the functions."""
    import collections
    import re

    own, calls, name = {}, {}, None
    for line in lowered_text.splitlines():
        started = re.match(r"\s*func\.func \w+ @([\w.$-]+)\(", line)
        if started:
            name = started.group(1)
            own[name], calls[name] = collections.Counter(), []
            continue
        if name is None:
            continue
        own[name].update(re.findall(r'kernel_name = "(\w+)"', line))
        calls[name] += re.findall(r"\bcall @([\w.$-]+)\(", line)

    @functools.lru_cache(None)
    def total(fn):
        return sum((total(c) for c in calls.get(fn, ())),
                   own.get(fn, collections.Counter()))
    return total("main")


# Rows per grid step for flat-buffer elementwise kernels. A (512, 128) fp32
# block is 256 KiB — small enough that an 8-operand optimizer kernel stays
# well under the ~16 MiB VMEM budget with double buffering, large enough to
# saturate HBM bandwidth. Default only: `launch` consults the tuning DB
# (apex_tpu.ops.autotune, family "optimizer") and accepts an explicit
# ``block_rows`` per call; the module constant stays the arena's shard
# alignment anchor (optim.distributed imports it).
BLOCK_ROWS = 512
LANES = 128


def as_rows(buf, block_rows=None):
    """View a flat arena buffer as (rows, 128). Arena buffers are padded to
    BUFFER_MULTIPLE (= 512 * 128 elements) so rows % BLOCK_ROWS == 0 always
    holds for the default block; a tuned/explicit ``block_rows`` that does
    not divide the buffer is refused upstream in `_resolve_block_rows`, not
    here."""
    n = buf.shape[0]
    br = BLOCK_ROWS if block_rows is None else block_rows
    assert n % (br * LANES) == 0, (
        f"arena buffer length {n} is not a multiple of {br * LANES} "
        f"(block_rows={br} x {LANES} lanes). Flat optimizer buffers must "
        f"come from apex_tpu.arena.flatten, which pads to BUFFER_MULTIPLE "
        f"= {512 * LANES} elements; a buffer satisfying BUFFER_MULTIPLE "
        f"but not a tuned non-default block is rejected before launch by "
        f"_resolve_block_rows, which falls back to BLOCK_ROWS={BLOCK_ROWS} "
        f"and names the tuning-DB fingerprint responsible.")
    return buf.reshape(n // LANES, LANES)


def _resolve_block_rows(rows, buf0, block_rows):
    """Pick the grid block for one launch: explicit caller value, else a
    tuning-DB hit for this buffer's (length, dtype), else BLOCK_ROWS.

    A tuned/explicit block that does not divide the (BUFFER_MULTIPLE-padded)
    buffer would trip the `as_rows` shape assert deep in pallas plumbing
    with no hint of *which* DB entry chose it — the satellite-2 bug. Refuse
    it here instead: warn naming the offending fingerprint and the fallback
    taken, then launch on the default block.
    """
    import warnings

    n = int(buf0.shape[0])
    explicit = block_rows is not None
    if not explicit:
        from apex_tpu.ops import autotune
        block_rows = autotune.tuned_rows(
            "optimizer", (n,), buf0.dtype, lo=8, hi=4096)
        if block_rows is None:
            return BLOCK_ROWS
    br = int(block_rows)
    if br <= 0 or rows % br:
        from apex_tpu.ops import autotune
        fp = autotune.fingerprint("optimizer", (n,), buf0.dtype)
        src = "explicit block_rows" if explicit else "tuning entry"
        warnings.warn(
            f"{src} {fp}: block_rows={br} does not divide the "
            f"{rows}-row arena buffer (length {n}, BUFFER_MULTIPLE-padded) "
            f"— falling back to BLOCK_ROWS={BLOCK_ROWS}; re-run "
            f"scripts/kernel_tune.py --update-db to re-measure this shape",
            RuntimeWarning, stacklevel=3)
        return BLOCK_ROWS
    return br


def launch(kernel, inputs, outs, scalars=None, block_rows=None, *, name):
    """Shared pallas_call plumbing for flat-buffer elementwise kernels.

    The single launch convention every arena kernel uses (the analogue of
    the reference's `multi_tensor_apply.cuh` launcher): a 1-D grid over
    (BLOCK_ROWS, 128) VMEM blocks of each input buffer, an optional f32
    hyperparameter vector in SMEM prepended to the kernel args, and outputs
    that are either per-block buffers or (1,1) SMEM scalar accumulators
    revisited by every grid step (TPU grids are sequential, so
    read-modify-write accumulation is well-defined; Mosaic requires scalar
    stores to target SMEM, not VMEM).

    ``outs`` is a list of ("block", dtype) | ("scalar", dtype) entries.
    Block outputs come back as flat buffers, scalar outputs as (1, 1)
    arrays, in order. ``name`` is the kernel's in :data:`KERNEL_NAMES`.
    """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows_arrs = [as_rows(b) for b in inputs]
    rows = rows_arrs[0].shape[0]
    br = _resolve_block_rows(rows, inputs[0], block_rows)
    block = pl.BlockSpec((br, LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    scalar = pl.BlockSpec((1, 1), lambda i: (0, 0),
                          memory_space=pltpu.SMEM)

    in_specs = [block] * len(rows_arrs)
    args = tuple(rows_arrs)
    if scalars is not None:
        in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] + in_specs
        args = (jnp.asarray(scalars, jnp.float32),) + args

    out_specs, out_shapes = [], []
    for kind, dt in outs:
        if kind == "block":
            out_specs.append(block)
            out_shapes.append(jax.ShapeDtypeStruct((rows, LANES),
                                                   jnp.dtype(dt)))
        elif kind == "scalar":
            out_specs.append(scalar)
            out_shapes.append(jax.ShapeDtypeStruct((1, 1), jnp.dtype(dt)))
        else:
            raise ValueError(f"unknown out kind {kind!r}")

    results = pallas_call(
        kernel,
        name=name,
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shapes),
    )(*args)
    if not isinstance(results, (list, tuple)):
        results = (results,)
    final = tuple(r.reshape(-1) if kind == "block" else r
                  for r, (kind, _) in zip(results, outs))
    return final if len(final) > 1 else final[0]
