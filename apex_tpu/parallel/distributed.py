"""Data-parallel gradient synchronisation — the DDP equivalent.

The reference's ``DistributedDataParallel`` (`apex/parallel/distributed.py:
129-639`) is ~600 lines of machinery whose entire job is to make NCCL
allreduce overlap backward: per-param grad hooks, arrival-order bucket
construction broadcast from rank 0, flatten/unflatten, side CUDA streams,
epilogue callbacks. On TPU the same capability is a *program property*:
gradients computed under ``shard_map`` over a ``data`` mesh axis are synced
with ``psum``, and XLA's latency-hiding scheduler overlaps the collectives
with remaining backward compute — the bucket/stream machinery is the
compiler's job. What survives as API are the *semantic* knobs:

- ``gradient_average`` / ``gradient_predivide_factor``
  (`distributed.py:144-148,442-451`): pre/post division around the reduce.
- ``allreduce_always_fp32`` (`distributed.py:140-143,455-459`): reduce half
  grads in fp32.
- ``delay_allreduce`` (`distributed.py:168,491-510`): the reference's
  ``allreduce_fallback`` — skip overlapped per-bucket reduction, sync
  everything in one flat fused all-reduce per dtype at the end. Here it
  switches ``sync`` to :func:`flat_tree_all_reduce` (one ``psum`` of a
  concatenated buffer per dtype instead of per-tensor ``psum``s).
- ``no_sync`` / ``_disable_allreduce`` (`distributed.py:566-570`): gradient
  accumulation without communication.
- ``message_size`` (`distributed.py:165`): the bucket-combine threshold,
  forwarded to XLA's collective-combiner via jit ``compiler_options``
  (``xla_gpu_all_reduce_combine_threshold_bytes`` — the DebugOptions field
  is shared across backends, and XLA:CPU and libtpu both accept it).
  With ``bucket_allreduce=True`` the same knob
  sizes *explicit* buckets instead: ``allreduce_bucket`` parity
  (`distributed.py:425-475`) — one psum per reverse-parameter-order
  bucket, chained so the latency-hiding scheduler overlaps each bucket's
  all-reduce with the remaining backward (see
  :mod:`apex_tpu.parallel.comm`).
- ``compress`` (``"bf16"`` / ``"int8"``): compressed collectives with an
  optional error-feedback residual — capability the reference never had
  (EQuARX/DynamiQ lineage), see :func:`comm.bucketed_all_reduce`.

``Reducer`` (`distributed.py:89-126`) survives as the manual-trigger
average; ``flat_dist_call`` (`distributed.py:26-49`) as ``flat_all_reduce``
over an arena buffer.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.parallel.mesh import DATA_AXIS

#: named-scope patterns (regex fragments) under which this package
#: deliberately emits collectives — the allowlist apexlint's
#: implicit-resharding rules (APX102/APX202) check compiled collectives
#: against. The canonical table now lives in
#: :mod:`apex_tpu.parallel.registry` — one declarative row per planned
#: collective family, carrying the mesh axis it communicates over (the
#: mesh model / topology rules consume the same rows); this name is the
#: backward-compatible flat view.
from apex_tpu.parallel.registry import known_patterns as _known_patterns

KNOWN_COLLECTIVE_SCOPES = _known_patterns()


def _is_float(x):
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def sync_gradients(grads, axis_name: str = DATA_AXIS, *,
                   gradient_average: bool = True,
                   gradient_predivide_factor: float = 1.0,
                   allreduce_always_fp32: bool = False):
    """All-reduce a gradient pytree across ``axis_name`` (inside shard_map).

    Implements the arithmetic of ``allreduce_bucket``
    (`apex/parallel/distributed.py:425-475`): optionally cast to fp32,
    divide by ``predivide_factor`` before the reduce, ``psum``, then divide
    by ``world/predivide`` after (or not at all when ``gradient_average``
    is off), casting back to the gradient dtype at the end.
    """
    world = jax.lax.axis_size(axis_name)

    def _sync(g):
        if not _is_float(g):
            return g
        orig = g.dtype
        if allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if gradient_predivide_factor != 1.0:
            g = g / gradient_predivide_factor
        g = jax.lax.psum(g, axis_name)
        if gradient_average:
            post = world / gradient_predivide_factor
            if post != 1.0:
                g = g / post
        return g.astype(orig)

    return jax.tree_util.tree_map(_sync, grads)


def flat_all_reduce(buf: jax.Array, axis_name: str = DATA_AXIS, *,
                    average: bool = True) -> jax.Array:
    """One fused all-reduce of a flat arena buffer — ``flat_dist_call``
    (`apex/parallel/distributed.py:26-49`) with the flatten already done by
    the arena. Also the ``delay_allreduce`` fallback path
    (`distributed.py:491-510`)."""
    out = jax.lax.psum(buf, axis_name)
    if average:
        out = out / jax.lax.axis_size(axis_name)
    return out


def flat_tree_all_reduce(grads, axis_name: str = DATA_AXIS, *,
                         gradient_average: bool = True,
                         gradient_predivide_factor: float = 1.0,
                         allreduce_always_fp32: bool = False):
    """``allreduce_fallback`` (`apex/parallel/distributed.py:491-510`):
    concatenate all floating gradients into one flat buffer *per dtype*
    (the reference's type-bucketed ``flat_dist_call``), one ``psum`` per
    buffer, then split back. Same arithmetic knobs as
    :func:`sync_gradients`."""
    world = jax.lax.axis_size(axis_name)
    leaves, treedef = jax.tree_util.tree_flatten(grads)

    groups = {}
    for i, leaf in enumerate(leaves):
        if _is_float(leaf):
            groups.setdefault(jnp.asarray(leaf).dtype, []).append(i)

    out = list(leaves)
    for dtype, idxs in groups.items():
        flat = jnp.concatenate(
            [jnp.ravel(leaves[i]) for i in idxs])
        if allreduce_always_fp32:
            flat = flat.astype(jnp.float32)
        if gradient_predivide_factor != 1.0:
            flat = flat / gradient_predivide_factor
        flat = jax.lax.psum(flat, axis_name)
        if gradient_average:
            post = world / gradient_predivide_factor
            if post != 1.0:
                flat = flat / post
        flat = flat.astype(dtype)
        off = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = flat[off:off + n].reshape(leaves[i].shape)
            off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def dynamics_probe(local_grads, synced_grads,
                   axis_name: str = DATA_AXIS):
    """The training-dynamics observatory's per-step gradient scalars
    (:class:`apex_tpu.monitor.dynamics.DynamicsProbe`): call between
    ``sync`` and ``apply_gradients`` with the replica-LOCAL gradient
    tree and the synced (averaged) tree — both already in hand there —
    and feed the result to the ``Amp.step(dynamics=…)`` hook or
    :func:`~apex_tpu.monitor.dynamics.dynamics_observe` directly.

    Wire cost is two scalar-class collectives riding the existing sync
    dispatch, each under its own registered scope
    (:mod:`apex_tpu.parallel.registry` — APX102/APX202 and the per-axis
    byte split resolve them):

    - ``ddp/dynamics_gns``: ONE scalar psum of the per-replica squared
      grad norm → the mean ``|G_local|²`` the gradient-noise-scale
      estimator pairs against the pooled ``|G_big|²`` (computed
      locally — the synced tree is replicated after the sync);
    - ``ddp/dynamics_geom``: one all-gather of the per-replica
      ``[|g_i|², g_i·g̅]`` scalar pair → the cosine spectrum and the
      Adasum projection coefficients (arXiv 2006.02924), ``2·world``
      floats on the wire.

    ``synced_grads`` must be the *averaged* sync output (the
    ``gradient_average=True`` default): the GNS algebra reads it as the
    pooled-mean gradient. Non-floating leaves are ignored, mirroring
    ``sync``. Works under any mapping that binds ``axis_name``
    (shard_map/pmap); the probe itself adds no host ops.
    """
    from apex_tpu.monitor.dynamics import DynamicsProbe
    from apex_tpu.trace.spans import span as _span

    def _sq(tree):
        acc = jnp.float32(0)
        for leaf in jax.tree_util.tree_leaves(tree):
            if _is_float(leaf):
                acc = acc + jnp.sum(
                    jnp.square(jnp.asarray(leaf).astype(jnp.float32)))
        return acc

    local_sq = _sq(local_grads)
    pooled_sq = _sq(synced_grads)
    dot = jnp.float32(0)
    for g, s in zip(jax.tree_util.tree_leaves(local_grads),
                    jax.tree_util.tree_leaves(synced_grads)):
        if _is_float(g) and _is_float(s):
            dot = dot + jnp.sum(
                jnp.asarray(g).astype(jnp.float32)
                * jnp.asarray(s).astype(jnp.float32))
    world = jax.lax.axis_size(axis_name)
    with _span("ddp/dynamics_gns", kind="collective"):
        local_sq_mean = jax.lax.pmean(local_sq, axis_name)
    with _span("ddp/dynamics_geom", kind="collective"):
        pairs = jax.lax.all_gather(jnp.stack([local_sq, dot]),
                                   axis_name)
    return DynamicsProbe(local_sq_mean=local_sq_mean,
                         pooled_sq=pooled_sq,
                         local_sqs=pairs[:, 0], dots=pairs[:, 1],
                         world=jnp.float32(world))


class Reducer:
    """Manual-trigger parameter/gradient averaging
    (`apex/parallel/distributed.py:89-126`): construction-time broadcast is
    replaced by ``replicate`` (params placed with a replicated sharding are
    identical on all devices by construction); ``reduce`` averages a pytree
    across the data axis whenever the user calls it."""

    def __init__(self, axis_name: str = DATA_AXIS):
        self.axis_name = axis_name

    def reduce(self, tree):
        world = jax.lax.axis_size(self.axis_name)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, self.axis_name) / world
            if _is_float(x) else x, tree)


def replica_broadcast(tree, axis_name=DATA_AXIS, *, source=0):
    """Bit-exact re-broadcast of a pytree from one replica of
    ``axis_name`` to all of them (inside shard_map) — the in-place
    repair collective of the silent-divergence defense
    (:mod:`apex_tpu.guard.integrity`).

    Every replica receives the ``source`` replica's **exact bits**: the
    broadcast is a ``psum`` of the where-selected *bit pattern*
    (integer addition against zeros is exact), never of the float
    values — a float psum would already lose ``-0.0`` signs, and
    bit-exactness is the whole point (the repaired replica must equal
    the majority bitwise, or the fingerprint re-verification fails).
    ``source`` may be a traced scalar (the quorum vote's choice is a
    runtime value). Call under a registered collective scope
    (``guard/integrity_repair``) so apexlint APX102/APX202 stay clean.
    """
    me = jax.lax.axis_index(axis_name)
    src = jnp.asarray(source, me.dtype)

    def _one(x):
        from apex_tpu.utils import uint_view_dtype
        x = jnp.asarray(x)
        if jnp.issubdtype(x.dtype, jnp.floating):
            bits = jax.lax.bitcast_convert_type(
                x, uint_view_dtype(x.dtype))
            sel = jnp.where(me == src, bits, jnp.zeros_like(bits))
            return jax.lax.bitcast_convert_type(
                jax.lax.psum(sel, axis_name), x.dtype)
        if x.dtype == jnp.bool_:
            sel = jnp.where(me == src, x.astype(jnp.int32), 0)
            return jax.lax.psum(sel, axis_name) != 0
        if jnp.issubdtype(x.dtype, jnp.integer):
            sel = jnp.where(me == src, x, jnp.zeros_like(x))
            return jax.lax.psum(sel, axis_name)
        # passing an uncovered dtype through unrepaired would silently
        # leave the divergence in place — refuse loudly (mirrors
        # guard.integrity's fold, which refuses to fingerprint it)
        raise TypeError(
            f"replica_broadcast cannot re-broadcast dtype {x.dtype} "
            f"bit-exactly — exclude the leaf from the repaired "
            f"subtree explicitly")

    return jax.tree_util.tree_map(_one, tree)


def replicate(tree, mesh: Mesh):
    """Place a pytree replicated on every device of ``mesh`` — the
    construction-time rank-0 broadcast of the reference DDP
    (`apex/parallel/distributed.py:253`), done by sharding instead of
    communication."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


class DistributedDataParallel:
    """Data-parallel train-step transform.

    ``ddp = DistributedDataParallel(mesh)`` then ``ddp.wrap(step)`` turns a
    per-device step ``(state, batch) -> (state, metrics)`` whose gradients
    are produced locally into a jitted SPMD program: the batch is split over
    the data axis, the step runs per shard, and every gradient the step
    syncs through ``ddp.sync_gradients`` (or the wrapper's automatic sync if
    the step returns raw grads) is all-reduced.

    The constructor flags mirror `apex/parallel/distributed.py:129-191`.
    """

    def __init__(self, mesh: Mesh, axis_name: str = DATA_AXIS, *,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 allreduce_always_fp32: bool = False,
                 delay_allreduce: bool = False,
                 message_size: Optional[int] = None,
                 grad_dtype=None,
                 bucket_allreduce: bool = False,
                 compress: Optional[str] = None,
                 compress_block: Optional[int] = None,
                 comm_plan=None):
        from apex_tpu.parallel import comm as _comm
        if comm_plan is not None:
            # a hierarchy.CommPlan IS the compression + topology spec:
            # its axes replace axis_name, its per-hop dtypes replace
            # compress, and delay_allreduce's one terminal flat reduce
            # is the exact shape it exists to remove
            if compress is not None or allreduce_always_fp32 or \
                    delay_allreduce or compress_block is not None:
                raise ValueError(
                    "comm_plan fixes the per-hop wire dtypes, the "
                    "quantization block and the topology; it does not "
                    "compose with compress, compress_block, "
                    "allreduce_always_fp32 or delay_allreduce (set "
                    "compress_block via plan_comm)")
            for ax in comm_plan.axis_names:
                if ax not in mesh.axis_names:
                    raise ValueError(
                        f"comm_plan axis {ax!r} not in mesh "
                        f"{mesh.axis_names} — build the mesh with "
                        "hierarchical_data_mesh (or matching axis "
                        "names) for a hierarchical plan")
            for hop in comm_plan.hops:
                if mesh.shape[hop.axis] != hop.size:
                    raise ValueError(
                        f"comm_plan axis {hop.axis!r} has size "
                        f"{hop.size} but the mesh has "
                        f"{mesh.shape[hop.axis]}")
            axis_name = (comm_plan.axis_names[0]
                         if len(comm_plan.axis_names) == 1
                         else tuple(comm_plan.axis_names))
        elif axis_name not in mesh.axis_names:
            raise ValueError(f"axis {axis_name!r} not in mesh "
                             f"{mesh.axis_names}")
        if compress not in _comm.COMPRESS_MODES:
            raise ValueError(f"compress must be one of "
                             f"{_comm.COMPRESS_MODES}, got {compress!r}")
        if compress is not None and allreduce_always_fp32:
            raise ValueError("compress fixes the wire dtype; it does not "
                             "compose with allreduce_always_fp32")
        if bucket_allreduce and delay_allreduce:
            raise ValueError("bucket_allreduce (overlapped per-bucket "
                             "reduction) and delay_allreduce (one "
                             "terminal flat reduce) are opposite modes")
        self.mesh = mesh
        self.axis_name = axis_name
        #: None | hierarchy.CommPlan — the topology-aware hierarchical
        #: schedule (int8 ICI reduce-scatter / bf16-or-int8 DCN reduce /
        #: ICI all-gather, planner-chosen per hop; see
        #: apex_tpu.parallel.hierarchy). Strictly opt-in: comm_plan=None
        #: leaves every existing path untouched.
        self.comm_plan = comm_plan
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.delay_allreduce = delay_allreduce
        self.message_size = message_size
        #: explicit message_size-bounded buckets in reverse-parameter
        #: order, one chained psum each — the apex ``allreduce_bucket``
        #: overlap structure (see apex_tpu.parallel.comm)
        self.bucket_allreduce = bucket_allreduce
        #: None | "bf16" | "int8" — compressed collectives with optional
        #: error feedback (pass ``residual=`` to :meth:`sync`)
        self.compress = compress
        self.compress_block = (compress_block if compress_block
                               else _comm.DEFAULT_COMPRESS_BLOCK)
        #: dtype the gradients ARRIVE in — used only to size the
        #: message_size → combine-threshold conversion (bf16 grads halve
        #: the byte threshold). It does NOT cast the reduction: grads
        #: reduce in their incoming dtype (upcast via
        #: allreduce_always_fp32 if wanted). Defaults to fp32 sizing
        #: (the reference counts fp32 elements,
        #: `apex/parallel/distributed.py:165`); allreduce_always_fp32
        #: forces fp32 sizing regardless.
        self.grad_dtype = grad_dtype
        self._sync_enabled = True

    @property
    def world_size(self) -> int:
        if isinstance(self.axis_name, tuple):
            n = 1
            for a in self.axis_name:
                n *= self.mesh.shape[a]
            return n
        return self.mesh.shape[self.axis_name]

    # -- in-step API ---------------------------------------------------------

    def sync(self, grads, residual=None):
        """Sync a gradient pytree (call inside the wrapped step). Honors
        ``no_sync`` — the `_disable_allreduce` flag
        (`apex/parallel/distributed.py:566-570`) — and ``delay_allreduce``
        (one flat fused reduce per dtype, the `allreduce_fallback` path).
        With ``bucket_allreduce`` or ``compress`` set, the sync runs
        through :func:`comm.bucketed_all_reduce` (per-bucket chained
        psums / compressed collectives).

        ``residual`` enables error feedback for the compressed modes:
        pass the previous step's residual (seed with
        :meth:`init_residual`) and the return value becomes
        ``(synced_grads, new_residual)`` — thread it through your step
        state with a per-device sharding (docs/parallel.md). Without
        ``residual`` the return value stays a bare pytree.

        Runs under a ``kind="collective"`` trace span so the psums are
        scoped ``ddp/sync_gradients`` in xplane traces and HLO dumps
        (per-bucket sub-spans ``bucket00``… nest inside it) — that
        attribution is what survives into the compiled program.
        The span itself executes at trace time (this code runs inside
        the user's jitted step), so *runtime* in-flight-collective
        forensics come from host-side collective spans around the
        blocking point, e.g. ``with trace.span("allreduce-wait",
        kind="collective"): jax.block_until_ready(grads)`` — see
        docs/tracing.md."""
        if not self._sync_enabled:
            return grads if residual is None else (grads, residual)
        from apex_tpu.parallel import comm as _comm
        from apex_tpu.trace.spans import span as _span
        if self.comm_plan is not None:
            from apex_tpu.parallel import hierarchy as _hier
            msg = self.message_size if self.message_size else (
                _comm.DEFAULT_MESSAGE_SIZE if self.bucket_allreduce
                else None)
            with _span("ddp/sync_gradients", kind="collective"):
                if self.comm_plan.is_hierarchical:
                    return _hier.hierarchical_sync(
                        grads, self.comm_plan, message_size=msg,
                        gradient_average=self.gradient_average,
                        gradient_predivide_factor=self
                        .gradient_predivide_factor,
                        residual=residual)
                # a flat (single-slice) plan is the planner-chosen
                # compress mode over one axis — the existing machinery
                return _comm.bucketed_all_reduce(
                    grads, self.axis_name, message_size=msg,
                    gradient_average=self.gradient_average,
                    gradient_predivide_factor=self
                    .gradient_predivide_factor,
                    compress=self.comm_plan.hops[0].dtype,
                    residual=residual,
                    compress_block=self.comm_plan.compress_block)
        if self.bucket_allreduce or self.compress is not None:
            # compress without bucketing = one bucket per dtype
            msg = self.message_size if self.message_size else (
                _comm.DEFAULT_MESSAGE_SIZE if self.bucket_allreduce
                else None)
            with _span("ddp/sync_gradients", kind="collective"):
                return _comm.bucketed_all_reduce(
                    grads, self.axis_name, message_size=msg,
                    gradient_average=self.gradient_average,
                    gradient_predivide_factor=self
                    .gradient_predivide_factor,
                    allreduce_always_fp32=self.allreduce_always_fp32,
                    compress=self.compress, residual=residual,
                    compress_block=self.compress_block)
        fn = flat_tree_all_reduce if self.delay_allreduce else \
            sync_gradients
        with _span("ddp/sync_gradients", kind="collective"):
            synced = fn(
                grads, self.axis_name,
                gradient_average=self.gradient_average,
                gradient_predivide_factor=self.gradient_predivide_factor,
                allreduce_always_fp32=self.allreduce_always_fp32)
        # exact modes have no compression error: the residual passes
        # through unchanged so callers can keep one code shape
        return synced if residual is None else (synced, residual)

    def init_residual(self, grads):
        """Zeroed error-feedback residual matching a gradient pytree —
        see :func:`apex_tpu.parallel.comm.init_residual`."""
        from apex_tpu.parallel import comm as _comm
        return _comm.init_residual(grads)

    def pmean(self, x):
        """Cross-replica mean over this DDP's topology (use for the
        logged loss). Matters with a hierarchical ``comm_plan``: a
        ``jax.lax.pmean`` over the axis *tuple* lowers to one flat
        whole-mesh all-reduce — the DCN-crossing shape APX203 flags —
        while this emits one psum per axis (within-slice, then
        one-member-per-slice across). Call it inside a registered
        collective span (``ddp/loss_pmean``)."""
        if self.comm_plan is not None and self.comm_plan.is_hierarchical:
            from apex_tpu.parallel import hierarchy as _hier
            return _hier.hierarchical_pmean(x, self.comm_plan)
        return jax.lax.pmean(x, self.axis_name)

    def no_sync(self):
        """Context manager: steps wrapped while active skip gradient
        all-reduce (gradient accumulation across microbatches)."""
        ddp = self

        class _NoSync:
            def __enter__(self):
                self._prev = ddp._sync_enabled
                ddp._sync_enabled = False

            def __exit__(self, *exc):
                ddp._sync_enabled = self._prev

        return _NoSync()

    # -- step transform ------------------------------------------------------

    def wrap(self, step_fn: Callable, *,
             state_specs=P(), batch_specs=None, out_specs=None,
             donate_state: bool = True) -> Callable:
        """shard_map ``step_fn(state, batch) -> (state, aux)`` over the mesh.

        ``state`` is replicated (every device holds identical params, like
        DDP's broadcast invariant), ``batch`` is split on its leading dim.
        ``step_fn`` must call ``self.sync`` on its gradients (or use
        ``wrap_grad_fn``). Donation keeps the replicated state update
        in-place.
        """
        batch_specs = batch_specs if batch_specs is not None else \
            P(self.axis_name)
        out_specs = out_specs if out_specs is not None else \
            (state_specs, P())

        jit_kwargs = {}
        if donate_state:
            jit_kwargs["donate_argnums"] = (0,)

        # ``self._sync_enabled`` is read inside step_fn at *trace* time, so
        # a single compiled program would bake in whichever value was active
        # at the first call — breaking no_sync for already-compiled steps.
        # Build one program per flag value, each from a distinct closure
        # (distinct trace caches) that pins the flag while tracing.
        def _build(sync_on: bool):
            def pinned(*args, **kwargs):
                prev = self._sync_enabled
                self._sync_enabled = sync_on
                try:
                    return step_fn(*args, **kwargs)
                finally:
                    self._sync_enabled = prev
            mapped = jax.shard_map(
                pinned, mesh=self.mesh,
                in_specs=(state_specs, batch_specs),
                out_specs=out_specs,
                check_vma=False)
            opts = self._compiler_options()
            if opts:
                return jax.jit(mapped, compiler_options=opts,
                               **jit_kwargs)
            return jax.jit(mapped, **jit_kwargs)

        programs = {}

        @functools.wraps(step_fn)
        def dispatch(*args, **kwargs):
            key = self._sync_enabled
            if key not in programs:
                programs[key] = _build(key)
            return programs[key](*args, **kwargs)

        return dispatch

    def _compiler_options(self) -> Optional[dict]:
        """``message_size`` (elements; the reference default is 1e7 ≈
        40 MB of fp32, `apex/parallel/distributed.py:165`) → the XLA
        collective-combiner threshold, scaled by the reduction dtype's
        itemsize. ``None`` lets XLA choose.

        The DebugOptions field is shared across backends despite the
        gpu prefix: XLA:CPU and libtpu 0.0.34 both parse it as a jit
        compiler option (a malformed value is INVALID_ARGUMENT on
        either — tests/test_parallel.py, and a v5e run in PR 21), so it
        is passed unconditionally and a backend that refuses it fails
        the compile where the option was set. Whether a backend's
        combiner then honors the threshold is its own business — treat
        it as a hint, exactly like the reference's bucketing
        heuristic."""
        if self.message_size is None:
            return None
        import jax.numpy as jnp
        dt = jnp.float32 if (self.allreduce_always_fp32 or
                             self.grad_dtype is None) else self.grad_dtype
        itemsize = jnp.dtype(dt).itemsize
        return {"xla_gpu_all_reduce_combine_threshold_bytes":
                str(int(self.message_size) * itemsize)}

    # -- telemetry -----------------------------------------------------------

    def collective_bytes(self, step_fn: Callable, *args, **kwargs) -> dict:
        """Static per-step collective traffic of a (wrapped) step, by
        opcode, from the compiled HLO — ``{"all-reduce": bytes, ...,
        "total": bytes}``.

        The accounting the reference could only approximate from its own
        bucket bookkeeping (`apex/parallel/distributed.py:425-475`); here
        the compiled program is the ground truth. Compile-time constant:
        feed it to ``MetricsLogger(collective_bytes_per_step=...)`` (or
        let ``MetricsLogger.attach`` derive it) so every logged record
        carries the step's communication volume.
        """
        from apex_tpu.monitor.collectives import collective_bytes as _cb
        return _cb(step_fn, *args, **kwargs)

    def memory_report(self, step_fn: Callable, *args,
                      batch_size: Optional[int] = None, **kwargs):
        """Static per-device HBM footprint of a (wrapped) step — a
        :class:`apex_tpu.prof.MemoryReport` whose class table attributes
        every byte to params / optimizer state / activations / **comm**
        (the ``bucket_plan`` buffers and compressed-collective wire
        staging show up under ``comm``, scoped ``ddp/sync_gradients``).
        AOT-only like :meth:`collective_bytes`: one compile, never a
        dispatch. ``batch_size`` is the PER-DEVICE batch dimension (the
        post-shard_map leading dim, i.e. global batch / world_size) and
        enables the what-if batch forecast. See docs/memory.md."""
        from apex_tpu.prof.memory import memory_report as _mr
        if batch_size is None:
            # infer: the common leading dim of the batch-side args
            # (everything after state), divided over the data axis the
            # wrapper splits it on. Ambiguity (leaves disagreeing on a
            # world-divisible leading dim — e.g. a batch_stats vector
            # riding along) leaves batch_size None: no forecast beats a
            # silently wrong one.
            dims = {l.shape[0] for a in args[1:]
                    for l in jax.tree_util.tree_leaves(a)
                    if getattr(l, "shape", ())}
            cands = {d for d in dims if d % self.world_size == 0}
            if len(cands) == 1:
                batch_size = cands.pop() // self.world_size
        return _mr(step_fn, *args, batch_size=batch_size, **kwargs)

    def wrap_grad_fn(self, grad_fn: Callable) -> Callable:
        """Wrap ``grad_fn(*a, **k) -> (value, grads)`` so grads come back
        synced — the "model wrapper" usage of the reference where backward
        itself triggers the reduction."""
        @functools.wraps(grad_fn)
        def wrapped(*args, **kwargs):
            value, grads = grad_fn(*args, **kwargs)
            return value, self.sync(grads)
        return wrapped
