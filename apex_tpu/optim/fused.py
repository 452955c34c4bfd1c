"""Fused single-process optimizers over the flat arena.

TPU-native rebuild of ``apex.optimizers`` (SURVEY.md §2.4): the reference
partitions params by dtype into tensor lists and makes one
``multi_tensor_applier`` launch per group per step
(`apex/optimizers/fused_adam.py:119-199`). Here params/grads/state live in
per-dtype arena buffers and one Pallas kernel per partition updates the
whole model (apex_tpu.ops.optim_kernels). Python-side per-param list
building — a hot loop the reference pays every step — does not exist:
flatten/unflatten trace once under jit and fuse into the step.

Two protocols in one object:

- fused:  ``new_params, new_state = opt.step(grads, state, params)``
          (the fast path; apex's ``optimizer.step()``)
- optax:  ``updates, new_state = opt.update(grads, state, params)``
          (GradientTransformation-compatible, costs one extra subtract)

``apex_tpu.amp.Amp`` auto-detects the fused protocol.

Every pass over the parameters is traced under a ``jax.named_scope`` of
its own, ``optim/<optimizer>/<phase>`` (``optim/lamb/norms``,
``optim/lamb/update``, ``optim/sgd/update``, ``optim/<optimizer>/arena``
for the flatten and unflatten of the arena strategy): the device trace
carries it (``prof.xplane.own_scope``), beneath amp's ``amp/update``. A
fusion carries the scope of one of its ops, so a pass reads under the
phase XLA named it after: LAMB's first sweep (moments, update direction
and both norms in one fusion) under ``norms``, and an SGD update that XLA
fuses into the weight-gradient fusion under the backward.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import arena
from apex_tpu.ops import optim_kernels as K
from apex_tpu.ops import multi_tensor as MT

Scalar = Union[float, jax.Array, Callable[[jax.Array], jax.Array]]


class _LeafOut:
    """Per-leaf multi-output bundle for the tree strategy — deliberately
    NOT a pytree container (a plain tuple would collide with tuple nodes
    in user param trees)."""
    __slots__ = ("vals",)

    def __init__(self, *vals):
        self.vals = vals


def _bias_corrections(count, beta1, beta2, enabled, sqrt2=False):
    if not enabled:
        return jnp.float32(1.0), jnp.float32(1.0)
    step = jnp.asarray(count, jnp.float32)
    bc1 = 1.0 - jnp.power(jnp.float32(beta1), step)
    bc2 = 1.0 - jnp.power(jnp.float32(beta2), step)
    return bc1, (jnp.sqrt(bc2) if sqrt2 else bc2)


class FusedOptState(NamedTuple):
    """Optimizer state: step count + named flat slot buffers per partition.

    ``slots["m"]["float32"]`` is the momentum buffer covering every fp32
    parameter. All slots are fp32 regardless of param dtype.
    """
    count: jax.Array
    slots: Dict[str, Dict[str, jax.Array]]


class FusedOptimizer:
    """Base: arena planning, flatten/unflatten, dual protocol.

    ``strategy`` selects how the fused update is laid out:

    - ``"arena"``: flatten params/grads into per-dtype flat buffers and
      run one Pallas kernel per partition — the direct
      `multi_tensor_apply` rebuild.
    - ``"tree"``: per-tensor jnp updates (identical f32 math) that XLA
      fuses into per-tensor roofline passes. On TPU there is no kernel
      -launch overhead to amortize, and the arena's flatten/unflatten
      is a genuine relayout of every byte (measured ~28 ms/step on
      BERT-Large 334M: the flat T(1024) buffer vs the params' T(8,128)
      tiling), so for large models the tree strategy is strictly
      faster; PERF.md round 2 measured the two tying already at
      ResNet-50 scale.
    - ``"auto"`` (default): tree for models over ~8M params, arena
      below (where the arena's single-kernel dispatch is measured
      equivalent and the L1 bitwise harness pins its layout).
    """

    #: names of fp32 state buffers allocated per partition
    slot_names = ()

    #: "auto" switches to the tree strategy at this many parameters
    TREE_THRESHOLD = 8_000_000

    #: the optimizer's word in ``optim/<scope>/<phase>``
    scope = ""

    def _phase(self, phase: str):
        """The named scope of one pass over the parameters."""
        return jax.named_scope(f"optim/{self.scope}/{phase}")

    def __init__(self, lr: Scalar, strategy: str = "auto"):
        if strategy not in ("auto", "tree", "arena"):
            raise ValueError(f"unknown strategy {strategy!r}")
        self.lr = lr
        self.strategy = strategy

    def _use_tree(self, params) -> bool:
        if self.strategy != "auto":
            return self.strategy == "tree"
        n = sum(int(np.prod(l.shape)) if l.shape else 1
                for l in jax.tree_util.tree_leaves(params))
        return n >= self.TREE_THRESHOLD

    @staticmethod
    def _split(out_tree, n):
        """tree of per-leaf ``_LeafOut`` bundles -> n trees.

        The bundle is an unregistered class (NOT a tuple): structural
        tuples inside a user's params pytree would be indistinguishable
        from per-leaf outputs and silently corrupt the split."""
        is_o = lambda x: isinstance(x, _LeafOut)
        return tuple(
            jax.tree_util.tree_map(lambda o, i=i: o.vals[i], out_tree,
                                   is_leaf=is_o)
            for i in range(n))

    # -- protocol ------------------------------------------------------------

    def init(self, params) -> FusedOptState:
        if self._use_tree(params):
            zeros = lambda t: jax.tree_util.tree_map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), t)
            return FusedOptState(
                count=jnp.int32(0),
                slots={name: zeros(params) for name in self.slot_names})
        spec = arena.plan(params)
        return FusedOptState(
            count=jnp.int32(0),
            slots={name: arena.zeros(spec, dtype=jnp.float32)
                   for name in self.slot_names})

    def step(self, grads, state: FusedOptState, params):
        """Fused update: returns (new_params, new_state)."""
        if self._use_tree(params):
            return self._tree_step(grads, state, params)
        spec = arena.plan(params)
        with self._phase("arena"):
            p_bufs = arena.flatten(params, spec)
            g_bufs = arena.flatten(grads, spec, cast=jnp.float32)
        count = state.count + 1
        lr = self.lr(count) if callable(self.lr) else self.lr

        ctx = self._step_context(spec, g_bufs)
        new_p, new_slots = {}, {name: {} for name in self.slot_names}
        for part in spec.partitions:
            dt = part.dtype
            slots = {name: state.slots[name][dt] for name in self.slot_names}
            p_out, s_out = self._partition_step(
                spec, dt, p_bufs[dt], g_bufs[dt], slots, count, lr, ctx=ctx)
            new_p[dt] = p_out
            for name in self.slot_names:
                new_slots[name][dt] = s_out[name]
        with self._phase("arena"):
            new_params = arena.unflatten(new_p, spec)
        return new_params, FusedOptState(count=count, slots=new_slots)

    def update(self, grads, state: FusedOptState, params):
        """optax GradientTransformation protocol (updates = new - old)."""
        new_params, new_state = self.step(grads, state, params)
        updates = jax.tree_util.tree_map(
            lambda n, o: (n.astype(jnp.float32)
                          - o.astype(jnp.float32)).astype(o.dtype),
            new_params, params)
        return updates, new_state

    # -- subclass hooks ------------------------------------------------------

    def _step_context(self, spec, g_bufs):
        """Once-per-step work over all partitions (e.g. global grad norms)."""
        return None

    def _partition_step(self, spec, dt, p, g, slots, count, lr, ctx):
        raise NotImplementedError

    def _tree_step(self, grads, state, params):
        raise NotImplementedError(
            f"{type(self).__name__} has no tree strategy; construct with "
            f"strategy='arena'")

    def _resolve_lr(self, count):
        return self.lr(count) if callable(self.lr) else self.lr


class FusedAdam(FusedOptimizer):
    """Adam/AdamW over the arena (`apex/optimizers/fused_adam.py:34-202`).

    ``adam_w_mode=True`` decouples weight decay (AdamW), matching the
    reference default.
    """

    slot_names = ("m", "v")
    scope = "adam"

    def __init__(self, lr: Scalar = 1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, adam_w_mode=True, bias_correction=True,
                 strategy: str = "auto"):
        super().__init__(lr, strategy)
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.bias_correction = bias_correction

    def _partition_step(self, spec, dt, p, g, slots, count, lr, ctx):
        with self._phase("update"):
            p2, m2, v2 = K.adam_update(
                p, g, slots["m"], slots["v"], lr=lr, beta1=self.beta1,
                beta2=self.beta2, eps=self.eps,
                weight_decay=self.weight_decay, step=count,
                adam_w_mode=self.adam_w_mode,
                bias_correction=self.bias_correction)
        return p2, {"m": m2, "v": v2}

    def _tree_step(self, grads, state, params):
        count = state.count + 1
        lr = self._resolve_lr(count)
        bc1, bc2 = _bias_corrections(count, self.beta1, self.beta2,
                                     self.bias_correction)
        wd, b1, b2, eps = (self.weight_decay, self.beta1, self.beta2,
                           self.eps)

        def leaf(p, g, m, v):
            p32 = p.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            if not self.adam_w_mode:
                g32 = g32 + wd * p32
            m2 = b1 * m + (1.0 - b1) * g32
            v2 = b2 * v + (1.0 - b2) * g32 * g32
            upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
            if self.adam_w_mode:
                upd = upd + wd * p32
            return _LeafOut((p32 - lr * upd).astype(p.dtype), m2, v2)

        with self._phase("update"):
            out = jax.tree_util.tree_map(leaf, params, grads,
                                         state.slots["m"], state.slots["v"])
        p2, m2, v2 = self._split(out, 3)
        return p2, FusedOptState(count=count, slots={"m": m2, "v": v2})


class FusedSGD(FusedOptimizer):
    """SGD with momentum (`apex/optimizers/fused_sgd.py:6-217`)."""

    slot_names = ("m",)
    scope = "sgd"

    def __init__(self, lr: Scalar = 1e-3, momentum=0.0, dampening=0.0,
                 weight_decay=0.0, nesterov=False, wd_after_momentum=False,
                 strategy: str = "auto"):
        super().__init__(lr, strategy)
        if nesterov and (momentum <= 0 or dampening != 0):
            raise ValueError(
                "Nesterov momentum requires a momentum and zero dampening")
        self.momentum = momentum
        self.dampening = dampening
        self.weight_decay = weight_decay
        self.nesterov = nesterov
        self.wd_after_momentum = wd_after_momentum

    def _partition_step(self, spec, dt, p, g, slots, count, lr, ctx):
        first = (count == 1) if self.momentum > 0 else False
        with self._phase("update"):
            p2, m2 = K.sgd_update(
                p, g, slots["m"], lr=lr, momentum=self.momentum,
                dampening=self.dampening, weight_decay=self.weight_decay,
                nesterov=self.nesterov, first_run=first,
                wd_after_momentum=self.wd_after_momentum)
        return p2, {"m": m2}

    def _tree_step(self, grads, state, params):
        count = state.count + 1
        lr = self._resolve_lr(count)
        first = ((count == 1) if self.momentum > 0
                 else jnp.bool_(False))
        mom, damp, wd = self.momentum, self.dampening, self.weight_decay

        def leaf(p, g, m):
            p32 = p.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            if not self.wd_after_momentum:
                g32 = g32 + wd * p32
            m2 = jnp.where(first, g32, mom * m + (1.0 - damp) * g32)
            upd = (g32 + mom * m2) if self.nesterov else m2
            if self.wd_after_momentum:
                upd = upd + wd * p32
            return _LeafOut((p32 - lr * upd).astype(p.dtype), m2)

        with self._phase("update"):
            out = jax.tree_util.tree_map(leaf, params, grads,
                                         state.slots["m"])
        p2, m2 = self._split(out, 2)
        return p2, FusedOptState(count=count, slots={"m": m2})


class FusedAdagrad(FusedOptimizer):
    """Adagrad (`apex/optimizers/fused_adagrad.py:5-95`)."""

    slot_names = ("h",)
    scope = "adagrad"

    def __init__(self, lr: Scalar = 1e-2, eps=1e-10, weight_decay=0.0,
                 adagrad_w_mode=False, strategy: str = "auto"):
        super().__init__(lr, strategy)
        self.eps = eps
        self.weight_decay = weight_decay
        self.adagrad_w_mode = adagrad_w_mode

    def _partition_step(self, spec, dt, p, g, slots, count, lr, ctx):
        with self._phase("update"):
            p2, h2 = K.adagrad_update(
                p, g, slots["h"], lr=lr, eps=self.eps,
                weight_decay=self.weight_decay,
                adagrad_w_mode=self.adagrad_w_mode)
        return p2, {"h": h2}

    def _tree_step(self, grads, state, params):
        count = state.count + 1
        lr = self._resolve_lr(count)
        wd, eps = self.weight_decay, self.eps

        def leaf(p, g, h):
            p32 = p.astype(jnp.float32)
            g32 = g.astype(jnp.float32)
            if not self.adagrad_w_mode:
                g32 = g32 + wd * p32
            h2 = h + g32 * g32
            upd = g32 / (jnp.sqrt(h2) + eps)
            if self.adagrad_w_mode:
                upd = upd + wd * p32
            return _LeafOut((p32 - lr * upd).astype(p.dtype), h2)

        with self._phase("update"):
            out = jax.tree_util.tree_map(leaf, params, grads,
                                         state.slots["h"])
        p2, h2 = self._split(out, 2)
        return p2, FusedOptState(count=count, slots={"h": h2})


def lamb_trust_ratios(part, p, u, *, use_nvlamb, weight_decay):
    """Per-position LAMB trust ratios over one arena partition.

    Static arena ranges → per-tensor norms as fused slice-reduces and
    the trust-ratio spread as concatenated broadcasts; the traced
    segment_ids alternative lowers to scatter/gather over the whole
    arena, which TPU serializes (~500 ms on a BERT-Large buffer).
    NVLAMB applies the ratio even where wd==0 — with a single group,
    plain LAMB and NVLAMB agree unless wd==0 globally. Shared by the
    modern and legacy-contrib FusedLAMB surfaces.
    """
    p_norms = MT.per_tensor_l2norm_ranges(p, part.offsets, part.sizes)
    u_norms = MT.per_tensor_l2norm_ranges(u, part.offsets, part.sizes)
    ratio = jnp.where((p_norms > 0) & (u_norms > 0),
                      p_norms / u_norms, 1.0)
    if not use_nvlamb and weight_decay == 0.0:
        ratio = jnp.ones_like(ratio)
    return MT.spread_per_tensor(ratio, part.offsets, part.padded, len(p))


class FusedLAMB(FusedOptimizer):
    """LAMB (`apex/optimizers/fused_lamb.py:4-215`): global grad-norm clip,
    Adam-style direction, per-tensor trust ratio.

    Two Pallas stages with the per-tensor norms computed between them over
    the arena via segment reduction — the same split as the reference's
    `multi_tensor_lamb` stage pair.
    """

    slot_names = ("m", "v")
    scope = "lamb"

    def __init__(self, lr: Scalar = 1e-3, betas=(0.9, 0.999), eps=1e-6,
                 weight_decay=0.01, adam_w_mode=True, bias_correction=True,
                 max_grad_norm=1.0, use_nvlamb=False,
                 strategy: str = "auto"):
        super().__init__(lr, strategy)
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.adam_w_mode = adam_w_mode
        self.bias_correction = bias_correction
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb

    def _global_clip_scale(self, g_all):
        """clip factor from the global grad norm over *all* partitions
        (`fused_lamb.py:120-136`)."""
        if not self.max_grad_norm:
            return jnp.float32(1.0)
        with self._phase("norms"):
            sq = sum(jnp.square(MT.multi_tensor_l2norm(g))
                     for g in g_all.values())
            gnorm = jnp.sqrt(sq)
            return jnp.where(
                gnorm > self.max_grad_norm, self.max_grad_norm / gnorm,
                1.0).astype(jnp.float32)

    def _step_context(self, spec, g_bufs):
        # global grad norm computed ONCE per step over all partitions
        return self._global_clip_scale(g_bufs)

    def _partition_step(self, spec, dt, p, g, slots, count, lr, ctx):
        clip = ctx
        with self._phase("update"):
            u, m2, v2 = K.lamb_stage1(
                p, g, slots["m"], slots["v"], beta1=self.beta1,
                beta2=self.beta2, eps=self.eps,
                weight_decay=self.weight_decay, step=count,
                bias_correction=self.bias_correction,
                adam_w_mode=self.adam_w_mode, clip_scale=clip)

        part = spec.partition(dt)
        with self._phase("norms"):
            ratio_pos = lamb_trust_ratios(part, p, u,
                                          use_nvlamb=self.use_nvlamb,
                                          weight_decay=self.weight_decay)
        with self._phase("update"):
            p2 = K.lamb_stage2(p, u, ratio_pos, lr=lr)
        return p2, {"m": m2, "v": v2}

    def _tree_step(self, grads, state, params):
        count = state.count + 1
        lr = self._resolve_lr(count)
        bc1, bc2 = _bias_corrections(count, self.beta1, self.beta2,
                                     self.bias_correction)
        b1, b2, eps, wd = (self.beta1, self.beta2, self.eps,
                           self.weight_decay)

        # global grad-norm clip factor (`fused_lamb.py:120-136`)
        if self.max_grad_norm:
            with self._phase("norms"):
                sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree_util.tree_leaves(grads))
                gnorm = jnp.sqrt(sq)
                clip = jnp.where(gnorm > self.max_grad_norm,
                                 self.max_grad_norm / gnorm, 1.0)
        else:
            clip = jnp.float32(1.0)
        plain_identity = not self.use_nvlamb and self.weight_decay == 0.0

        def leaf(p, g, m, v):
            with self._phase("update"):
                p32 = p.astype(jnp.float32)
                g32 = g.astype(jnp.float32) * clip
                if not self.adam_w_mode:
                    g32 = g32 + wd * p32
                m2 = b1 * m + (1.0 - b1) * g32
                v2 = b2 * v + (1.0 - b2) * g32 * g32
                u = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + eps)
                if self.adam_w_mode:
                    u = u + wd * p32
            # per-tensor trust ratio — each leaf IS one tensor, so the
            # norms are plain reduces (no arena segments needed)
            if plain_identity:
                ratio = jnp.float32(1.0)
            else:
                with self._phase("norms"):
                    pn = jnp.sqrt(jnp.sum(jnp.square(p32)))
                    un = jnp.sqrt(jnp.sum(jnp.square(u)))
                    ratio = jnp.where((pn > 0) & (un > 0), pn / un, 1.0)
            with self._phase("update"):
                return _LeafOut(
                    (p32 - lr * ratio * u).astype(p.dtype), m2, v2)

        out = jax.tree_util.tree_map(leaf, params, grads,
                                     state.slots["m"], state.slots["v"])
        p2, m2, v2 = self._split(out, 3)
        return p2, FusedOptState(count=count, slots={"m": m2, "v": v2})


class FusedNovoGrad(FusedOptimizer):
    """NovoGrad (`apex/optimizers/fused_novograd.py:67-210`).

    Per-layer norm EMAs live in a (num_tensors,) fp32 vector per partition —
    the reference's ``exp_avg_sq`` buffer, which stores *norms* (not
    squares, `fused_novograd.py:157-158`) and blends them linearly. Defaults
    match the reference: decoupled decay (``reg_inside_moment=False`` ↔
    MOMENT_MODE_1), bias correction on, grad averaging on, L2 norms,
    first-step norm initialization (``init_zero=False``).
    """

    slot_names = ("m",)
    scope = "novograd"

    def __init__(self, lr: Scalar = 1e-3, betas=(0.9, 0.999), eps=1e-8,
                 weight_decay=0.0, bias_correction=True,
                 reg_inside_moment=False, grad_averaging=True, norm_type=2,
                 init_zero=False, strategy: str = "auto"):
        super().__init__(lr, strategy)
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.bias_correction = bias_correction
        self.reg_inside_moment = reg_inside_moment
        self.grad_averaging = grad_averaging
        if norm_type not in (0, 2):
            raise ValueError("FusedNovoGrad only supports l2/inf norm")
        self.norm_type = norm_type
        self.init_zero = init_zero

    def init(self, params) -> FusedOptState:
        if self._use_tree(params):
            return FusedOptState(
                count=jnp.int32(0),
                slots={"m": jax.tree_util.tree_map(
                           lambda p: jnp.zeros(jnp.shape(p), jnp.float32),
                           params),
                       "vnorm": jax.tree_util.tree_map(
                           lambda p: jnp.float32(0.0), params)})
        spec = arena.plan(params)
        slots = {"m": arena.zeros(spec, dtype=jnp.float32)}
        slots["vnorm"] = {
            p.dtype: jnp.zeros((len(p.sizes),), jnp.float32)
            for p in spec.partitions}
        return FusedOptState(count=jnp.int32(0), slots=slots)

    def _per_tensor_norm(self, g, part):
        if self.norm_type == 2:
            return MT.per_tensor_l2norm_ranges(g, part.offsets, part.sizes)
        return MT.per_tensor_maxnorm_ranges(g, part.offsets, part.sizes)

    # custom step: vnorm slot has non-buffer shape
    def step(self, grads, state, params):
        if self._use_tree(params):
            return self._tree_step(grads, state, params)
        spec = arena.plan(params)
        with self._phase("arena"):
            p_bufs = arena.flatten(params, spec)
            g_bufs = arena.flatten(grads, spec, cast=jnp.float32)
        count = state.count + 1
        lr = self.lr(count) if callable(self.lr) else self.lr

        new_p = {}
        new_slots = {"m": {}, "vnorm": {}}
        for part in spec.partitions:
            dt = part.dtype
            p, g = p_bufs[dt], g_bufs[dt]
            with self._phase("norms"):
                norms = self._per_tensor_norm(g, part)
                v_prev = state.slots["vnorm"][dt]
                blended = self.beta2 * v_prev + (1.0 - self.beta2) * norms
                if self.init_zero:
                    v_new = blended
                else:
                    # init with first-step norm so the first blend is a
                    # no-op (`fused_novograd.py:163-174`)
                    v_new = jnp.where(count == 1, norms, blended)
                vpos = MT.spread_per_tensor(
                    v_new, part.offsets, part.padded, len(p), fill=1.0)
            with self._phase("update"):
                p2, m2 = K.novograd_update(
                    p, g, state.slots["m"][dt], vpos, lr=lr,
                    beta1=self.beta1, beta2=self.beta2, eps=self.eps,
                    weight_decay=self.weight_decay, step=count,
                    grad_averaging=self.grad_averaging,
                    bias_correction=self.bias_correction,
                    reg_inside_moment=self.reg_inside_moment)
            new_p[dt] = p2
            new_slots["m"][dt] = m2
            new_slots["vnorm"][dt] = v_new
        with self._phase("arena"):
            new_params = arena.unflatten(new_p, spec)
        return new_params, FusedOptState(count=count, slots=new_slots)

    def _tree_step(self, grads, state, params):
        count = state.count + 1
        lr = self._resolve_lr(count)
        bc1, bc2 = _bias_corrections(count, self.beta1, self.beta2,
                                     self.bias_correction, sqrt2=True)
        b1, b2, wd, eps = (self.beta1, self.beta2, self.weight_decay,
                           self.eps)
        b3 = (1.0 - b1) if self.grad_averaging else 1.0

        def leaf(p, g, m, vprev):
            with self._phase("norms"):
                p32 = p.astype(jnp.float32)
                g32 = g.astype(jnp.float32)
                if self.norm_type == 2:
                    nrm = jnp.sqrt(jnp.sum(jnp.square(g32)))
                else:
                    nrm = jnp.max(jnp.abs(g32))
                blended = b2 * vprev + (1.0 - b2) * nrm
                v_new = blended if self.init_zero else \
                    jnp.where(count == 1, nrm, blended)
            with self._phase("update"):
                denom = v_new / bc2 + eps
                if self.reg_inside_moment:
                    gg = g32 / denom + wd * p32
                    m2 = b1 * m + b3 * gg
                    p2 = p32 - lr * (m2 / bc1)
                else:
                    m2 = b1 * m + b3 * g32
                    p2 = p32 - lr * ((m2 / bc1) / denom + wd * p32)
                return _LeafOut(p2.astype(p.dtype), m2, v_new)

        out = jax.tree_util.tree_map(leaf, params, grads,
                                     state.slots["m"],
                                     state.slots["vnorm"])
        p2, m2, v2 = self._split(out, 3)
        return p2, FusedOptState(count=count,
                                 slots={"m": m2, "vnorm": v2})
