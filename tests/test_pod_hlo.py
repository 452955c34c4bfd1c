"""Pod-scale collective-structure assertions (VERDICT r4 item 5).

The driver cannot attach 64 chips, but the collective structure of the
compiled step is a compile-time artifact: these tests compile the
O2+DDP flagship step and the ZeRO optimizer path and assert the
optimized HLO contains the intended collectives — one fused grad
all-reduce per step at full message size (or the reduce-scatter /
all-gather pair for ZeRO), never a per-tensor collective storm. The
same audit runs against a real v5e-64 topology via the AOT compiler
when the environment provides one (scripts/pod_comm_budget.py); here
the 8-device CPU mesh keeps it CI-runnable. Reference analogue: the
bucketed hierarchy apex hand-builds
(`apex/parallel/distributed.py:604-624`,
`apex/contrib/optimizers/distributed_fused_adam.py:250-290`).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from scripts.pod_comm_budget import (collectives,
                                     hierarchical_structure_audit,
                                     lower_flagship, overlap_audit,
                                     stablehlo_collectives)


def _compile_resnet_step(mesh, n, delay_allreduce, **mode_kw):
    # small ResNet keeps CI fast; the collective structure is the same,
    # and the step construction is the SAME code the v5e-64 evidence
    # compiles (scripts/pod_comm_budget.py)
    from apex_tpu import models

    model_small = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                                width=16, dtype=jnp.bfloat16)
    lowered, params_s = lower_flagship(
        mesh, n, delay_allreduce=delay_allreduce, model=model_small,
        image_size=32, per_chip_batch=4, **mode_kw)
    hlo = lowered.compile().as_text()
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params_s))
    n_tensors = len(jax.tree_util.tree_leaves(params_s))
    return hlo, n_params, n_tensors, lowered, params_s


_BUCKET_MSG = 30_000    # elements: splits the small model into 2 buckets


def _xla_combines_allreduces(mesh) -> bool:
    """Feature-probe the backend's all-reduce combiner pass: two
    independent psums merge into one variadic all-reduce where the pass
    runs (older XLA CPU pipelines don't schedule it at all)."""
    def f(a, b):
        return jax.lax.psum(a, "data"), jax.lax.psum(b, "data")

    mapped = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=(P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))
    x = jnp.ones((8, 256), jnp.float32)
    hlo = mapped.lower(x, x).compile().as_text()
    n_ar = len([c for c in collectives(hlo) if c[0] == "all-reduce"])
    return n_ar <= 1


@pytest.mark.parametrize("delay", [True, False])
def test_ddp_one_fused_grad_allreduce(mesh8, delay):
    """The grad sync must compile to ~one full-size all-reduce — with
    delay_allreduce a flat per-dtype buffer, without it the XLA
    combiner's variadic merge — never one collective per tensor."""
    if not delay and not _xla_combines_allreduces(mesh8):
        pytest.skip("this XLA pipeline has no all-reduce combiner pass; "
                    "the fused-sync claim needs delay_allreduce here")
    hlo, n_params, n_tensors, _, _ = _compile_resnet_step(mesh8, 8, delay)
    colls = collectives(hlo)
    # everything except the scalar loss pmean is grad traffic
    ars = [c for c in colls if c[0] == "all-reduce" and c[3] > 128]
    grad_bytes = n_params * 4
    assert n_tensors > 20, "model too small to prove no-storm"
    assert len(ars) <= 4, (
        f"collective storm: {len(ars)} all-reduces for "
        f"{n_tensors} tensors:\n" + "\n".join(map(str, ars)))
    total = sum(c[3] for c in ars)
    # XLA may algebraically move a stray small tensor's reduction out
    # of the fused op (CPU backend: 764 of 131176 bytes); the claim is
    # structural — bulk coverage, not bitwise byte accounting
    assert total >= int(grad_bytes * 0.95), (
        f"grad all-reduces cover {total} bytes < fp32 grads "
        f"{grad_bytes}")


def test_zero_optimizer_scatter_gather(mesh8):
    """DistributedFusedAdam (ZeRO): grads reduce-scatter to shards,
    updated params all-gather back — and no full-size all-reduce."""
    from apex_tpu import parallel
    from apex_tpu.optim import DistributedFusedAdam

    opt = DistributedFusedAdam(lr=1e-3, axis_name=parallel.DATA_AXIS)
    n_params = 1 << 20
    params = {"w": jax.ShapeDtypeStruct((n_params,), jnp.float32)}

    def step(params, xb):
        def loss_fn(p):
            return jnp.sum(jnp.square(p["w"])) * jnp.mean(xb)
        # grads stay UNREDUCED: the ZeRO optimizer's own pipeline does
        # psum_scatter -> shard update -> all_gather
        grads = jax.grad(loss_fn)(params)
        opt_state = opt.init(params)
        new_params, _ = opt.step(grads, opt_state, params)
        return new_params

    x_s = jax.ShapeDtypeStruct((8,), jnp.float32)
    stepped = jax.jit(jax.shard_map(
        step, mesh=mesh8,
        in_specs=(P(), P(parallel.DATA_AXIS)),
        out_specs=P(), check_vma=False))
    hlo = stepped.lower(params, x_s).compile().as_text()
    colls = collectives(hlo)
    kinds = {c[0] for c in colls}
    assert "reduce-scatter" in kinds, f"no reduce-scatter: {colls}"
    assert "all-gather" in kinds, f"no all-gather: {colls}"
    param_bytes = n_params * 4
    big_ar = [c for c in colls
              if c[0] == "all-reduce" and c[3] >= param_bytes // 2]
    assert not big_ar, (
        f"ZeRO path still moves full-size all-reduces: {big_ar}")


# What PR 21 established (PERF.md, "Bring-up on the chip"): the XLA:CPU of
# jaxlib 0.9.0 drops the optimization-barrier chain and merges the
# per-bucket all-reduces into one variadic all-reduce, so the two
# structural asserts below cannot hold on the CI mesh. The TPU compiler
# keeps them apart — 4 all-reduces for 4 buckets on four v5e chips, and
# `ddp/overlap-start-done` passes compiled. Whether bucketed sync survives
# at all is ROADMAP D5's call; strict, so a jaxlib that stops merging
# shows up here.
_XLA_CPU_MERGES_BUCKETS = pytest.mark.xfail(
    strict=True, reason="XLA:CPU (jaxlib 0.9.0) merges the per-bucket "
                        "all-reduces; the TPU compiler does not")


class TestBucketedOverlap:
    """Overlap-audit assertions for the bucketed backward-ordered sync
    (apex ``allreduce_bucket`` parity) on the CI mesh. The async
    start/done-pair half of the audit needs a TPU-scheduled module and
    lives in the slow v5e-64 test below + the ``ddp/overlap-start-done``
    compile-check case; here the structure (per-bucket all-reduces that
    the combiner cannot re-merge, wire dtype/bytes) is pinned."""

    @_XLA_CPU_MERGES_BUCKETS
    def test_per_bucket_allreduces_not_merged(self, mesh8):
        from apex_tpu.parallel import comm

        hlo, n_params, _, _, params_s = _compile_resnet_step(
            mesh8, 8, False, bucket_allreduce=True,
            message_size=_BUCKET_MSG)
        plan = comm.bucket_plan(jax.tree_util.tree_leaves(params_s),
                                _BUCKET_MSG)
        assert len(plan) >= 2, "model too small to exercise bucketing"
        ars = [c for c in collectives(hlo)
               if c[0] == "all-reduce" and c[3] > 128]
        assert len(ars) >= len(plan), (
            f"buckets merged into {len(ars)} all-reduces "
            f"(plan has {len(plan)}):\n" + "\n".join(map(str, ars)))
        # no single terminal all-reduce carries the whole gradient
        grad_bytes = n_params * 4
        assert all(c[3] < grad_bytes for c in ars), ars
        # ...but together they still cover it
        assert sum(c[3] for c in ars) >= int(grad_bytes * 0.95)

    @_XLA_CPU_MERGES_BUCKETS
    def test_bucket_bytes_bounded_by_message_size(self, mesh8):
        from apex_tpu.parallel import comm

        hlo, _, _, _, params_s = _compile_resnet_step(
            mesh8, 8, False, bucket_allreduce=True,
            message_size=_BUCKET_MSG)
        plan = comm.bucket_plan(jax.tree_util.tree_leaves(params_s),
                                _BUCKET_MSG)
        # bucketing is at tensor granularity: a single oversized tensor
        # may exceed the cap, exactly like the reference's
        # allreduce_bucket — the bound is max(cap, biggest tensor)
        biggest = max(int(np.prod(l.shape)) for l in
                      jax.tree_util.tree_leaves(params_s))
        cap_bytes = max(_BUCKET_MSG, biggest) * 4
        ars = [c for c in collectives(hlo)
               if c[0] == "all-reduce" and c[3] > 128]
        assert max(c[3] for c in ars) <= cap_bytes * 1.05, (ars,
                                                            cap_bytes)
        assert max(b.bytes() for b in plan) <= cap_bytes

    def test_bf16_wire_bytes_halved(self, mesh8):
        """compress="bf16": wire bytes ≤ 50% of the logical fp32 grad
        bytes. Asserted on the LOWERED module's collectives — CPU's
        float-normalization pass promotes bf16 all-reduces to f32 in
        the optimized text (TPU keeps them native; the slow v5e-64
        audit asserts the optimized module there)."""
        _, n_params, _, lowered, _ = _compile_resnet_step(
            mesh8, 8, False, bucket_allreduce=True,
            message_size=_BUCKET_MSG, compress="bf16")
        colls = stablehlo_collectives(lowered.as_text())
        ars = [c for c in colls if c[0] == "all-reduce" and c[3] > 128]
        assert ars and all(c[1] == "bf16" for c in ars), colls
        logical = n_params * 4
        wire = sum(c[3] for c in ars)
        assert wire <= logical * 0.505, (wire, logical)
        assert wire >= logical * 0.45, (wire, logical)

    def test_default_mode_structurally_unchanged(self, mesh8):
        """The default (no-bucket, no-compress) DDP path must compile
        to the same program as before this layer existed — same opcode
        sequence, same collectives (the compile-check case
        ``ddp/no-compress-bitident``, run here so CI owns it)."""
        from apex_tpu.ops import compile_check as cc

        fn = dict(cc.CASES)["ddp/no-compress-bitident"]
        fn()

    def test_overlap_audit_parses_async_pairs(self):
        """overlap_audit on a synthetic scheduled module: start/done
        pairs found, compute between them counted."""
        hlo = "\n".join([
            "%ars.1 = (f32[100]{0}, f32[100]{0}) "
            "all-reduce-start(%p0), replica_groups={{0,1}}",
            "%fusion.7 = f32[8]{0} fusion(%p1), kind=kLoop",
            "%dot.3 = f32[8,8]{1,0} dot(%p1, %p2)",
            "%ard.1 = f32[100]{0} all-reduce-done(%ars.1)",
            "%ars.2 = (f32[50]{0}, f32[50]{0}) "
            "all-reduce-start(%fusion.7), replica_groups={{0,1}}",
            "%ard.2 = f32[50]{0} all-reduce-done(%ars.2)",
        ])
        pairs = overlap_audit(hlo)
        assert len(pairs) == 2
        assert pairs[0]["compute_between"] == 2
        assert pairs[0]["bytes"] == 400
        assert pairs[1]["compute_between"] == 0


class TestHierarchicalSchedule:
    """The collectives-v2 structure pins on the CI mesh: the
    hierarchical comm_plan compiles to within-slice ICI hops plus
    one-member-per-slice DCN hops (APX203 absent), the per-hop dtype
    split is readable from the compiled module, and the committed
    NEGATIVE twin proves APX203 still fires on the flat path — the
    done-state of ROADMAP item 2 as standing static artifacts."""

    def _hier_compile(self, mesh2x4, dtypes=None):
        from apex_tpu import models
        from apex_tpu.lint.mesh_model import parse_mesh_spec
        from apex_tpu.parallel import hierarchy

        mm = parse_mesh_spec("dp2x4")
        kw = {} if dtypes is None else {"dtypes": dtypes}
        plan = hierarchy.plan_comm(mm, grad_bytes=1 << 20, **kw)
        model = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                              width=16, dtype=jnp.bfloat16)
        lowered, params_s = lower_flagship(
            mesh2x4, 8, delay_allreduce=False, model=model,
            image_size=32, per_chip_batch=4,
            message_size=_BUCKET_MSG, comm_plan=plan)
        return lowered.compile().as_text(), mm, plan, params_s

    def test_one_member_per_slice_dcn_groups(self, mesh2x4):
        hlo, mm, plan, _ = self._hier_compile(mesh2x4)
        assert plan.dtype_by_link() == {"ici": "int8", "dcn": "int8"}
        dcn_i, ici_i = hierarchical_structure_audit(hlo, mm)
        assert dcn_i and ici_i

    def test_per_hop_dtype_split_in_wire_report(self, mesh2x4):
        from apex_tpu import monitor

        hlo, _, _, _ = self._hier_compile(mesh2x4)
        by_hop = monitor.wire_report(hlo_text=hlo)["by_hop"]
        assert "s8" in by_hop["ici"], by_hop
        assert "s8" in by_hop["dcn"], by_hop
        # the slice-local hops carry ~intra x the DCN shard traffic
        assert sum(by_hop["ici"].values()) > \
            sum(by_hop["dcn"].values()), by_hop

    def test_apx203_negative_twin_flat_path_still_fires(self, mesh8):
        """The gate's gate: the FLAT bucketed sync over the same
        2-slice model must still produce APX203 — otherwise the
        'hierarchical flagship is APX203-clean' claim passes
        vacuously."""
        from apex_tpu import models
        from apex_tpu.lint.mesh_model import parse_mesh_spec
        from apex_tpu.lint.spmd_pass import dcn_flat_findings

        model = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                              width=16, dtype=jnp.bfloat16)
        lowered, _ = lower_flagship(
            mesh8, 8, delay_allreduce=False, model=model,
            image_size=32, per_chip_batch=4, bucket_allreduce=True,
            message_size=_BUCKET_MSG)
        findings = dcn_flat_findings(lowered.compile().as_text(),
                                     parse_mesh_spec("dp2x4"))
        assert findings, "flat DDP sync no longer trips APX203"
        assert all(f.rule == "dcn-flat-collective" for f in findings)

    def test_ef_residual_roundtrips_through_flagship_shapes(self,
                                                            mesh2x4):
        """Lowering with residual threading intact: comm_plan syncs
        inside the flagship compile without touching the default path
        (the bitident compile-check owns the None case)."""
        hlo, mm, plan, params_s = self._hier_compile(mesh2x4)
        # grad traffic present at full coverage: every f32 param
        # element crossed the ICI scatter as int8 payload
        from apex_tpu import monitor
        by_hop = monitor.wire_report(hlo_text=hlo)["by_hop"]
        n_params = sum(int(np.prod(l.shape))
                       for l in jax.tree_util.tree_leaves(params_s))
        assert by_hop["ici"].get("s8", 0) >= n_params


@pytest.mark.slow
def test_v5e256_2slice_aot_hierarchical_audit():
    """CI pin of the pod-scale evidence: the hierarchical comm_plan
    compiled AOT for a 256-chip v5e target factored as 2 (modeled)
    slices x 128 chips — one-member-per-slice DCN reduce groups and
    the per-hop dtype split asserted from the real TPU-scheduled HLO
    (int8 payloads survive TPU optimization; CPU promotes only float
    wires). Skipped where the TPU AOT compiler is unavailable, exactly
    like the v5e-64 siblings — the 8-device structural twins above
    keep the shape pinned in-budget."""
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:16x16")
    except Exception as e:
        pytest.skip(f"no TPU AOT topology support: {e}")
    from jax.sharding import Mesh

    from apex_tpu import models, monitor
    from apex_tpu.lint.mesh_model import parse_mesh_spec
    from apex_tpu.parallel import hierarchy

    n = len(topo.devices)
    assert n == 256
    mesh = Mesh(np.array(topo.devices).reshape(2, n // 2),
                ("data_inter", "data_intra"))
    mm = parse_mesh_spec(f"dp2x{n // 2}")
    model = models.ResNet(stage_sizes=[1, 1], num_classes=10,
                          width=16, dtype=jnp.bfloat16)
    plan = hierarchy.plan_comm(mm, grad_bytes=1 << 20)
    try:
        lowered, _ = lower_flagship(
            mesh, n, delay_allreduce=False, model=model, image_size=32,
            per_chip_batch=4, message_size=_BUCKET_MSG, comm_plan=plan)
        hlo = lowered.compile().as_text()
    except Exception as e:
        pytest.skip(f"TPU AOT compile unavailable: {e}")
    dcn_i, ici_i = hierarchical_structure_audit(hlo, mm)
    assert dcn_i and ici_i
    by_hop = monitor.wire_report(hlo_text=hlo)["by_hop"]
    assert "s8" in by_hop.get("ici", {}), by_hop
    assert "s8" in by_hop.get("dcn", {}), by_hop


@pytest.mark.slow
def test_v5e64_aot_overlap_and_compression():
    """The acceptance audit against a REAL v5e-64 topology: bucketed
    mode compiles to per-bucket all-reduces (no single terminal
    all-reduce — the structure the latency-hiding scheduler needs to
    emit start/done pairs behind backward; pairs themselves are
    asserted only when the printed module carries them, see below), and
    ``compress="bf16"`` moves ≤ 50% of the logical grad bytes in the
    OPTIMIZED module (bf16 is native on TPU). Skipped where the
    environment cannot AOT-compile for TPU topologies (CPU-only CI —
    the structural halves above keep it pinned in-budget)."""
    from apex_tpu.parallel import comm

    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:8x8")
    except Exception as e:
        pytest.skip(f"no TPU AOT topology support: {e}")
    from jax.sharding import Mesh
    from apex_tpu import parallel
    mesh = Mesh(np.array(topo.devices), (parallel.DATA_AXIS,))

    try:
        hlo, n_params, _, _, params_s = _compile_resnet_step(
            mesh, 64, False, bucket_allreduce=True,
            message_size=_BUCKET_MSG)
    except Exception as e:
        pytest.skip(f"TPU AOT compile unavailable: {e}")
    plan = comm.bucket_plan(jax.tree_util.tree_leaves(params_s),
                            _BUCKET_MSG)
    grad_bytes = n_params * 4
    ars = [c for c in collectives(hlo)
           if c[0] == "all-reduce" and c[3] > 128]
    assert len(ars) >= len(plan) >= 2, (len(ars), len(plan))
    assert all(c[3] < grad_bytes for c in ars), "terminal all-reduce"
    # async start/done pairs appear only in modules printed AFTER the
    # latency-hiding scheduler's async conversion; the v5e AOT path
    # prints the optimized-but-sync form (measured: zero -start ops),
    # so the pair half is conditional — the per-bucket structure above
    # is what gives the scheduler its overlap freedom either way
    pairs = [p for p in overlap_audit(hlo) if p["bytes"] > 128]
    if pairs:
        assert any(p["compute_between"] > 0 for p in pairs), pairs

    hlo_bf16, n_params, _, _, _ = _compile_resnet_step(
        mesh, 64, False, bucket_allreduce=True,
        message_size=_BUCKET_MSG, compress="bf16")
    # scheduled TPU modules carry collectives as start/done pairs (the
    # audit reports payload bytes once per pair); unscheduled fall back
    # to the sync-collective scan
    pairs_bf16 = [p for p in overlap_audit(hlo_bf16)
                  if p["op"] == "all-reduce" and p["bytes"] > 128]
    if pairs_bf16:
        wire = sum(p["bytes"] for p in pairs_bf16)
    else:
        wire = sum(c[3] for c in collectives(hlo_bf16)
                   if c[0] == "all-reduce" and c[3] > 128)
    assert wire <= n_params * 4 * 0.505, (wire, n_params * 4)


@pytest.mark.slow
def test_v5e64_aot_collective_structure():
    """The same audit against a REAL v5e-64 topology via the AOT
    compiler — the full-scale evidence. Skipped when the environment
    cannot AOT-compile for TPU topologies (CPU-only CI). ``slow``: the
    64-device AOT compile alone runs past the whole tier-1 budget's
    margin on CPU CI (290s+); the 8-device mesh audits above keep the
    structure pinned in-budget."""
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:8x8")
    except Exception as e:
        pytest.skip(f"no TPU AOT topology support: {e}")
    from jax.sharding import Mesh
    from apex_tpu import parallel
    mesh = Mesh(np.array(topo.devices), (parallel.DATA_AXIS,))
    try:
        hlo, n_params, n_tensors = _compile_resnet_step(mesh, 64, True)
    except Exception as e:
        pytest.skip(f"TPU AOT compile unavailable: {e}")
    colls = collectives(hlo)
    grad_bytes = n_params * 4
    ars = [c for c in colls if c[0] == "all-reduce" and c[3] > 128]
    assert len(ars) <= 4, ars
    # same 0.95 slack as the CPU sibling: XLA may algebraically move a
    # stray small tensor's reduction out of the fused op
    assert sum(c[3] for c in ars) >= int(grad_bytes * 0.95), ars
    # all 64 chips participate in one replica group — enumerated or
    # iota-printed form depending on XLA version
    import re as _re
    assert _re.search(r"replica_groups=(\{\{0,1,2,3|\[1,64\]<=\[64\])",
                      hlo), "no 64-wide replica group found"


# ---- kernels of a benchmark cell at its real shape, for the v5e's compiler ---

@pytest.fixture(scope="module")
def v5e_chip():
    """One described (not attached) v5e chip, or a skip where this process
    cannot describe one."""
    from jax.sharding import SingleDeviceSharding
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_mla_attention_compiles_for_the_v5e_at_the_cell_s_shape(
        v5e_chip, monkeypatch):
    """``kimi_linear.lm_s8192_b1``'s attention, and
    ``kanana2.lm_s8192_b1_v16k``'s: 32 heads of 192 with v at its own 128,
    causal, 8192 tokens, forward and both backward kernels through Mosaic,
    at the tiles ``models/mla.py`` passes. Interpret mode cannot see what
    this sees: the kernels' default 1024 x 1024 tiles took 17.7 MiB of the
    16 MiB of scoped VMEM at this head size (v padded to 192), and the v
    side's blocks, 256 lanes a step, are whole lane tiles."""
    from apex_tpu import ops
    from apex_tpu.models import mla
    from apex_tpu.ops import _dispatch, attention
    for mod in (_dispatch, attention):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    x = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                             sharding=v5e_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=v5e_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, None, 192 ** -0.5, True,
            *mla._ATTN_TILES).astype(jnp.float32))

    # the suite's "highest" is for float32 oracles; the chip runs the default
    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, v).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3      # forward, dq, dk/dv
    for kernel in ("apex_attn_fwd", "apex_attn_bwd_dq", "apex_attn_bwd_dkv"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel


@pytest.mark.parametrize("dtype,precision", [
    (jnp.float32, "default"), (jnp.bfloat16, "default"),
    (jnp.float32, "highest")])
def test_kda_kernels_compile_for_the_v5e_at_the_cell_s_shape(
        v5e_chip, monkeypatch, dtype, precision):
    """``kimi_linear.lm_s8192_b1``'s delta rule: 32 heads of 128, 8192 tokens,
    forward and backward kernel through Mosaic, with float32 ``q, k, v`` as
    the model hands them over and with half ones as a user under O1 may, and
    under a default matmul precision of ``highest`` (Mosaic refuses float32
    passes over bfloat16 operands: the chip said so first, PR 29).
    What interpret mode cannot see: the blocks' tiling, the transposed and
    the bfloat16 matmuls, lane offsets of 64 inside the stacked heads'
    ``(128, 128)`` matrices, the scoped VMEM."""
    from apex_tpu.ops import _dispatch, delta_rule
    monkeypatch.setattr(_dispatch, "use_interpret", lambda: False)
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=v5e_chip)
    x = shape(1, 8192, 32, 128, dt=dtype)
    args = (x, x, x, shape(1, 8192, 32, 128), shape(1, 8192, 32))
    loss = lambda *a: jnp.sum(delta_rule.gated_delta_rule(*a))
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
            *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("apex_kda_fwd", "apex_kda_bwd"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel
    assert "triangular-solve" not in text and "while" not in text


def test_gated_attention_compiles_for_the_v5e_at_the_cell_s_shape(
        v5e_chip, monkeypatch):
    """``qwen3_next.lm_s8192_b1_v19k``'s attention: 16 q heads on 2 k/v heads
    of 256, causal, 8192 tokens, forward and both backward kernels through
    Mosaic at the op's own tiles. At a head of two whole lane tiles a head
    goes through alone, and the default 1024 x 1024 tiles that the compiler
    refuses at d = 192 fit (the backward under its raised VMEM limit);
    1024 x 512 and 512 x 512 do not (16.08 and 17.74 MiB of 16, PR 32)."""
    from apex_tpu import ops
    from apex_tpu.ops import _dispatch, attention
    for mod in (_dispatch, attention):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 8192, 16, 256), jnp.bfloat16,
                             sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 8192, 2, 256), jnp.bfloat16,
                              sharding=v5e_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, None, 1 / 16, True).astype(jnp.float32))

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3      # forward, dq, dk/dv
    for kernel in ("apex_attn_fwd", "apex_attn_bwd_dq", "apex_attn_bwd_dkv"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel
    dk, dv = compiled.out_info[1:]
    assert dk.shape == dv.shape == (1, 8192, 2, 256)


def test_grouped_query_attention_compiles_for_the_v5e_at_the_cell_s_shape(
        v5e_chip, monkeypatch):
    """``lfm2_moe.lm_s8192_b2_v8k``'s attention: two sequences, 32 q heads
    on 8 k/v heads of 64, causal, 8192 tokens, the op's own 1024 x 1024
    tiles with two heads a step. With the two tests above it puts the three
    decoder cells' causal kernels through Mosaic as PR 38 left them: the
    arithmetic of a tile under one ``scf.if`` on the frontier, and k / v
    (q / do / lse / delta in dk/dv) block indices clamped to it."""
    from apex_tpu import ops
    from apex_tpu.ops import _dispatch, attention
    for mod in (_dispatch, attention):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((2, 8192, 32, 64), jnp.bfloat16,
                             sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16,
                              sharding=v5e_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, None, 1 / 8, True).astype(jnp.float32))

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    for kernel in ("apex_attn_fwd", "apex_attn_bwd_dq", "apex_attn_bwd_dkv"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel


@pytest.mark.parametrize("heads,window", [
    pytest.param(72, 512, id="window-72-heads-512-tiles"),
    pytest.param(48, None, id="global-48-heads-own-tiles")])
def test_window_and_global_attention_compile_for_the_v5e_at_the_cell_s_shape(
        v5e_chip, monkeypatch, heads, window):
    """``laguna_s.lm_s4096_b1_v12k``'s two kinds of attention: one sequence
    of 4096 tokens, 72 (window) or 48 (global) q heads on 8 k/v heads of
    128, bfloat16, the op's own tiles. The window layers' band at 512 x 512
    tiles, four heads a step, both bounds of the frontier in the index maps;
    the global layers' causal frontier at 1024 x 1024 tiles, two heads a
    step under the raised limit."""
    from apex_tpu import ops
    from apex_tpu.ops import _dispatch, attention
    for mod in (_dispatch, attention):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 4096, heads, 128), jnp.bfloat16,
                             sharding=v5e_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 128), jnp.bfloat16,
                              sharding=v5e_chip)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, None, 128 ** -0.5, True,
            window=window).astype(jnp.float32))

    with jax.default_matmul_precision("default"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    for kernel in ("apex_attn_fwd", "apex_attn_bwd_dq", "apex_attn_bwd_dkv"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel
    dk, dv = compiled.out_info[1:]
    assert dk.shape == dv.shape == (1, 4096, 8, 128)


def test_the_causal_skip_leaves_the_other_kernels_mosaic_text_alone(
        v5e_chip, monkeypatch):
    """``attention/causal-skip-no-extra-dispatch`` lowered for the described
    v5e: with the skip and without it, the non-causal multi-block kernels,
    the single-k forward and the fused backward are the same Mosaic payloads
    (BERT's kernels are among them), and the causal multi-block ones are
    not."""
    from apex_tpu.ops import _dispatch, attention, compile_check
    for mod in (_dispatch, attention):
        monkeypatch.setattr(mod, "use_interpret", lambda: False)
    with jax.default_matmul_precision("default"):
        compile_check._causal_skip_reach_case(sharding=v5e_chip)


@pytest.mark.parametrize("dtype,precision", [
    (jnp.float32, "default"), (jnp.bfloat16, "default"),
    (jnp.float32, "highest")])
def test_kda_kernels_take_a_scalar_decay_and_shared_key_heads_for_the_v5e(
        v5e_chip, monkeypatch, dtype, precision):
    """``qwen3_next.lm_s8192_b1_v19k``'s delta rule: 16 key heads serving 32
    value heads of 128, one decay a head, 8192 tokens: the two kernels of
    one decay a head, which read the decay as it is and a key head where it
    is, and write a key head's cotangent once. What interpret mode cannot
    see: the transposes of ``(128, 128)`` blocks (the decay matrix's
    columns, the exponents' cotangents), the ``(C, 1)`` columns through the
    0/1 sums, a key head's ``(C, 128)`` block read at its own index."""
    _gdn_compiles(v5e_chip, monkeypatch, 8192, 16, 32, dtype, precision)


def _gdn_compiles(v5e_chip, monkeypatch, tokens, key_heads, heads, dtype,
                  precision):
    from apex_tpu.ops import _dispatch, delta_rule
    monkeypatch.setattr(_dispatch, "use_interpret", lambda: False)
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=v5e_chip)
    qk = shape(1, tokens, key_heads, 128, dt=dtype)
    args = (qk, qk, shape(1, tokens, heads, 128, dt=dtype),
            shape(1, tokens, heads), shape(1, tokens, heads))
    loss = lambda *a: jnp.sum(delta_rule.gated_delta_rule(*a))
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.grad(loss, argnums=range(5))).lower(
            *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("apex_gdn_fwd", "apex_gdn_bwd"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel
    assert "apex_kda" not in text
    assert "triangular-solve" not in text and "while" not in text
    assert [o.shape for o in compiled.out_info] == [a.shape for a in args]


@pytest.mark.parametrize("key_heads,heads", [(3, 3), (3, 6), (2, 8)],
                         ids=["odd_heads", "shared_in_place", "shared_by_four"])
def test_gdn_kernels_compile_for_the_v5e_at_other_head_counts(
        v5e_chip, monkeypatch, key_heads, heads):
    """One decay a head at 256 tokens where a grid step holds one head (an
    odd count: ``(64, 64)`` blocks to transpose), where the steps' pairs of
    heads share a key head, and where four value heads do (repeated)."""
    _gdn_compiles(v5e_chip, monkeypatch, 256, key_heads, heads, jnp.float32,
                  "default")


@pytest.mark.parametrize("cell,wide,channels,norm", [
    ("kimi_q", 4096, 4096, ((0, 4096, 128 ** -0.5),)),
    ("kimi_v", 4096, 4096, ()),
    ("qwen_qkv", 12288, 8192, ((0, 2048, 128 ** -0.5), (2048, 4096, 1.0))),
])
@pytest.mark.parametrize("dtype,precision", [
    (jnp.bfloat16, "default"), (jnp.float32, "highest")])
def test_short_conv_kernels_compile_for_the_v5e_at_the_cells_shapes(
        v5e_chip, monkeypatch, cell, wide, channels, norm, dtype, precision):
    """Both decoder cells' short convolution at 8192 tokens: Kimi's q (every
    head normalised and scaled) and v (none) over 4096 channels, Qwen's one
    call over the first 8192 channels of a 12288-wide projection (q scaled,
    k, then v plain); bfloat16 in as under O1, and float32 under a suite's
    ``highest``. What interpret mode cannot see: the reads from the float32
    scratch at sublane offsets 13, 14, 15 (the taps' shifts), the bfloat16
    ``(16, 128)`` tiles of the rows before and after a block, the ``(4,
    C)`` block of the taps' cotangent, the scoped VMEM."""
    from apex_tpu.ops import _dispatch
    from apex_tpu.ops.short_conv import short_conv
    monkeypatch.setattr(_dispatch, "use_interpret", lambda: False)
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=v5e_chip)
    x, taps = shape(1, 8192, wide, dt=dtype), shape(4, channels)
    weight = shape(1, 8192, channels)
    loss = lambda x, taps, w: jnp.sum(short_conv(x, taps, norm, 128) * w)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            x, taps, weight).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("apex_short_conv_fwd", "apex_short_conv_bwd"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel
    _, (d_x, d_taps) = compiled.out_info
    assert d_x.shape == x.shape and d_x.dtype == dtype
    assert d_taps.shape == taps.shape and d_taps.dtype == jnp.float32


@pytest.mark.parametrize("dtype,precision", [
    (jnp.bfloat16, "default"), (jnp.float32, "highest")])
def test_gated_short_conv_kernels_compile_for_the_v5e_at_the_cells_shape(
        v5e_chip, monkeypatch, dtype, precision):
    """LFM2's mixer between its projections at its cell's shape: two
    sequences of 8192 tokens, ``[B; C; z]`` of 3 x 2048 channels, three
    taps. What interpret mode cannot see: three blocks of one array at
    three channel offsets, the backward's fourth grid axis over the parts of
    ``d x`` with two bfloat16 blocks kept in VMEM for their turn, the scoped
    VMEM. ``d [B; C; z]`` leaves the kernel as one array."""
    from apex_tpu.ops import _dispatch
    from apex_tpu.ops.short_conv import short_conv
    monkeypatch.setattr(_dispatch, "use_interpret", lambda: False)
    shape = lambda *s, dt=jnp.float32: jax.ShapeDtypeStruct(
        s, dt, sharding=v5e_chip)
    x, taps = shape(2, 8192, 6144, dt=dtype), shape(3, 2048)
    weight = shape(2, 8192, 2048)
    loss = lambda x, taps, w: jnp.sum(short_conv(
        x, taps, (), 128, (4096, 2048)).astype(jnp.float32) * w)
    with jax.default_matmul_precision(precision):
        compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
            x, taps, weight).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    for kernel in ("apex_short_conv_fwd", "apex_short_conv_bwd"):
        assert f"({kernel})" in text or f"/{kernel}/" in text, kernel
    # the backward reads x as it is, six times over, and no concatenation
    # or pad follows it
    assert "concatenate" not in text and " pad(" not in text
    _, (d_x, d_taps) = compiled.out_info
    assert d_x.shape == x.shape and d_x.dtype == dtype
    assert d_taps.shape == taps.shape and d_taps.dtype == jnp.float32


@pytest.mark.parametrize("cell,tokens,top_k,routed,n_held,hidden,width,dtype", [
    ("lfm2", 16384, 4, 64, 8, 2048, 1536, jnp.bfloat16),
    ("kimi", 8192, 8, 256, 8, 2304, 1024, jnp.bfloat16),
    ("qwen", 8192, 10, 512, 32, 2048, 512, jnp.bfloat16),
    ("kimi_float32", 8192, 8, 256, 8, 2304, 1024, jnp.float32),
])
def test_the_expert_layer_compiles_for_the_v5e_at_the_cells_shapes(
        v5e_chip, monkeypatch, cell, tokens, top_k, routed, n_held, hidden,
        width, dtype):
    """One rank's expert layer of each decoder cell, forward and backward,
    through Mosaic: ``apex_gmm`` in its three forms (3 forward, 3 again and 2
    in the backward) and ``apex_tgmm`` (3 for the weights' gradients, and the
    two sums over each token's rows: three left operands and a float32
    result forward, one backward), with the grid's bound, the visits' groups
    and tiles read on the device. What interpret mode cannot
    see: the scoped VMEM of the tiles ``_gmm_columns`` / ``_tgmm_tiles``
    pick (bfloat16 as under O1, and float32), the transposed-lhs ``dot`` of
    ``apex_tgmm``, the 32-bit select over packed rows. No ``scatter`` is in
    the compiled layer."""
    from apex_tpu import amp
    from apex_tpu.ops import _dispatch, grouped_matmul, moe
    policy = amp.Policy.from_opt_level(
        "O1" if dtype == jnp.bfloat16 else "O0")
    for module in (_dispatch, grouped_matmul):
        monkeypatch.setattr(module, "use_interpret", lambda: False)
    shape = lambda *s, dt=dtype: jax.ShapeDtypeStruct(
        s, dt, sharding=v5e_chip)
    held = tuple(range(n_held))

    def loss(x, w, chosen, gate, up, down):
        with amp.policy_scope(policy):
            return jnp.sum(moe.held_experts(x, w, chosen, gate, up, down,
                                            held, routed))
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 3, 4, 5))).lower(
        shape(tokens, hidden), shape(tokens, top_k, dt=jnp.float32),
        shape(tokens, top_k, dt=jnp.int32), shape(n_held, hidden, width),
        shape(n_held, hidden, width), shape(n_held, width, hidden)).compile()
    text = compiled.as_text()
    calls = lambda kernel: sum(
        "tpu_custom_call" in line and f"/{kernel}/pallas_call" in line
        for line in text.splitlines())
    assert calls("apex_gmm") == 3 + 3 + 2
    assert calls("apex_tgmm") == 3 + 1 + 1
    assert " scatter(" not in text
    _, (d_x, d_w, d_gate, _, d_down) = compiled.out_info
    assert d_x.shape == (tokens, hidden) and d_x.dtype == dtype
    assert d_w.shape == (tokens, top_k) and d_w.dtype == jnp.float32
    assert d_gate.shape == (n_held, hidden, width) and d_gate.dtype == dtype
    assert d_down.shape == (n_held, width, hidden)


def test_the_scan_kernels_read_the_convolution_kernels_for_the_v5e(
        v5e_chip, monkeypatch):
    """One KDA layer at the published head size, compiled for the v5e: in
    the optimised program ``apex_kda_fwd`` takes q, k and v from the three
    ``apex_short_conv_fwd`` calls themselves and each ``apex_short_conv_bwd``
    takes its cotangent from ``apex_kda_bwd`` itself: XLA puts no copy, no
    ``(B, T, H, d)`` relayout and no fusion between the kernels."""
    import re
    from apex_tpu.models.kimi_linear import KimiDeltaAttention
    from apex_tpu.ops import _dispatch
    monkeypatch.setattr(_dispatch, "use_interpret", lambda: False)
    layer = KimiDeltaAttention(hidden=256, heads=4, head_dim=128)
    x = jnp.ones((1, 512, 256))
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))
    placed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        (params, x))
    loss = lambda p, x: jnp.sum(layer.apply(p, x) ** 2)
    with jax.default_matmul_precision("default"):
        text = jax.jit(jax.value_and_grad(loss)).lower(
            *placed).compile().as_text()
    calls = {m[0]: (m[1], m[2]) for m in re.findall(
        r"%((apex_\w+?)(?:\.\d+)?) = .*? custom-call\(([^)]*)\)", text)}
    made_by = lambda operand: calls.get(operand.strip().lstrip("%"),
                                        (operand,))[0]
    # a tuple's element: %pallas_call.N = ... get-tuple-element(%call)
    element = dict(re.findall(
        r"%([\w.-]+) = \S+ get-tuple-element\(%([\w.]+)\)", text))
    source = lambda operand: made_by(element.get(
        operand.strip().lstrip("%"), operand))
    forward = [ops for name, (kernel, ops) in calls.items()
               if kernel == "apex_kda_fwd"]
    assert len(forward) == 1
    assert [source(o) for o in forward[0].split(",")[:3]] == [
        "apex_short_conv_fwd"] * 3, forward
    backward = [ops for name, (kernel, ops) in calls.items()
                if kernel == "apex_short_conv_bwd"]
    assert len(backward) == 3
    for ops in backward:        # x, rows before, rows after, taps, d y, d y
        assert source(ops.split(",")[4]) == "apex_kda_bwd", ops
