"""One-stop step profiling: trace capture + per-op report + MFU.

Combines the capture (``jax.profiler.trace``), the xplane parser
(:mod:`apex_tpu.prof.xplane`) and XLA cost analysis
(:mod:`apex_tpu.prof.hlo`) into the workflow the reference needed three
tools for (nvtx annotate → nvprof → pyprof.parse → pyprof.prof):

    rep = prof.profile_step(step_fn, state, batch)
    print(rep.table())
    print(rep.mfu(peak_flops=197e12))
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import jax

from apex_tpu.prof import hlo as _hlo
from apex_tpu.prof import xplane as _xplane

__all__ = ["trace", "profile_step", "StepReport", "PEAK_FLOPS",
           "PEAK_HBM_BW", "VMEM_BYTES", "device_peak_flops",
           "device_peak_hbm_bw"]

# per-chip peak bf16 FLOP/s by device kind (public spec sheets). The
# v5e rows are Google Cloud's "TPU v5e" documentation: 197 TFLOP/s bf16,
# 819 GB/s HBM; the chip tool's v5e reports device_kind "TPU v5 lite"
# (chip run, PR 21).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}

# per-chip peak HBM bandwidth (bytes/s) by device kind — public spec
# sheets; PERF.md's measured steps sustain 97-98% of these, so the
# roofline denominator is honest. The bandwidth half of the peak table
# device_peak_flops starts (apex_tpu.prof.roofline reads both).
PEAK_HBM_BW = {
    "TPU v4": 1.228e12,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2.765e12,
    "TPU v5p": 2.765e12,
    "TPU v6 lite": 1.64e12,
    "TPU v6e": 1.64e12,
}

# per-chip VMEM capacity (bytes) — the on-chip scratch a Mosaic kernel
# tiles against (not a bandwidth: VMEM feeds the MXU at compute rate by
# construction, so a VMEM-resident working set never bounds a roofline;
# what DOES bound kernels is whether their tiles FIT — the autotuner's
# sweep constraint, see docs/profiling.md#roofline)
VMEM_BYTES = {
    "TPU v4": 128 << 20,
    "TPU v5 lite": 128 << 20,
    "TPU v5e": 128 << 20,
    "TPU v5": 112 << 20,
    "TPU v5p": 112 << 20,
    "TPU v6 lite": 128 << 20,
    "TPU v6e": 128 << 20,
}


def lookup_peak(table, kind: str) -> float:
    """Device-kind prefix match into a peak table; 0.0 says the kind is
    not in it (the one place the prefix-match semantics live —
    roofline_report resolves its explicit ``device_kind`` strings
    through here too, and classifies rows ``unknown`` on a 0). Anything
    that divides by a peak goes through :func:`device_peak_flops` /
    :func:`device_peak_hbm_bw`, which refuse an unknown device."""
    for k, v in table.items():
        if kind.startswith(k):
            return v
    return 0.0


def _device_kind(device) -> str:
    device = device or jax.devices()[0]
    return getattr(device, "device_kind", "cpu")


def _require_peak(table, what: str, device) -> float:
    kind = _device_kind(device)
    peak = lookup_peak(table, kind)
    if not peak:
        raise ValueError(
            f"no {what} for device kind {kind!r} in the peak table "
            f"({sorted(table)}): a utilization against an unknown peak "
            f"is not a number — measure on a listed chip or add the "
            f"kind with its source")
    return peak


def device_peak_flops(device=None) -> float:
    """Peak bf16 FLOP/s of a jax device; an unknown kind (the CPU, a
    chip not in :data:`PEAK_FLOPS`) raises ``ValueError``."""
    return _require_peak(PEAK_FLOPS, "peak FLOP/s", device)


def device_peak_hbm_bw(device=None) -> float:
    """Peak HBM bytes/s of a jax device; an unknown kind raises
    ``ValueError``."""
    return _require_peak(PEAK_HBM_BW, "peak HBM bandwidth", device)


@contextlib.contextmanager
def trace(logdir: str, **kwargs):
    """Capture a profiler trace to ``logdir`` (jax.profiler.trace shim)."""
    with jax.profiler.trace(logdir, **kwargs):
        yield logdir


@dataclasses.dataclass
class StepReport:
    """Profile of one jitted step: measured per-op times + static costs."""

    profile: _xplane.TraceProfile     # measured device activity
    cost: Dict[str, float]            # XLA cost analysis of the step
    wall_us: float                    # host wall time per iteration
    iters: int
    logdir: str

    @property
    def device_us(self) -> float:
        """Measured device time per iteration (XLA module runs)."""
        if self.profile.module_runs:
            return self.profile.module_total_us / self.profile.module_runs
        return self.wall_us

    def mfu(self, peak_flops: Optional[float] = None) -> float:
        """Model FLOPs utilization vs the chip's peak, from measured time."""
        peak = device_peak_flops() if peak_flops is None else peak_flops
        return self.cost["flops"] / (self.device_us * 1e-6) / peak

    def by_category(self) -> Dict[str, float]:
        return self.profile.by_category()

    def table(self, top: int = 20) -> str:
        # unknown device kind (CPU, new chips): mfu() raises there —
        # the table prints n/a
        known = lookup_peak(PEAK_FLOPS, _device_kind(None))
        mfu_s = f"{self.mfu():.1%}" if known else "n/a"
        head = (f"device={self.profile.device or '(none)'} "
                f"iters={self.iters} wall/iter={self.wall_us:.0f}us "
                f"device/iter={self.device_us:.0f}us "
                f"flops={self.cost['flops']:.3g} "
                f"bytes={self.cost['bytes_accessed']:.3g} "
                f"mfu={mfu_s}")
        cats = "  ".join(f"{k}={v:.0f}us" for k, v in
                         list(self.by_category().items())[:8])
        return "\n".join([head, cats, self.profile.table(top=top)])


def profile_step(fn, *args, iters: int = 5, warmup: int = 2,
                 logdir: Optional[str] = None, keep_trace: bool = False,
                 **kwargs) -> StepReport:
    """Profile a jittable step function end to end.

    Jits (if needed), warms up ``warmup`` calls, then runs ``iters``
    calls under a profiler trace and parses the resulting xplane into
    per-op records. Works with functions returning pytrees; results are
    synced via host fetch of one leaf.

    When no ``logdir`` is given a temp dir holds the trace and is
    **removed after parsing** (every record the report needs is already
    in the returned ``StepReport``); pass ``keep_trace=True`` to keep it
    for offline tools (tensorboard, ``python -m apex_tpu.prof``). An
    explicit ``logdir`` is always the caller's to clean up.
    """
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    own_tmpdir = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="apex_tpu_prof_")

    def _sync(out):
        leaves = jax.tree_util.tree_leaves(out)
        if leaves:
            import numpy as np
            np.asarray(jax.device_get(leaves[0]))

    try:
        for _ in range(max(warmup, 1)):
            out = jitted(*args, **kwargs)
        _sync(out)

        t0 = time.perf_counter()
        with trace(logdir):
            for _ in range(iters):
                out = jitted(*args, **kwargs)
            _sync(out)
        wall = (time.perf_counter() - t0) / iters

        cost = _hlo.cost_analysis(jitted, *args, **kwargs)
        prof = _xplane.parse_trace(logdir)
    finally:
        if own_tmpdir and not keep_trace:
            shutil.rmtree(logdir, ignore_errors=True)
            logdir = ""
    return StepReport(profile=prof, cost=cost, wall_us=wall * 1e6,
                      iters=iters, logdir=logdir)
