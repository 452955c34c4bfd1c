"""apex_tpu.prof — profiling/tracing subsystem (the pyprof equivalent).

The reference's pyprof pipeline is three offline stages
(`apex/pyprof/nvtx/nvmarker.py` annotate → nvprof → `parse/` → `prof/`
FLOPs analyzers). TPU-native, the same capability is:

- :mod:`~apex_tpu.prof.annotate` — ``scope``/``annotate`` named-scope
  helpers + ``annotate_modules`` flax interceptor (arg shapes/dtypes per
  module call, reversible, no monkey-patching);
- :mod:`~apex_tpu.prof.xplane` — parse ``jax.profiler`` xplane.pb traces
  into per-HLO-op timing records;
- :mod:`~apex_tpu.prof.hlo` — XLA cost analysis + per-instruction
  FLOPs/bytes estimates from optimized HLO;
- :mod:`~apex_tpu.prof.report` — ``profile_step`` one-stop capture →
  parse → MFU report;
- :mod:`~apex_tpu.prof.memory` — HBM footprint reports: per-buffer
  attribution (params / optimizer state / activations / comm) from the
  optimized HLO + ``memory_analysis()``, peak-live estimate, what-if
  batch scaler vs HBM capacity (docs/memory.md);
- :mod:`~apex_tpu.prof.compile_watch` — the set-up timeline: import,
  trace, lower, compile and cache load as spans on one clock
  (``setup_report``), the counters folded from them, and the retrace
  detector naming the argument whose shape changed (autotune-origin
  compiles tagged separately via ``autotune_scope``);
- :mod:`~apex_tpu.prof.roofline` — per-op efficiency attribution:
  measured device time joined with analytic FLOPs/bytes against the
  chip's peak table, compute/memory bound classes, per-family
  aggregation, and the fingerprinted ``worst_gaps`` autotuner feed
  (docs/profiling.md#roofline);
- :mod:`~apex_tpu.prof.sentinel` — noise-aware perf-regression gate
  over bench JSON trajectories (robust median/MAD, direction-aware,
  fingerprinted waivers; ``scripts/perf_sentinel.py``);
- :mod:`~apex_tpu.prof.sharding` — per-mesh-axis HBM attribution from
  the compiled module's HloSharding annotations: sharded-by vs
  replicated-over per axis, closure over ``memory_report``'s class
  totals, what-if ``forecast_axes`` shrink pricing
  (``scripts/mesh_explain.py``; docs/memory.md#shard-report).
"""

from apex_tpu.prof.annotate import (CallRecord, annotate, annotate_modules,
                                    scope)
from apex_tpu.prof.compile_watch import (CompileWatcher, FunctionWatch,
                                         SetupReport, autotune_scope,
                                         global_counters, setup_report)
from apex_tpu.prof.hlo import (OpEstimate, compiled_hlo, cost_analysis,
                               op_estimates, op_estimates_from_text)
from apex_tpu.prof.memory import (BufferRecord, MemoryReport,
                                  device_memory_sample, hbm_capacity,
                                  memory_report)
from apex_tpu.prof.report import (PEAK_FLOPS, PEAK_HBM_BW, StepReport,
                                  device_peak_flops, device_peak_hbm_bw,
                                  profile_step, trace)
from apex_tpu.prof.roofline import (RooflineReport, RooflineRow,
                                    roofline_report)
from apex_tpu.prof.sharding import (ShardRecord, ShardReport,
                                    shard_report)
from apex_tpu.prof.xplane import OpRecord, TraceProfile, parse_trace

__all__ = [
    "CallRecord", "annotate", "annotate_modules", "scope",
    "OpEstimate", "compiled_hlo", "cost_analysis", "op_estimates",
    "op_estimates_from_text",
    "PEAK_FLOPS", "PEAK_HBM_BW", "StepReport", "device_peak_flops",
    "device_peak_hbm_bw", "profile_step", "trace",
    "OpRecord", "TraceProfile", "parse_trace",
    "MemoryReport", "BufferRecord", "memory_report", "hbm_capacity",
    "device_memory_sample",
    "CompileWatcher", "FunctionWatch", "autotune_scope",
    "global_counters", "SetupReport", "setup_report",
    "RooflineReport", "RooflineRow", "roofline_report",
    "ShardRecord", "ShardReport", "shard_report",
]
