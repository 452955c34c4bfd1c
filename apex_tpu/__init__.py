"""apex_tpu — a TPU-native mixed-precision & distributed training framework.

A brand-new JAX/XLA/Pallas framework with the capabilities of NVIDIA Apex
(reference: /root/reference, see SURVEY.md):

- ``apex_tpu.amp``       — precision policy engine with O0–O3 presets and a
                           functional dynamic loss scaler (no host syncs).
- ``apex_tpu.arena``     — flat parameter arena (the multi-tensor-apply substrate).
- ``apex_tpu.ops``       — fused Pallas kernels: multi-tensor scale/axpby/l2norm,
                           LayerNorm, MLP, softmax-CE, NHWC BatchNorm, attention.
- ``apex_tpu.optim``     — fused optimizers (SGD/Adam/LAMB/NovoGrad/Adagrad) and
                           ZeRO-style sharded distributed optimizers.
- ``apex_tpu.parallel``  — data parallelism, SyncBatchNorm, LARC, mesh helpers,
                           ring-attention sequence parallelism.
- ``apex_tpu.models``    — ResNet, DCGAN, BERT-style transformer, RNN stacks.
- ``apex_tpu.sparsity``  — 2:4 structured sparsity (ASP).
- ``apex_tpu.prof``      — profiler/trace tooling over jax.profiler + HLO cost
                           analysis.
- ``apex_tpu.monitor``   — runtime telemetry: in-graph training-health
                           counters + host-side metrics pipeline (sinks,
                           step-time/MFU, collective-bytes accounting).
- ``apex_tpu.trace``     — distributed tracing + flight recorder: span-level
                           step timelines (Chrome-trace/Perfetto export),
                           crash dumps, hang watchdog, NaN provenance.
- ``apex_tpu.lint``      — apexlint: jaxpr/HLO static-analysis passes that
                           catch precision leaks, donation misses, implicit
                           resharding and host syncs before they cost a run.
- ``apex_tpu.ckpt``      — elastic checkpointing + fault escalation: async
                           donation-safe sharded snapshots, crash-safe
                           manifest-last commits, resume on a different
                           mesh shape, silent-rank → checkpoint-and-exit.
- ``apex_tpu.guard``     — self-healing training: in-graph anomaly
                           detection (loss spikes, grad explosions,
                           nonfinite params), a skip→backoff→rewind→
                           escalate policy ladder, and a deterministic
                           chaos-injection harness.

Unlike the reference (an interception-based library over an eager framework),
apex_tpu expresses the same capabilities as *policies, functional transforms and
kernels* compiled by XLA: precision is a policy object applied at the library
boundary, loss scaling is explicit state threaded through the train step,
gradient synchronisation is ``psum`` over a named mesh axis, and the fused
CUDA kernels of the reference are Pallas kernels over a flat parameter arena.
"""

__version__ = "0.1.0"

import importlib as _importlib
import time as _time

_SUBPACKAGES = ("amp", "arena", "ckpt", "fp16_utils", "guard", "lint",
                "monitor", "ops", "optim", "parallel", "prof", "reparam",
                "trace", "utils")

# each subpackage's import is a span of prof.compile_watch's timeline:
# the clock is read before the first and after each
_start = _time.perf_counter()
_ends = []
for _name in _SUBPACKAGES:
    _importlib.import_module(f"{__name__}.{_name}")
    _ends.append((_name, _time.perf_counter()))
prof.compile_watch.record_import(__name__, _start, _ends)  # noqa: F821

__all__ = [*_SUBPACKAGES, "__version__"]
