"""Compile-level validation of the zero-dispatch telemetry contract.

The whole point of the in-graph :class:`~apex_tpu.monitor.Metrics` design
is that monitoring must not change the step's dispatch structure: the
counters ride along as extra outputs of the one compiled program, and no
host transfer happens until the logger flushes. These helpers let tests
(and ``python -m apex_tpu.ops``, see the ``monitor/no-extra-dispatch``
case) assert exactly that from the compiled HLO.
"""

from __future__ import annotations

from typing import List, Tuple

from apex_tpu.prof import hlo as _hlo

__all__ = ["HOST_TRAFFIC_MARKERS", "module_count_and_host_ops"]

# HLO spellings of device→host traffic inside a compiled module. On the
# TPU (libtpu 0.0.34, read off a compiled module on a v5e) every jax
# callback — debug.print, pure_callback, io_callback — is a send/recv
# pair with ``is_host_transfer=true``; XLA:CPU/GPU compile the same
# callbacks to a custom call whose target ends ``python_cpu_callback`` /
# ``python_gpu_callback`` (``xla_ffi_…`` on jax 0.9.0, ``xla_…`` before).
# ``monitor/no-extra-dispatch`` seeds a jax.debug.print and asserts these
# markers see it, so a renamed target cannot blind the detector again.
HOST_TRAFFIC_MARKERS = (
    " outfeed(", " infeed(", " send(", " send-done(", " recv(",
    " recv-done(", "python_cpu_callback", "python_gpu_callback",
    "tpu_host_callback", "HostCompute",
)


def module_count_and_host_ops(fn, *args, **kwargs) -> Tuple[int, List[str]]:
    """(number of HLO modules, host-traffic instructions) of a compiled fn.

    A monitored train step must report the same module count as its
    unmonitored twin (one executable — no telemetry side-programs) and an
    empty host-traffic list (no per-step device→host syncs).
    """
    text = _hlo.compiled_hlo(fn, *args, **kwargs)
    n_modules = text.count("HloModule ") or 1
    host = [line.strip()[:160] for line in text.splitlines()
            if any(m in line for m in HOST_TRAFFIC_MARKERS)]
    return n_modules, host
