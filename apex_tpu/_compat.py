"""Virtual CPU devices for the test and audit meshes."""

from __future__ import annotations

import jax

__all__ = ["request_cpu_devices"]


def request_cpu_devices(n: int) -> None:
    """Ask for ``n`` virtual CPU devices. Call before anything touches
    ``jax.devices()``: the count is read when the CPU backend starts."""
    jax.config.update("jax_num_cpu_devices", n)
