"""JAX's persistent compilation cache, placed from outside or at one
fixed path.

Entry points that compile real models call :func:`enable_compile_cache`
once, before their first ``jit`` (``chip_smoke.py``, ``bench.py``, the
examples, ``scripts/prof_*.py``, ``python -m apex_tpu.ops``). It is not
called on ``import apex_tpu``: a library import configures nothing.
"""

from __future__ import annotations

import os

import jax

__all__ = ["CACHE_DIR_ENV", "DEFAULT_CACHE_DIR", "enable_compile_cache"]

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache, git-ignored. Fixed on purpose — the
#: directory is how a later process finds what this one compiled, so
#: nothing that varies per run (pid, time, tempdir) may enter the path.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable
    itself and no directory is set in code; otherwise JAX is pointed at
    :data:`DEFAULT_CACHE_DIR`. Every compile is kept, however short
    (JAX's default skips those under a second), so a second run of the
    same program compiles nothing.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from_env = os.environ.get(CACHE_DIR_ENV)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
