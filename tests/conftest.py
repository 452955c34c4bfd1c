"""Test configuration: run everything on a simulated 8-device CPU mesh.

The reference's test tiers all require real GPUs (SURVEY.md §4). We do better:
JAX can expose N virtual CPU devices, so every distributed-semantics test in
this suite runs hostside in CI with no accelerator. Pallas kernels detect the
CPU backend and fall back to interpreter mode (see apex_tpu.ops._dispatch).

This must run before any other module initialises a JAX backend.
"""

import jax
import pytest

from apex_tpu import _compat

jax.config.update("jax_platforms", "cpu")
_compat.request_cpu_devices(8)
# Tests compare against fp32 references; keep matmuls at full fp32 precision.
jax.config.update("jax_default_matmul_precision", "highest")
# The examples call utils.enable_compile_cache(); the suite stays hermetic —
# nothing it compiles is read from or written to a persistent cache.
jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(devices):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(devices), ("data",))


@pytest.fixture(scope="session")
def mesh4x2(devices):
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(devices).reshape(4, 2), ("data", "model"))


@pytest.fixture(scope="session")
def mesh2x4(devices):
    """The factored data mesh of the hierarchical gradient sync:
    2 slices over (modeled) DCN x 4 chips over ICI — the dp2x4 mesh
    model's axis names, so plans/models/meshes line up."""
    import numpy as np
    from jax.sharding import Mesh

    from apex_tpu.parallel import DATA_INTER_AXIS, DATA_INTRA_AXIS
    return Mesh(np.array(devices).reshape(2, 4),
                (DATA_INTER_AXIS, DATA_INTRA_AXIS))
