"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Must run before any jax import (pytest imports conftest first), mirroring the
driver's multi-chip dry-run environment.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never run on an accelerator
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture
def rng():
    import numpy as np

    return np.random.default_rng(42)


@pytest.fixture(autouse=True, scope="module")
def _bound_jit_state():
    """Full-suite runs (~950 tests, one process, one core) accumulate
    thousands of XLA:CPU executables; past a few GB of JIT state the
    LLVM-side compile occasionally segfaults mid-suite (observed at
    arbitrary tests ~30 min in — jax 0.9 backend_compile_and_load, not
    reproducible on the module alone). Dropping the executable caches
    between modules bounds that state; modules recompile their own
    programs, which they mostly would anyway (distinct shapes)."""
    yield
    import jax

    jax.clear_caches()
