"""Shared test setup: fake multi-device CPU, hypothesis fallback, and the
fixed-seed RNG / small-mesh fixtures."""

import os
import sys

# Fake CPU devices so mesh/sharding tests exercise real partitioning.
# Must be in place before the jax backend initializes (conftest imports
# run before any test module, so this is the safe spot).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro import testing as repro_testing  # noqa: E402

repro_testing.install_hypothesis_fallback()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from repro.launch.mesh import make_test_mesh  # noqa: E402


@pytest.fixture
def rng():
    """Fixed-seed numpy Generator — deterministic across runs."""
    return np.random.default_rng(0)


@pytest.fixture
def small_mesh():
    """Concrete 2x2 ("data", "model") mesh with Auto axes over fake CPU
    devices (``jax.make_mesh`` defaults to Explicit axes, under which
    ``with_sharding_constraint`` refuses a logical-rule mesh)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 (fake) devices")
    return make_test_mesh(2, 2)


@pytest.fixture
def abstract_mesh():
    """Device-free 2x2 ("data", "model") mesh for rule-resolution tests."""
    return jax.sharding.AbstractMesh((2, 2), ("data", "model"))
