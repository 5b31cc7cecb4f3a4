"""Test harness: run everything on a virtual 8-device CPU mesh so the REAL
collective/sharding path is exercised without TPU hardware — the analogue of
the reference testing its full DistriOptimizer/AllReduceParameter path under
Spark ``local[4]`` (``pipeline/estimator/DistriEstimatorSpec.scala:118``).
"""

import os

# Must be set before jax initializes its backends. Hard override: unit tests
# always run on the virtual 8-device CPU mesh, whatever JAX_PLATFORMS the
# caller's environment names (on a machine with a chip that would be the
# TPU, which a second process could not take anyway).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# the variable is read when jax is imported; this also covers a jax that a
# pytest plugin imported before this file
jax.config.update("jax_platforms", "cpu")
import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; the long chaos scenarios opt out of it
    config.addinivalue_line(
        "markers", "slow: long-running scenario excluded from tier-1 "
                   "(run explicitly or with -m slow)")


@pytest.fixture(autouse=True)
def fresh_context():
    """Reset global context/mesh (and the process-wide metrics registry —
    cumulative counters must not leak across cases) between tests."""
    from analytics_zoo_tpu.common.context import reset_zoo_context
    from analytics_zoo_tpu.observability import reset_default_registry
    from analytics_zoo_tpu.pipeline.api.keras.engine import reset_uids
    reset_zoo_context()
    reset_uids()
    reset_default_registry()
    yield
    reset_zoo_context()


@pytest.fixture
def rng():
    return jax.random.key(42)


def assert_allclose(a, b, rtol=1e-4, atol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)
