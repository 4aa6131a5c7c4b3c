"""Test harness: single-process 8-virtual-device CPU mesh.

Analogue of the reference's local-mode Spark `local[4]` harness
(ref: src/test/scala/com/microsoft/hyperspace/SparkInvolvedSuite.scala:26-56):
distribution is exercised through virtual devices on one host.
Env must be set before jax initializes its backends.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# device kernels must FAIL tests, not silently fall back to the host path
# (the fail-open circuit breaker is a production behavior, not CI's)
os.environ["HYPERSPACE_DEVICE_STRICT"] = "1"

# a plugin may have imported jax before this file set JAX_PLATFORMS; the
# config update still wins as long as no backend has been initialized
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture()
def tmp_session(tmp_path):
    """Fresh session with its own warehouse/system path per test (analogue of
    HyperspaceSuite's per-suite `spark.hyperspace.system.path` temp dir)."""
    from hyperspace_tpu.session import HyperspaceSession

    return HyperspaceSession(warehouse_dir=str(tmp_path))
