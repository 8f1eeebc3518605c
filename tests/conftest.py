"""Test config: run on a virtual 8-device CPU mesh so sharding/DP paths are
exercised without TPU hardware (reference analogue: test_multi_device_exec.py
faking group2ctx with multiple mx.cpu(i) contexts)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# tests run on the virtual 8-device CPU platform: an explicit choice of the
# host (mx.tpu(i) then resolves to host device i), made before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    np.random.seed(0)
