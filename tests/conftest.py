"""Test configuration: force CPU XLA with 8 virtual devices.

All tests run on CPU XLA (the reference's EdgeTPU `device_type:dummy`
pattern: the full framework is exercised with a software device,
tests/nnstreamer_filter_edgetpu/unittest_edgetpu.cc:30). Sharding tests get
an 8-device virtual mesh via --xla_force_host_platform_device_count.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Tier-1 runs on CPU XLA with eight virtual devices, also on a host that
# has a chip: tests must not depend on, or take, the one-process-per-chip
# TPU. The config update covers a JAX that was imported (and read
# JAX_PLATFORMS) before this file set the variable.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)
