"""Test environment: force JAX onto a virtual 8-device CPU platform so
sharding/pjit paths are exercised without TPU hardware (the driver separately
dry-runs the multi-chip path).  Set before anything imports jax; child
processes the tests start inherit both variables."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy scenario excluded from the tier-1 run "
        "(-m 'not slow'); runnable explicitly")
