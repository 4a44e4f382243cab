"""Test env: 8 virtual CPU devices so multi-chip sharding (mesh/shard_map)
is exercised without TPU hardware — the analog of the reference's unistore
mock cluster (BootstrapWithMultiRegions) giving multi-node semantics in one
process (SURVEY.md §4.2).

Tests never touch a TPU: the chip is reached only through the chip tool,
by ``chip_smoke.py``.  XLA_FLAGS and JAX_PLATFORMS must be set before the
CPU backend initializes, hence before the first jax import."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

assert len(jax.devices()) == 8, jax.devices()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: excluded from tier-1 (-m 'not slow'); "
        "bench-scale rungs run on demand")


@pytest.fixture
def rng():
    return np.random.default_rng(0)
