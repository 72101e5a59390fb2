"""Test config: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; all sharding tests run on
`--xla_force_host_platform_device_count=8` CPU devices (same XLA partitioner
code paths as real ICI meshes). Must be set before jax import.
"""

import os

# FORCE the CPU, not setdefault: on a machine with a chip the suite must
# not take it (ManagedProcess children inherit this through os.environ)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# one CPU core runs every process the suite spawns: a worker's event loop
# can starve past the production 10s lease TTL, making its model flap out
# of discovery mid-test (the 404 flake class). Inherited by ManagedProcess
# children through os.environ.
os.environ.setdefault("DYN_LEASE_TTL_S", "45")

import asyncio  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (subprocess soaks etc.); tier-1 runs -m 'not slow'",
    )


@pytest.fixture
def event_loop_policy():
    return asyncio.DefaultEventLoopPolicy()
