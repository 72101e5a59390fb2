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
import signal  # noqa: E402
import traceback  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running (subprocess soaks etc.); tier-1 runs -m 'not slow'",
    )


@pytest.fixture
def event_loop_policy():
    return asyncio.DefaultEventLoopPolicy()


# One clock for every test. A test phase (setup, call, teardown) that is
# still running after TEST_LIMIT_S fails with the main thread's stack, so a
# hang costs one test and not an xdist worker until the driver's cut.
# SIGALRM lands in the main thread, which is where pytest and xdist run
# tests; the exception is a KeyboardInterrupt so that asyncio re-raises it
# out of a callback or task instead of logging it, and subprocess.run kills
# its child. It fires again every tenth of the limit, because what the
# first one unwinds into (asyncio.run's cleanup, a finally that joins a
# thread) can block as well.
TEST_LIMIT_S = 300


class _TestClockExpired(KeyboardInterrupt):
    pass


def _clocked(item, phase):
    stacks = []

    def on_alarm(signum, frame):
        stacks.append("".join(traceback.format_list(
            f for f in traceback.extract_stack(frame)
            if "/_pytest/" not in f.filename and "/pluggy/" not in f.filename
        )))
        raise _TestClockExpired()

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S, TEST_LIMIT_S / 10)
    try:
        result = yield
    except _TestClockExpired:
        pass
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
    if stacks:  # also when the test swallowed the exception and returned
        pytest.fail(
            f"{item.nodeid} {phase} still running after {TEST_LIMIT_S} s; "
            f"main thread was at:\n{stacks[0]}",
            pytrace=False,
        )
    return result


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _clocked(item, "setup"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _clocked(item, "call"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_teardown(item):
    return (yield from _clocked(item, "teardown"))
