"""Test configuration: force a virtual 8-device CPU platform.

Sharding/multi-chip tests run against 8 virtual CPU devices
(xla_force_host_platform_device_count), the strategy prescribed for testing
TPU sharding without TPU hardware.  Must run before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8").strip()

import pytest  # noqa: E402


def pytest_configure(config):
    # mirror the pyproject.toml marker registry so the suite stays
    # --strict-markers-clean even when run from another rootdir
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
                   "gate (ROADMAP.md runs -m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests driving the "
                   "nnstreamer_tpu.testing.faults proxy")
    config.addinivalue_line(
        "markers", "perf: hot-path regression smokes (copy gates via "
                   "tools/hotpath_bench.py --assert; fast, "
                   "counter-based, tier-1 runs them)")


@pytest.fixture(scope="session")
def jax_cpu_devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {devs}"
    return devs


