"""Test configuration.

Tests run on an 8-device virtual CPU mesh (the SURVEY §4 analog of the
reference's fake_cpu_device.h pluggable-backend tests): sharding/collective
semantics are identical to a TPU pod slice, only the transport differs.
Pallas kernels run in interpret mode. The chip is reached only through
`python chip_smoke.py` (README "Running").
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_TRACEBACK_FILTERING", "off")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert jax.default_backend() == "cpu" and len(jax.devices()) == 8


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: subprocess-spawning chaos/integration tests excluded from the "
        "tier-1 run (-m 'not slow')",
    )


import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate_shared_executables():
    """The engine's in-process table of shared executables deliberately
    spans engine instances — which would also span TESTS: an engine built in
    an earlier test would donate buckets to a later test's identical-dims
    engine, breaking exact bucket_stats assertions. Start every test with an
    empty table."""
    from paddle_tpu.inference.engine import clear_shared_executables

    clear_shared_executables()
    yield
