"""Test configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (benchmark/run.py is what runs on the
real chip, through the chip tool). These env vars must be set before jax
is imported anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_configure(config):
    # Tier-1 runs with `-m "not slow"` (ROADMAP.md); heavy scale scenarios
    # (10k-node simcluster runs) carry this marker so they only run when
    # asked for explicitly: `pytest -m slow tests/test_simcluster.py`.
    config.addinivalue_line(
        "markers", "slow: heavy scale tests excluded from tier-1"
    )


@pytest.fixture(autouse=True, scope="session")
def _drain_device_threads():
    """Interpreter teardown while a daemon thread (coalescer dispatcher,
    a shut-down server's shape prewarm) sits inside an XLA call aborts the
    process with std::terminate AFTER all tests passed — drain device work
    before pytest exits."""
    yield
    from nomad_tpu.ops.coalesce import quiesce_all

    quiesce_all(timeout=20.0)
