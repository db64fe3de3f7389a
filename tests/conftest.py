"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Mirrors how the reference tests multi-process behavior on localhost
(SURVEY.md §4.3): multi-chip sharding logic is exercised on virtual CPU
devices; real-TPU runs are ``python chip_smoke.py`` on the chip.

The platform is pinned through jax.config as well as JAX_PLATFORMS, so a
test run never takes a chip whatever the environment says.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


import pytest  # noqa: E402


@pytest.fixture
def drain_launches():
    """Launches so far of the spatial engine's paging programs (strip
    drains and the all-gather fallback's drain), as a callable."""
    from goworld_tpu.telemetry import sentinel

    return lambda: sum(
        sentinel.launches_total(name) for name in
        ("spatial_drain", "spatial_drain_bits", "sharded_drain"))
