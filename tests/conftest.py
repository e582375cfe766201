"""Test configuration: force a virtual 8-device CPU mesh.

The reference exercises distributed behavior on Flink's in-process
mini-cluster (multiple local subtasks — SURVEY.md §4). The moral equivalent
here: JAX's host-platform device partitioning, giving 8 virtual CPU devices
so every sharding/collective path compiles and runs without TPU hardware.

Both settings are environment variables JAX reads when it is first
imported, so this must run before jax is imported anywhere in the test
process. The suite ALWAYS runs on the CPU, whatever the machine holds:
the chip is exercised by ``chip_smoke.py``, not by pytest.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest


@pytest.fixture
def sample_edges():
    """The canonical 7-edge / 5-vertex sample graph every reference operation
    test uses (``test/GraphStreamTestUtils.java:56-67``)."""
    return [
        (1, 2, 12.0),
        (1, 3, 13.0),
        (2, 3, 23.0),
        (3, 4, 34.0),
        (3, 5, 35.0),
        (4, 5, 45.0),
        (5, 1, 51.0),
    ]
