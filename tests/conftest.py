"""Test env: force an 8-device virtual CPU platform.

Mirrors the driver's multi-chip dry-run environment so every sharding test
exercises a real (virtual) device mesh.  ``JAX_PLATFORMS`` selects the
backend; it is set here, before jax is imported, so the tests stay on the
CPU even on a host with a chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

