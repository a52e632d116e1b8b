"""Test env: force an 8-device virtual CPU platform.

Mirrors the driver's multi-chip dry-run environment so every sharding test
exercises a real (virtual) device mesh.  ``JAX_PLATFORMS`` selects the
backend; it is set here, before jax is imported, so the tests stay on the
CPU even on a host with a chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The tests hold the product to references and do not time it: XLA does
# as little optimisation as it will (a quarter of tier-1's CPU seconds:
# ROADMAP C16), here and in the jobs the tests launch;
# ``tests/tpu_compile.one_chip`` turns it back on for the described chip.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
