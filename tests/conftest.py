"""Test config: force JAX onto a virtual 8-device CPU mesh so sharding
paths compile and run without TPU hardware, whatever JAX_PLATFORMS says
(the chip path runs through chip_smoke.py instead).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
