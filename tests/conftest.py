"""Test configuration: force an 8-device CPU platform for every test, so
the multi-chip sharding paths run on XLA's host-platform emulation.  Both
settings must land before the first backend initialization."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
