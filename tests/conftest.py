"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding paths
(tp/dp/sp) compile and execute without TPU hardware, and pin JAX to the CPU
for this process and every subprocess the tests spawn.
"""

import os

# XLA_FLAGS is read lazily when the CPU client is created, so setting it here
# (before any jax operation) works.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("DYNTPU_LOG", "warning")
# Subprocesses spawned by tests (sdk serve supervisor etc.) run on the CPU too.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
