"""Test harness: simulate a multi-chip TPU mesh with 8 virtual CPU devices.

This is the TPU-build replacement for the reference's Docker-based COINSTAC
simulator (SURVEY.md §4): N local containers + 1 remote container on one machine
become N virtual jax devices on a "site" mesh axis.

Env vars must be set before jax initializes — hence module level, before any
jax import.
"""

import os

# the tests run on the CPU whatever the machine has: a chip belongs to one
# process at a time, and the suite spawns workers
os.environ["JAX_PLATFORMS"] = "cpu"
# subprocess workers (tests/dcn_worker.py, CLI smokes) inherit the device
# count through the environment; this process takes it from the config knob
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", False)
