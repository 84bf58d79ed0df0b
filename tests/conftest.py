"""Test bootstrap: a CPU JAX backend with 8 virtual devices.

Tests must be hermetic and exercise multi-chip sharding on a virtual CPU
mesh (SURVEY.md §4), whatever accelerator the host holds. Both settings
must be in the environment before JAX makes its backends.
"""
import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Hermetic CPU env for training SUBPROCESSES spawned by e2e tests (this
# process's own backend is pinned to CPU above; subprocesses need the env).
CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "",
}
