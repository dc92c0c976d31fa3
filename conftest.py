"""Root conftest: put the test run on an 8-device virtual CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): distributed paths
are exercised on a local virtual "cluster" — here 8 virtual CPU devices
via --xla_force_host_platform_device_count, so sharding/collective code
compiles and runs without TPU hardware. Both settings are read when the
jax backend starts, which is after this file is imported (conftest
modules load before any test module imports jax), and subprocesses the
tests start inherit them.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()
