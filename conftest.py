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


def pytest_configure(config):
    """``pytest tests/`` (the tier-1 command) also collects the tests of
    the benchmark that judges every PR, each case as a test of its own,
    with their own ``conftest.py``. They come first so that the longest
    file (the harness end to end, minutes on one worker) overlaps the
    rest of the run. Every xdist worker runs this hook again on the
    controller's arguments, hence the second condition."""
    root = os.path.dirname(os.path.abspath(__file__))
    bench_tests = os.path.join(root, "benchmark", "tests")
    args = [os.path.abspath(a) for a in config.args]
    if os.path.join(root, "tests") in args and bench_tests not in args:
        config.args.insert(0, bench_tests)
