"""The tests drive the harness on the CPU at tiny sizes: a temporary
root holds the tiny BENCHMARK.json, the tiny configurations and
workloads, and links to the benchmark's own code directories."""
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def tiny_root(tmp_path):
    root = tmp_path / "root"
    bench = root / "benchmark"
    bench.mkdir(parents=True)
    tiny = os.path.join(HERE, "tiny")
    shutil.copy(os.path.join(tiny, "BENCHMARK.json"), root)
    for d in ("configs", "workloads"):
        shutil.copytree(os.path.join(tiny, d), bench / d)
    shutil.copytree(os.path.join(BENCH, "layer_metrics"),
                    bench / "layer_metrics")
    for d in ("drivers", "lib", "reference"):
        os.symlink(os.path.join(BENCH, d), bench / d)
    os.symlink(os.path.join(ROOT, "mxnet_tpu"), root / "mxnet_tpu")
    return str(root)
