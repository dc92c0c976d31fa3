"""Test fixtures. The CPU-mesh bootstrap lives in the root conftest.py
— by test time the process is on the 8-device virtual CPU mesh.
"""
import os

import numpy as np
import pytest

# bench tests must not pay for a real cost-ledger subprocess (ResNet-50
# compiles, minutes of CPU): attribution is opt-in under the suite.
# Tests that prove the ledger wiring set MXTPU_PROFILE_ATTRIB=1
# themselves (tests/test_profiling.py).
os.environ.setdefault("MXTPU_PROFILE_ATTRIB", "0")


@pytest.fixture(autouse=True)
def _seed_all():
    """with_seed() analogue (ref: tests/python/unittest/common.py)."""
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
