"""Test fixtures. The CPU-mesh bootstrap lives in the root conftest.py
— by test time the process is on the 8-device virtual CPU mesh.
"""
import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed_all():
    """with_seed() analogue (ref: tests/python/unittest/common.py)."""
    np.random.seed(0)
    import mxnet_tpu as mx
    mx.random.seed(0)
    yield
