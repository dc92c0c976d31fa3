"""Smoke tests for the driver entry point and the fixture programs.

Round 2 shipped a broken `entry()` (UnexpectedTracerError from
deferred param init inside jax.eval_shape) because nothing in the test
suite exercised it (VERDICT.md round 2, Weak #1). These tests run the
exact code paths the driver runs, on the CPU mesh.
"""
import os
import sys

import jax
import jax.numpy as jnp

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def test_entry_runs():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 1000)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_build_forward_fp32_variant():
    sys.path.insert(0, TOOLS)
    import programs
    fwd, pvals = programs.build_forward(4, dtype=jnp.float32)
    assert all(v.dtype != jnp.bfloat16 for v in pvals)
    out = fwd(jax.device_put(pvals),
              jnp.zeros((4, 3, 224, 224), jnp.float32))
    assert out.shape == (4, 1000)
