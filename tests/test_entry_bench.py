"""Smoke tests for the driver entry points and the bench body.

Round 2 shipped a broken `entry()`/`bench.py` (UnexpectedTracerError from
deferred param init inside jax.eval_shape) because nothing in the test
suite exercised them (VERDICT.md round 2, Weak #1). These tests run the
exact code paths the driver runs, on the CPU mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np


def test_entry_runs():
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 1000)
    assert bool(jnp.all(jnp.isfinite(out)))


def test_bench_body_runs():
    """The actual bench harness body: build_forward + timed loop."""
    import bench
    fwd, pvals = bench.build_forward(8)
    pvals = jax.device_put(pvals)
    data = jnp.asarray(
        np.random.default_rng(0).standard_normal(
            (8, 3, 224, 224), dtype=np.float32), dtype=jnp.bfloat16)
    reduce_fn = jax.jit(lambda t: jnp.sum(t.astype(jnp.float32)))

    def sync(out):
        v = float(reduce_fn(out))
        assert np.isfinite(v)
        return v

    ips = bench.measure(fwd, pvals, data, sync, iters=2, warmup=1)
    assert ips > 0


def test_bench_fp32_variant():
    import bench
    fwd, pvals = bench.build_forward(4, dtype=jnp.float32)
    assert all(v.dtype != jnp.bfloat16 for v in pvals)
    out = fwd(jax.device_put(pvals),
              jnp.zeros((4, 3, 224, 224), jnp.float32))
    assert out.shape == (4, 1000)


def test_bench_transformer_section(monkeypatch):
    """The long-context transformer bench body runs end to end (tiny
    config via MXTPU_BENCH_TFM) and reports finite tokens/s and the
    FLOPs per token its caller turns into MFU on a chip."""
    import bench
    monkeypatch.setenv("MXTPU_BENCH_TFM", "2,2,256,64")
    reduce_fn = jax.jit(lambda t: jnp.sum(t.astype(jnp.float32)))

    def sync(o):
        return float(reduce_fn(o))

    extra = {}
    tps = bench._bench_transformer(sync, extra, lambda m: None)
    assert tps > 0 and np.isfinite(tps)
    assert extra["transformer_flops_per_token"] > 0
