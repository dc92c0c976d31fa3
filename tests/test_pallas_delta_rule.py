"""The gated delta rule's Pallas kernels (``ops/pallas_kernels.py``
``delta_rule``: ``_gdn_fwd_call``, ``_gdn_bwd_call``) in interpret mode on
the CPU, at heads of 128 lanes with two value heads a key head: forward and
the five gradients against the token recurrence
(``benchmark/reference/qwen3_next.py`` ``delta_rule``) with the decay near
1, ordinary and near 0, at two chunks, three chunks and a length that needs
padding; at other groupings of the heads; in bfloat16; against the XLA path
on the same inputs; through the registered op and ``autograd``; the
dispatch's shape rule. Interpret mode cannot see what Mosaic refuses:
``tests/test_chip_compile.py`` compiles the same kernels at the published
widths."""
import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.ops import nn
from mxnet_tpu.ops import pallas_kernels as pk

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import qwen3_next as ref  # noqa: E402

ARGS = ("query", "key", "value", "g", "beta")
HEAD = jnp.cos(jnp.arange(128.0))


def _inputs(t, g_scale, seed=0, b=1, hk=1, hv=2, dk=128, dv=128):
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (arr(b, t, hk, dk), arr(b, t, hk, dk), arr(b, t, hv, dv),
            -g_scale * jnp.asarray(rng.random((b, t, hv)), jnp.float32),
            jnp.asarray(rng.random((b, t, hv)), jnp.float32))


def _recurrence(q, k, v, g, beta):
    hv, dk = v.shape[2], q.shape[3]
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) / dk ** 0.5, hv // q.shape[2], axis=2)
    k = jnp.repeat(unit(k), hv // k.shape[2], axis=2)
    return jnp.stack([ref.delta_rule(q[i], k[i], v[i], g[i], beta[i])
                      for i in range(q.shape[0])])


def _kernels(*args):
    return nn._delta_rule(*args, 64, 1e-6, interpret=True, force=True)


def _loss(fn):
    return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * HEAD)


@functools.lru_cache(maxsize=None)
def _gradients(t, g_scale, which):
    """The five gradients at float32 under ``highest``, by the kernels
    (``kernels``), the XLA path (``scan``) or the recurrence."""
    fn = {"kernels": _kernels, "recurrence": _recurrence,
          "scan": lambda *a: nn._delta_rule_scan(*a, 64, 1e-6)}[which]
    with jax.default_matmul_precision("highest"):
        return jax.grad(_loss(fn), argnums=range(5))(
            *_inputs(t, g_scale, seed=t))


# two chunks, three, and two and a part (the last chunk padded); the decay
# near 1 (where A is largest), ordinary, and near 0 (g down to -20 a token)
@pytest.mark.parametrize("g_scale", [0.01, 1.0, 20.0])
@pytest.mark.parametrize("t", [128, 192, 150])
def test_kernels_against_the_token_recurrence(t, g_scale):
    args = _inputs(t, g_scale, seed=t, b=2)
    with jax.default_matmul_precision("highest"):
        got, want = _kernels(*args), _recurrence(*args)
    assert got.shape == want.shape == (2, t, 2, 128)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
@pytest.mark.parametrize("g_scale", [0.01, 1.0, 20.0])
@pytest.mark.parametrize("t", [128, 150])
def test_kernels_gradients_against_the_token_recurrence(t, g_scale, arg):
    got = _gradients(t, g_scale, "kernels")[arg]
    want = _gradients(t, g_scale, "recurrence")[arg]
    np.testing.assert_allclose(got, want, rtol=1e-3,
                               atol=1e-4 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("arg", range(5), ids=ARGS)
def test_kernels_and_the_xla_path_agree_on_the_same_inputs(arg):
    """One algorithm behind one op: what a shape that falls back computes
    is what the kernels compute, rounding for rounding."""
    got = _gradients(150, 0.01, "kernels")[arg]
    want = _gradients(150, 0.01, "scan")[arg]
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_kernels_and_the_xla_path_give_the_same_output():
    args = _inputs(150, 0.01, seed=150, b=2)
    with jax.default_matmul_precision("highest"):
        got = _kernels(*args)
        want = nn._delta_rule_scan(*args, 64, 1e-6)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


# one value head a key head, four, and two key heads of two: a program
# serves every value head of its key head and sums their dq, dk
@pytest.mark.parametrize("hk,hv", [(2, 2), (1, 4), (2, 4)])
def test_kernels_at_other_groupings_of_the_heads(hk, hv):
    args = _inputs(150, 0.3, seed=hk + hv, hk=hk, hv=hv)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_loss(_kernels), argnums=range(5))(*args)
        want = jax.value_and_grad(
            _loss(lambda *a: nn._delta_rule_scan(*a, 64, 1e-6)),
            argnums=range(5))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, w in zip(got[1], want[1]):
        np.testing.assert_allclose(a, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


def test_kernels_with_eight_chunks_to_a_program():
    """512 tokens are eight chunks: one program a (batch, key head), as at
    the published shape, where a program takes eight of 64."""
    args = _inputs(512, 1.0, seed=8)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_loss(_kernels), argnums=range(5))(*args)
        want = jax.value_and_grad(
            _loss(lambda *a: nn._delta_rule_scan(*a, 64, 1e-6)),
            argnums=range(5))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for a, w in zip(got[1], want[1]):
        np.testing.assert_allclose(a, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


# a chunk of one 16 x 16 block, of two, and of eight (three merges)
@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_kernels_at_other_chunk_lengths(chunk):
    args = _inputs(300, 0.01, seed=chunk)
    rule = lambda *a: nn._delta_rule(*a, chunk, 1e-6, interpret=True,
                                     force=True)
    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(_loss(rule), argnums=range(5))(*args)
        want = jax.value_and_grad(
            _loss(lambda *a: nn._delta_rule_scan(*a, chunk, 1e-6)),
            argnums=range(5))(*args)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5)
    for a, w in zip(got[1], want[1]):
        np.testing.assert_allclose(a, w, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("g_scale", [0.01, 1.0, 20.0])
def test_kernels_in_bfloat16_keep_the_type_and_stay_close(g_scale):
    """Forward and gradients with ``value``'s type bfloat16 against the
    float32 recurrence on the same (rounded) inputs: 3 % of the largest
    value, the XLA path's own line."""
    args = _inputs(150, g_scale, seed=4)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    wide = [a.astype(jnp.float32) for a in low]
    got = _kernels(*low)
    assert got.dtype == jnp.bfloat16
    want = _recurrence(*wide)
    far = lambda a, w: float(jnp.abs(a.astype(jnp.float32) - w).max()) / \
        float(jnp.abs(w).max())
    assert far(got, want) < 0.03
    grads = jax.grad(_loss(_kernels), argnums=range(5))(*low)
    wants = jax.grad(_loss(_recurrence), argnums=range(5))(*wide)
    for name, x, a, w in zip(ARGS, low, grads, wants):
        assert a.dtype == x.dtype, name
        assert far(a, w) < 0.03, name


def test_kernels_through_the_registered_op_and_autograd(monkeypatch):
    monkeypatch.setattr(nn, "_delta_rule", functools.partial(
        nn._delta_rule, interpret=True, force=True))
    args = [mx.nd.array(np.asarray(a)) for a in _inputs(100, 0.5, seed=7)]
    for a in args:
        a.attach_grad()
    with autograd.record():
        out = mx.nd.GatedDeltaRule(*args)
        loss = (out * out).sum()
    loss.backward()
    data = [a._data for a in args]
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: nn.gated_delta_rule(*a))(*data))
    want = jax.grad(lambda *a: (_recurrence(*a) ** 2).sum(),
                    argnums=range(5))(*data)
    for a, w in zip(args, want):
        np.testing.assert_allclose(a.grad.asnumpy(), w, rtol=2e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("dk,dv,chunk,admitted", [
    (128, 128, 64, True), (256, 128, 64, True), (128, 128, 32, True),
    (16, 128, 64, False), (128, 8, 64, False), (128, 128, 7, False),
    (128, 128, 48, False)])
def test_the_dispatch_is_a_rule_of_shapes(dk, dv, chunk, admitted):
    assert pk.delta_rule_tiles(dk, dv, chunk) is admitted


def test_off_the_chip_the_op_takes_the_xla_path():
    """No TPU behind the default backend: the registered op runs the scan,
    whatever the shape (the kernels in interpret mode are for tests)."""
    text = str(jax.make_jaxpr(lambda *a: nn.gated_delta_rule(*a))(
        *_inputs(128, 1.0)))
    assert "pallas_call" not in text and "scan" in text
