"""The sequence-model ops (RMSNorm and its zero-centred weight, rotary
encoding over all or the first ``rotary_dim`` lanes, SwiGLU, short causal
convolution with and without its silu, grouped-query attention) against
``jax.numpy`` written out, forward and gradient, through the registered
ops."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops import registry

RNG = np.random.default_rng(11)


def _arr(*shape):
    return RNG.standard_normal(shape).astype(np.float32)


# -- written out ------------------------------------------------------------

def rms_norm(x, g, eps=1e-5):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rms_norm_zero_centered(x, g, eps=1e-6):
    return rms_norm(x, 1 + g, eps)


def rope(x, theta=1e6, lanes=None):
    _, t, _, d = x.shape
    d = lanes or d
    out = []
    for i in range(d):
        j = i % (d // 2)
        ang = jnp.arange(t) * theta ** (-2.0 * j / d)
        pair = -x[..., i + d // 2] if i < d // 2 else x[..., i - d // 2]
        out.append(x[..., i] * jnp.cos(ang)[None, :, None]
                   + pair * jnp.sin(ang)[None, :, None])
    return jnp.concatenate([jnp.stack(out, -1), x[..., d:]], -1)


def rope_first_4_lanes(x):
    return rope(x, 1e7, lanes=4)


def swiglu(a, b):
    return a / (1 + jnp.exp(-a)) * b


def causal_conv_silu(x, w):
    y = causal_conv(x, w)
    return y / (1 + jnp.exp(-y))


def causal_conv(x, w):
    b, t, c = x.shape
    width = w.shape[1]
    out = jnp.zeros_like(x)
    for j in range(width):
        shift = width - 1 - j
        piece = jnp.concatenate(
            [jnp.zeros((b, shift, c)), x[:, :t - shift]], 1)
        out = out + piece * w[:, j]
    return out


def gq_attention(q, k, v):
    b, t, h, d = q.shape
    group = h // k.shape[2]
    out = []
    for i in range(h):
        s = jnp.einsum("btd,bsd->bts", q[:, :, i], k[:, :, i // group])
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s / d ** 0.5, -1e30)
        out.append(jnp.einsum("bts,bsd->btd", jax.nn.softmax(s, -1),
                              v[:, :, i // group]))
    return jnp.stack(out, 2)


CASES = {
    "RMSNorm": (rms_norm, lambda: (_arr(2, 5, 16), 1 + 0.1 * _arr(16)), {}),
    "RMSNorm zero_centered": (rms_norm_zero_centered,
                              lambda: (_arr(2, 5, 16), 0.1 * _arr(16)),
                              {"eps": 1e-6, "zero_centered": True}),
    "RotaryEmbedding": (rope, lambda: (_arr(2, 7, 3, 8),),
                        {"theta": 1e6}),
    "RotaryEmbedding rotary_dim": (rope_first_4_lanes,
                                   lambda: (_arr(2, 7, 3, 16),),
                                   {"theta": 1e7, "rotary_dim": 4}),
    "CausalConv1D silu": (causal_conv_silu,
                          lambda: (_arr(2, 9, 6), _arr(6, 4)),
                          {"activation": "silu"}),
    "SwiGLU": (swiglu, lambda: (_arr(3, 6, 10), _arr(3, 6, 10)), {}),
    "CausalConv1D": (causal_conv, lambda: (_arr(2, 9, 6), _arr(6, 3)), {}),
    "GQAttention": (gq_attention,
                    lambda: (_arr(2, 6, 4, 8), _arr(2, 6, 2, 8),
                             _arr(2, 6, 2, 8)), {"causal": True}),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_forward_against_jax_numpy_written_out(name):
    want_fn, make, attrs = CASES[name]
    args = make()
    got = getattr(mx.nd, name.split()[0])(*[mx.nd.array(a) for a in args],
                                          **attrs)
    want = want_fn(*[jnp.asarray(a) for a in args])
    np.testing.assert_allclose(got.asnumpy(), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_gradient_against_jax_numpy_written_out(name):
    """Through autograd's tape: every input's gradient of a weighted sum
    of the output."""
    want_fn, make, attrs = CASES[name]
    args = make()
    nds = [mx.nd.array(a) for a in args]
    for a in nds:
        a.attach_grad()
    with autograd.record():
        out = getattr(mx.nd, name.split()[0])(*nds, **attrs)
        head = mx.nd.array(_arr(*out.shape))
        loss = (out * head).sum()
    loss.backward()
    want = jax.grad(
        lambda *xs: (want_fn(*xs) * head._data).sum(),
        argnums=tuple(range(len(args))))(*[jnp.asarray(a) for a in args])
    for a, w in zip(nds, want):
        np.testing.assert_allclose(a.grad.asnumpy(), np.asarray(w),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("name", sorted(CASES))
def test_bfloat16_keeps_its_type_and_stays_close(name):
    """Storage type in, storage type out; statistics in float32 keep a
    16-bit call within 16-bit rounding of the float32 one."""
    want_fn, make, attrs = CASES[name]
    args = make()
    op = registry.get(name.split()[0])
    got = op(*[jnp.asarray(a, jnp.bfloat16) for a in args], **attrs)
    assert got.dtype == jnp.bfloat16
    want = want_fn(*[jnp.asarray(a) for a in args])
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 0.05 * scale


def test_fully_connected_out_dtype_gives_float32_logits_and_gradients():
    x, w = _arr(4, 6, 16), _arr(32, 16)
    xb, wb = (mx.nd.array(a).astype("bfloat16") for a in (x, w))
    for a in (xb, wb):
        a.attach_grad()
    with autograd.record():
        out = mx.nd.FullyConnected(xb, wb, no_bias=True, flatten=False,
                                   out_dtype="float32")
        loss = (out * out).sum()
    loss.backward()
    assert out.dtype == np.float32
    assert xb.grad.dtype == wb.grad.dtype == jnp.bfloat16
    want = jnp.asarray(xb._data, jnp.float32) @ \
        jnp.asarray(wb._data, jnp.float32).T
    np.testing.assert_allclose(out.asnumpy(), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


# -- the flash kernel: grouped K/V heads through the index map ----------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("heads,kv", [(4, 4), (4, 1), (8, 2)])
def test_flash_kernel_grouped_heads_forward_and_gradient(heads, kv, dtype,
                                                         tol):
    b, t, d = 2, 64, 16
    q = jnp.asarray(_arr(b, heads, t, d), dtype)
    k = jnp.asarray(_arr(b, kv, t, d), dtype)
    v = jnp.asarray(_arr(b, kv, t, d), dtype)

    def dense(q, k, v):
        rep = lambda a: jnp.repeat(a, heads // kv, axis=1)
        return pk._dense_reference(
            q.reshape(b * heads, t, d).astype(jnp.float32),
            rep(k).reshape(b * heads, t, d).astype(jnp.float32),
            rep(v).reshape(b * heads, t, d).astype(jnp.float32),
            True, d ** -0.5).reshape(b, heads, t, d)

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, block_q=16,
                                  block_k=32, force=True, interpret=True)

    got, want = flash(q, k, v), dense(q, k, v)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)
    head = jnp.asarray(_arr(b, heads, t, d))
    g_got = jax.grad(lambda *a: (flash(*a).astype(jnp.float32) * head).sum(),
                     (0, 1, 2))(q, k, v)
    g_want = jax.grad(lambda *a: (dense(*a) * head).sum(), (0, 1, 2))(
        q, k, v)
    for a, w in zip(g_got, g_want):
        assert a.shape == w.shape and a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(w, np.float32),
                                   rtol=5 * tol, atol=5 * tol)


# -- window attention: kernel, dense path and the backward kernel --------------

def _masked_oracle(q, k, v, window):
    """The masked dense attention written out: [b, heads, t, d] over
    [b, kv, t, d]; query i sees keys i - window + 1 ... i."""
    heads, kv, t, d = q.shape[1], k.shape[1], q.shape[2], q.shape[3]
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    s = jnp.einsum("bhtd,bhsd->bhts", q, k) * d ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = (j <= i) & (j > i - window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhts,bhsd->bhtd", p, v)


def _window_inputs(heads, kv, t, d, dtype=jnp.float32, b=1):
    return (jnp.asarray(_arr(b, heads, t, d), dtype),
            jnp.asarray(_arr(b, kv, t, d), dtype),
            jnp.asarray(_arr(b, kv, t, d), dtype))


# a single key, a window of one block, one that ends inside a block, one
# longer than two, and two that cover the sequence; 14 query heads over 2
# K/V heads are groups of 7
@pytest.mark.parametrize("window", [1, 32, 45, 100, 256, 300])
@pytest.mark.parametrize("heads,kv", [(14, 2), (4, 4)])
def test_window_kernel_forward_and_banded_backward(heads, kv, window):
    t, d = 256, 16
    q, k, v = _window_inputs(heads, kv, t, d)

    def flash(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=32, block_k=64, force=True,
                                  interpret=True)

    np.testing.assert_allclose(flash(q, k, v),
                               _masked_oracle(q, k, v, window),
                               rtol=2e-5, atol=2e-5)
    head = jnp.asarray(_arr(1, heads, t, d))
    got = jax.grad(lambda *a: (flash(*a) * head).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_masked_oracle(*a, window) * head).sum(),
                    (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, rtol=1e-4, atol=1e-4)


def test_banded_backward_takes_a_band_and_not_every_column():
    """With a window the backward kernel visits the blocks of the band and
    no others: a gradient poisoned outside the band (NaN in every query row
    that no kept pair joins to the K block under test) leaves that block's
    ``dk``, ``dv`` as they were, and a query block's ``dq`` never reads the
    K blocks before its window or past its diagonal."""
    t, d, window, block_q, block_k = 512, 16, 70, 32, 64
    q, k, v = (a[0] for a in _window_inputs(2, 2, t, d))
    kw = dict(causal=True, scale=0.25, block_q=block_q, block_k=block_k,
              interpret=True, window=window)
    out, lse = pk._flash_call(q, k, v, **kw)
    do = jnp.asarray(_arr(2, t, d))
    want = pk._flash_bwd_call(q, k, v, out, lse, do, **kw)
    # K block 2 holds keys 128 ... 191: queries 128 ... 260 see one of them,
    # inside query blocks 4 ... 8; every other block's rows are poisoned
    rows = jnp.arange(t) // block_q
    far = (rows < 4) | (rows > 8)
    _, dk, dv = pk._flash_bwd_call(
        q, k, v, out, lse, jnp.where(far[None, :, None], jnp.nan, do), **kw)
    np.testing.assert_array_equal(dk[:, 128:192], want[1][:, 128:192])
    np.testing.assert_array_equal(dv[:, 128:192], want[2][:, 128:192])
    assert not np.isfinite(np.asarray(dk[:, :64])).any()
    # query block 8 (rows 256 ... 287) sees keys 187 ... 287: K blocks 2 ... 4
    cols = jnp.arange(t) // block_k
    dq, _, _ = pk._flash_bwd_call(
        q, jnp.where(((cols < 2) | (cols > 4))[None, :, None], jnp.nan, k),
        v, out, lse, do, **kw)
    np.testing.assert_array_equal(dq[:, 256:288], want[0][:, 256:288])


@pytest.mark.parametrize("window", [3, 40])
def test_window_on_the_dense_path_and_through_the_registered_op(window):
    b, t, heads, kv, d = 2, 48, 14, 2, 8
    q, k, v = _arr(b, t, heads, d), _arr(b, t, kv, d), _arr(b, t, kv, d)
    args = [mx.nd.array(a) for a in (q, k, v)]
    for a in args:
        a.attach_grad()
    with autograd.record():
        out = mx.nd.GQAttention(*args, causal=True, window=window)
        loss = (out * out).sum()
    loss.backward()
    swap = lambda a: jnp.swapaxes(jnp.asarray(a), 1, 2)
    oracle = lambda q, k, v: jnp.swapaxes(
        _masked_oracle(swap(q), swap(k), swap(v), window), 1, 2)
    np.testing.assert_allclose(out.asnumpy(), oracle(q, k, v), rtol=2e-5,
                               atol=2e-5)
    want = jax.grad(lambda *a: (oracle(*a) ** 2).sum(), (0, 1, 2))(
        *(jnp.asarray(a) for a in (q, k, v)))
    for a, w in zip(args, want):
        np.testing.assert_allclose(a.grad.asnumpy(), w, rtol=1e-4,
                                   atol=1e-4)


@pytest.mark.parametrize("window", [128, 129, 4096])
@pytest.mark.parametrize("force", [True, False])
def test_a_window_that_covers_the_sequence_is_plain_causal_to_the_bit(
        window, force):
    q, k, v = _window_inputs(14, 2, 128, 16, jnp.bfloat16)
    kw = dict(causal=True, block_q=32, block_k=64, force=force,
              interpret=True)
    loss = lambda fn: lambda *a: fn(*a).astype(jnp.float32).sum()
    with_w = lambda *a: pk.flash_attention(*a, window=window, **kw)
    plain = lambda *a: pk.flash_attention(*a, **kw)
    np.testing.assert_array_equal(np.asarray(with_w(q, k, v), np.float32),
                                  np.asarray(plain(q, k, v), np.float32))
    for a, w in zip(jax.grad(loss(with_w), (0, 1, 2))(q, k, v),
                    jax.grad(loss(plain), (0, 1, 2))(q, k, v)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(w, np.float32))


def test_window_needs_causal_attention():
    q, k, v = _window_inputs(2, 2, 64, 8)
    with pytest.raises(ValueError):
        pk.flash_attention(q, k, v, causal=False, window=8)


def test_reglu_is_relu_of_the_gate_times_up():
    g, u = _arr(5, 12), _arr(5, 12)
    got = mx.nd.SwiGLU(mx.nd.array(g), mx.nd.array(u), act="relu").asnumpy()
    np.testing.assert_allclose(got, np.maximum(g, 0) * u, rtol=1e-6)
    with pytest.raises(mx.MXNetError):
        mx.nd.SwiGLU(mx.nd.array(g), mx.nd.array(u), act="gelu")
