"""Pallas flash-attention kernel (interpret mode on the CPU mesh)
vs the dense reference (ref: the transformer.cc fused helpers the
reference hand-writes in CUDA; here the hot kernel is Pallas)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.pallas_kernels import (FLASH_MIN_SEQ, _dense_reference,
                                          flash_attention)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    rng = np.random.default_rng(0)
    B, H, T, D = 2, 2, 512, 32
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, T, D)),
                           jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, force=True,
                          block_q=128, block_k=128)
    ref = _dense_reference(q.reshape(B * H, T, D), k.reshape(B * H, T, D),
                           v.reshape(B * H, T, D), causal,
                           D ** -0.5).reshape(B, H, T, D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_dispatch_policy():
    rng = np.random.default_rng(1)
    # short/untileable sequences -> dense path (same numbers either way)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 100, 16)),
                           jnp.float32) for _ in range(3))
    out = flash_attention(q, k, v)
    assert out.shape == (1, 2, 100, 16)
    # 3-d input form
    q3, k3, v3 = (jnp.asarray(rng.standard_normal((4, 256, 32)),
                              jnp.float32) for _ in range(3))
    out3 = flash_attention(q3, k3, v3, force=True, block_q=128,
                           block_k=128)
    ref3 = _dense_reference(q3, k3, v3, False, 32 ** -0.5)
    np.testing.assert_allclose(np.asarray(out3), np.asarray(ref3),
                               rtol=1e-4, atol=1e-5)


def test_flash_bf16():
    rng = np.random.default_rng(2)
    B, H, T, D = 1, 2, 256, 32
    q, k, v = (jnp.asarray(rng.standard_normal((B, H, T, D)),
                           jnp.bfloat16) for _ in range(3))
    out = flash_attention(q, k, v, causal=True, force=True,
                          block_q=128, block_k=128)
    ref = _dense_reference(
        q.reshape(B * H, T, D).astype(jnp.float32),
        k.reshape(B * H, T, D).astype(jnp.float32),
        v.reshape(B * H, T, D).astype(jnp.float32), True,
        D ** -0.5).reshape(B, H, T, D)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.1, atol=0.05)


def test_contrib_op_registered():
    from mxnet_tpu import nd
    rng = np.random.default_rng(3)
    q = nd.array(rng.standard_normal((1, 2, 64, 16)).astype(np.float32))
    out = nd.contrib.flash_attention(q, q, q, causal=True)
    assert out.shape == (1, 2, 64, 16)


def test_flash_gradients():
    """The kernel path is differentiable (custom VJP: the backward
    kernel), matching dense gradients."""
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 128, 16)), jnp.float32)
               for _ in range(3))

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, force=True,
                                       block_q=64, block_k=64) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, True, 16 ** -0.5) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_flash_chunked_backward_rectangular():
    """Non-causal t_q != t_k through the backward kernel (the (T,T)
    matrix is never materialized; ADVICE r3)."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((2, 128, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 192, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 192, 16)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, force=True,
                                       block_q=64, block_k=64) ** 3)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_reference(q, k, v, False, 16 ** -0.5) ** 3)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# -- the backward kernel against the oracle - ----------------------------------

def _qkv(rng, heads, kv, t_q, t_k, d, dtype):
    draw = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)
    return draw(heads, t_q, d), draw(kv, t_k, d), draw(kv, t_k, d)


def _oracle(q, k, v, causal, scale, window=0):
    """``_dense_reference`` in float32 over K/V repeated for the group."""
    group = q.shape[0] // k.shape[0]
    wide = lambda a: jnp.repeat(a.astype(jnp.float32), group, axis=0)
    return _dense_reference(q.astype(jnp.float32), wide(k), wide(v), causal,
                            scale, window)


# groups of 1, 4, 7 and 8 at the three cells' lanes; not causal: t_q != t_k
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 4e-2)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("group,d", [(1, 64), (4, 64), (7, 128), (8, 256)])
def test_backward_kernel_matches_the_dense_gradient(group, d, causal, dtype,
                                                   tol):
    rng = np.random.default_rng(group * d)
    t_q, t_k = (64, 64) if causal else (32, 96)
    q, k, v = _qkv(rng, 2 * group, 2, t_q, t_k, d, dtype)
    head = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    scale = d ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=32, force=True, interpret=True)

    got = jax.grad(lambda *a: (flash(*a).astype(jnp.float32) * head).sum(),
                   (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_oracle(*a, causal, scale) * head).sum(),
                    (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        assert a.shape == w.shape and a.dtype == dtype
        w = np.asarray(w, np.float32)
        np.testing.assert_allclose(np.asarray(a, np.float32), w, rtol=tol,
                                   atol=tol * np.abs(w).max())


@pytest.mark.parametrize("group", [9, 6])
def test_a_window_one_k_block_wide_over_groups_of_9_and_6(group):
    """Laguna-S-2.1's shape scaled down: 128 lanes, a window of one K
    block, an eighth of the row (512 keys of 4,096 rows at the chip's
    blocks), 72 or 48 query heads over 8 K/V heads. Rows from the window's
    second block on start on a block that they see none of, which the
    forward folds in as all-masked; the backward takes query blocks twice
    the forward's. Forward and gradient against the dense oracle."""
    rng = np.random.default_rng(group)
    q, k, v = _qkv(rng, group, 1, 256, 256, 128, jnp.float32)
    head = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=32,
                               force=True, interpret=True, window=32)

    oracle = lambda q, k, v: _oracle(q, k, v, True, 128 ** -0.5, 32)
    np.testing.assert_allclose(flash(q, k, v), oracle(q, k, v), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * head).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (oracle(*a) * head).sum(), (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(a), w, rtol=1e-4,
                                   atol=1e-4 * np.abs(w).max())


@pytest.mark.parametrize("causal,window", [(False, 0), (True, 0), (True, 1),
                                           (True, 16), (True, 45)])
def test_forward_saves_the_log_sum_exp_of_the_masked_scores(causal, window):
    rng = np.random.default_rng(7)
    t_q, t_k, d = 64, 64 if causal else 96, 16
    q, k, v = _qkv(rng, 4, 2, t_q, t_k, d, jnp.float32)
    out, lse = pk._flash_call(q, k, v, causal, 0.25, 16, 32, True,
                              window=window)
    assert lse.shape == (4, 1, t_q) and lse.dtype == jnp.float32
    s = jnp.einsum("btd,bsd->bts", q * 0.25, jnp.repeat(k, 2, axis=0))
    if causal:
        i, j = jnp.arange(t_q)[:, None], jnp.arange(t_k)[None, :]
        seen = (j <= i) & ((j > i - window) if window else True)
        s = jnp.where(seen, s, -jnp.inf)
    np.testing.assert_allclose(lse[:, 0], jax.nn.logsumexp(s, axis=-1),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out, _oracle(q, k, v, causal, 0.25, window),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("block_q,block_k", [(16, 32), (32, 32), (32, 16)])
@pytest.mark.parametrize("window", [0, 1, 16, 32, 45, 100, 191])
def test_block_ranges_are_the_blocks_the_mask_keeps(block_q, block_k,
                                                    window):
    """``_k_blocks`` (the forward's loop and the backward's) against the
    mask written out: ``[a, d)`` are the blocks with a visible pair,
    ``[b, c)`` those with no hidden one."""
    t = 192
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    n_q, n_k = t // block_q, t // block_k
    tiles = seen.reshape(n_q, block_q, n_k, block_k)
    some, every = tiles.any((1, 3)), tiles.all((1, 3))     # [n_q, n_k]

    def check(bounds, some, every):
        a, b, c, d = (int(x) for x in bounds)
        assert 0 <= a <= b <= c <= d <= len(some)
        assert list(np.flatnonzero(some)) == list(range(a, d))
        assert every[b:c].all()
        # the masked edges hold every block that needs its mask; a block
        # with no hidden pair may sit there only next to one that has
        assert not every[a:b][:-1].any() and not every[c:d][1:].any()

    for qi in range(n_q):
        check(pk._k_blocks(jnp.int32(qi), block_q, block_k, n_k, True,
                           window), some[qi], every[qi])
    assert pk._k_blocks(0, block_q, block_k, n_k, False, 0) == (0, 0, n_k,
                                                                n_k)


def test_backward_is_one_kernel_and_no_scan():
    """The gradient's program: the forward kernel and the backward kernel
    under its own name; no scan, no float32 score tile in HBM, no K/V
    repeated for the group."""
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, 14, 2, 128, 128, 16, jnp.bfloat16)

    def loss(q, k, v):
        return pk._flash_diff(q, k, v, True, 0.25, 32, 64, True,
                              45).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v))
    assert text.count("pallas_call[") == 2
    assert "name=_flash_bwd_call" in text
    for gone in ("scan[", "dynamic_update_slice", "f32[14,32,128]", "f32[14,128,128]",
                 "bf16[14,128,16] = broadcast", "repeat"):
        assert gone not in text, gone


# -- a value width of its own (latent attention) -------------------------------

@pytest.mark.parametrize("group,d_v,causal", [(1, 128, True), (2, 64, False)])
def test_values_of_their_own_width_forward_and_backward(group, d_v, causal):
    """Moonlight-16B-A3B's latent attention scores over 192 lanes (128
    without position, 64 rotary) and reads values of 128: the kernels take
    ``v`` of its own width, 192 as the whole last dimension of a q or k
    block; the output and ``dv`` are ``d_v`` wide, ``dq`` and ``dk`` 192.
    Here also ``d_v`` 64 under grouped heads. Forward and gradient against
    the dense oracle."""
    rng = np.random.default_rng(d_v + group)
    t_q, t_k = (64, 64) if causal else (32, 96)
    q, k, _ = _qkv(rng, 2 * group, 2, t_q, t_k, 192, jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, t_k, d_v)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((2 * group, t_q, d_v)),
                       jnp.float32)
    scale = 192 ** -0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=32, force=True, interpret=True)

    oracle = lambda q, k, v: _oracle(q, k, v, causal, scale)
    out = flash(q, k, v)
    assert out.shape == (2 * group, t_q, d_v)
    np.testing.assert_allclose(out, oracle(q, k, v), rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *a: (flash(*a) * head).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (oracle(*a) * head).sum(), (0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        assert a.shape == w.shape
        w = np.asarray(w)
        np.testing.assert_allclose(np.asarray(a), w, rtol=1e-4,
                                   atol=1e-4 * np.abs(w).max())


def test_the_four_head_layout_passes_the_value_width_through():
    """(B, H, T, D) in, (B, H, T, D_v) out, the dense path alike."""
    rng = np.random.default_rng(11)
    q = jnp.asarray(rng.standard_normal((1, 2, 64, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 64, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 64, 16)), jnp.float32)
    want = _dense_reference(q[0], k[0], v[0], True, 24 ** -0.5)
    for force in (True, False):
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=32,
                              force=force, interpret=True)
        assert out.shape == (1, 2, 64, 16)
        np.testing.assert_allclose(out[0], want, rtol=2e-5, atol=2e-5)


def _plans(q, kv, window, scale):
    """(name, grid, block shapes, VMEM limit) of every Pallas call in the
    gradient of the kernel path at the chip's blocks (256 x 512 forward,
    512 x 512 backward), compiled mode, from the traced program."""
    def loss(q, k, v):
        return pk._flash_diff(q, k, v, True, scale, 256, 512, False,
                              window).astype(jnp.float32).sum()

    out = []
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, kv, kv)

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                gm = eqn.params["grid_mapping"]
                mosaic = eqn.params.get("compiler_params", {}).get(
                    "mosaic_tpu")
                out.append((eqn.params.get("name") or "_flash_call",
                            tuple(gm.grid),
                            [tuple(getattr(b, "block_size", b)
                                   for b in m.block_shape)
                             for m in gm.block_mappings],
                            mosaic and mosaic.vmem_limit_bytes))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return out


# the four language-model cells' attention (heads, keys, lanes over K/V
# heads, window) and what their kernels were planned as before the value
# width became a width of its own: grid, block shapes, the backward's VMEM
@pytest.mark.parametrize("q,kv,window,fwd_grid,bwd_grid,vmem", [
    ((64, 4096, 64), (16, 4096, 64), 0, (64, 16), (16, 4, 8), 30670848),
    ((32, 4096, 256), (4, 4096, 256), 0, (32, 16), (4, 8, 8), 51904512),
    ((28, 8192, 128), (4, 8192, 128), 4096, (28, 32), (4, 7, 16), 49545216),
    ((28, 8192, 128), (4, 8192, 128), 0, (28, 32), (4, 7, 16), 49545216),
    ((72, 4096, 128), (8, 4096, 128), 512, (72, 16), (8, 9, 8), 30670848),
    ((48, 4096, 128), (8, 4096, 128), 0, (48, 16), (8, 6, 8), 30670848)])
def test_the_cells_kernels_are_planned_as_before(q, kv, window, fwd_grid,
                                                 bwd_grid, vmem):
    """Where q, k and v share a width the kernels are the ones the
    language-model cells compiled before latent attention: the same grids,
    block shapes and backward VMEM scope (the forward asks for none)."""
    d, t_k = q[2], kv[1]
    plans = _plans(jax.ShapeDtypeStruct(q, jnp.bfloat16),
                   jax.ShapeDtypeStruct(kv, jnp.bfloat16), window, d ** -0.5)
    assert [p[0] for p in plans] == ["_flash_call", "_flash_bwd_call"]
    (_, grid, blocks, limit), (_, bgrid, bblocks, blimit) = plans
    assert grid == fwd_grid and limit is None
    assert blocks == [(1, 256, d), (1, t_k, d), (1, t_k, d), (1, 256, d),
                      (1, 1, 256)]
    assert bgrid == bwd_grid and blimit == vmem
    assert bblocks == [(1, 512, d)] * 3 + [(1, 1, 512)] + \
        [(1, t_k, d)] * 2 + [(1, 512, d)] + [(1, t_k, d)] * 2


def test_latent_attention_kernels_plan_each_width_apart():
    """At the Moonlight cell's shapes (16 heads, 8,192 keys, 192-lane q and
    k, 128-lane v) the blocks of v, the output, ``do`` and ``dv`` are 128
    lanes and the rest 192; the backward's VMEM counts each operand at its
    own padded width (192 takes two lane tiles)."""
    q = jax.ShapeDtypeStruct((16, 8192, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((16, 8192, 128), jnp.bfloat16)

    def loss(q, k, v):
        return pk._flash_diff(q, k, v, True, 192 ** -0.5, 256, 512, False,
                              0).astype(jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, q, v))
    assert "bf16[16,8192,128]" in text and text.count("pallas_call[") == 2
    need = pk._bwd_vmem_bytes(8192, 192, 512, 512, 2, 128)
    assert need == (8192 + 512) * (256 + 128) * 12 + 6 * 512 * 512 * 4
    assert pk._bwd_vmem_bytes(8192, 128, 512, 512, 2) == \
        pk._bwd_vmem_bytes(8192, 128, 512, 512, 2, 128)
    # K and V of a head at their own widths are inside the dispatch's line
    assert 8192 * (192 + 128) * 2 <= pk.VMEM_BUDGET_BYTES
