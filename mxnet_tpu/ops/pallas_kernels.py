"""Pallas TPU kernels for the hot ops.

The reference hand-writes its performance-critical kernels (MKL-DNN
primitives, fused CUDA attention helpers in src/operator/contrib/
transformer.cc); here the analogue is Pallas: attention is the
bandwidth-critical op whose naive lowering materializes the (T, T)
score matrix in HBM, and the flash kernel below keeps scores in VMEM
with an online softmax — O(T) memory instead of O(T^2).

The kernel auto-disables off-TPU (interpret mode covers the CPU test
mesh) and falls back to the jnp reference for shapes that don't tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _dense_reference(q, k, v, causal, scale, window=0):
    """jnp fallback, also the numerics oracle for the kernel tests.
    q, k, v: (BH, T, D). ``window`` > 0 (causal only): a query sees the
    last ``window`` keys up to itself and nothing older."""
    s = jnp.einsum("btd,bsd->bts", q * scale, k)
    if causal:
        t_q, t_k = q.shape[1], k.shape[1]
        if t_q > t_k:
            raise ValueError(
                f"causal attention with t_q ({t_q}) > t_k ({t_k}) leaves "
                "queries with no visible keys; pad K/V or drop causal")
        # queries are the LAST t_q positions of the key sequence
        # (decoder convention when t_q != t_k)
        q_pos = jnp.arange(t_q)[:, None] + (t_k - t_q)
        mask = jnp.arange(t_k)[None, :] <= q_pos
        if window:
            mask &= jnp.arange(t_k)[None, :] > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bts,bsd->btd", p, v)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k,
                  causal, scale, window=0):
    """One (batch*head, q-block) program: stream K/V blocks through
    VMEM folding each into an online-softmax accumulator (Dao 2022).
    Under ``window`` the loop starts at the block that holds the oldest
    key the program's first query sees, and both edges are masked."""
    qi = pl.program_id(1)
    # the products take the operands in their own type (bfloat16 rides
    # the MXU in one pass) and accumulate in float32
    q = q_ref[0]                                       # (BQ, D)
    t_k = k_ref.shape[1]
    n_k = t_k // block_k

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]   # (BK, D)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # (BQ, BK)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = k_pos <= q_pos
            if window:
                seen &= k_pos > q_pos - window
            s = jnp.where(seen, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = alpha[:, None] * acc + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, q_ref.shape[2]), jnp.float32)
    if causal:
        # blocks strictly above the diagonal contribute nothing
        n_live = jnp.minimum(((qi + 1) * block_q + block_k - 1)
                             // block_k, n_k)
    else:
        n_live = n_k
    # a row whose window starts past the first block's end folds that
    # block in as all-masked (weight exp(0) under the running maximum
    # NEG_INF); its first visible key then rescales that by exp(-1e30)
    # = 0, and every row sees its own position at the latest
    first = jnp.maximum(qi * block_q - window + 1, 0) // block_k \
        if window else 0
    m, l, acc = jax.lax.fori_loop(first, n_live, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale",
                                             "block_q", "block_k",
                                             "interpret", "window"))
def _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                window=0):
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    t_k = k.shape[1]
    # grouped-query heads: consecutive ``group`` query heads read one
    # K/V head, picked by the index map; nothing is repeated in HBM
    group = bh // k.shape[0]
    grid = (bh, t_q // block_q)
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale,
                               window=window)
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    return pl.pallas_call(
        kernel,
        # under shard_map the output must declare how it varies across
        # mesh axes (vma) — inherit q's
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype,
                                       vma=jax.typeof(q).vma),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0), **mem),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b // group, 0, 0),
                         **mem),
            pl.BlockSpec((1, t_k, d), lambda b, i: (b // group, 0, 0),
                         **mem),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0),
                               **mem),
        interpret=interpret,
    )(q, k, v)


# measured on one TPU chip (B=2 H=8 D=128 bf16, causal): dense wins to
# T=2048, flash 1.4x at 4096, 2.3x at 8192 — the T^2 HBM traffic
# crossover. Below this the fused dense path is optimal.
FLASH_MIN_SEQ = 4096
# this kernel stages full K+V per program in VMEM (~16 MB/core); beyond
# the budget the wrapper falls back to dense rather than fail Mosaic
# allocation. A K-streamed grid dimension would lift this.
VMEM_BUDGET_BYTES = 12 * 1024 * 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_diff(q, k, v, causal, scale, block_q, block_k, interpret,
                window=0):
    return _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                       window=window)


def _flash_diff_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                    window):
    out = _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                      window=window)
    return out, (q, k, v, out)


def _chunked_attention_bwd(q, k, v, out, g, causal, scale, block_q,
                           window=0):
    """FlashAttention-style backward without the (T, T) HBM matrix
    (Dao 2022 §3.1 backward): scan over q-blocks, recomputing each
    (block_q, T_k) score tile from q/k and using D = rowsum(dO ∘ O)
    for the softmax VJP. Peak memory is O(block_q · T_k) per step plus
    the dk/dv carries — the regime where the forward kernel dispatches
    (T ≥ FLASH_MIN_SEQ) no longer OOMs in training.

    Under ``window`` a q-block multiplies against a band of K and V of
    static length (``window + block_q`` rounded up to the block: every
    key its rows see), sliced where the block's last row ends and
    clipped at the sequence's start, and adds its dk, dv into that band:
    a window layer's backward costs its band, not T_k columns."""
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    nb = t_q // block_q
    f32 = jnp.float32
    dD = jnp.sum(g.astype(f32) * out.astype(f32), axis=-1)   # (BH, T_q)
    qs = jnp.swapaxes(q.reshape(bh, nb, block_q, d), 0, 1)
    gs = jnp.swapaxes(g.reshape(bh, nb, block_q, d), 0, 1)
    Ds = jnp.swapaxes(dD.reshape(bh, nb, block_q), 0, 1)
    kf = k.astype(f32)
    vf = v.astype(f32)
    band = -(-(window + block_q) // block_q) * block_q if window else t_k
    banded = band < t_k

    def body(carry, inp):
        dk, dv = carry
        qi, gi, Di, i = inp
        qi = qi.astype(f32)
        gi = gi.astype(f32)
        if banded:
            # the band ends with the block's last row (t_q == t_k)
            lo = jnp.maximum((i + 1) * block_q - band, 0)
            cut = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, band, 1)
            kb, vb = cut(kf), cut(vf)
        else:
            lo, kb, vb = 0, kf, vf
        s = jnp.einsum("bqd,bsd->bqs", qi * scale, kb)
        if causal:
            # forward kernel requires t_q == t_k when causal, so no
            # decoder offset here
            q_pos = i * block_q + jnp.arange(block_q)[:, None]
            k_pos = lo + jnp.arange(kb.shape[1])[None, :]
            seen = k_pos <= q_pos
            if window:
                seen &= k_pos > q_pos - window
            s = jnp.where(seen, s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)                       # (b, bq, Tk)
        dp = jnp.einsum("bqd,bsd->bqs", gi, vb)
        ds = p * (dp - Di[..., None])
        dqi = jnp.einsum("bqs,bsd->bqd", ds, kb) * scale
        dkb = jnp.einsum("bqs,bqd->bsd", ds, qi) * scale
        dvb = jnp.einsum("bqs,bqd->bsd", p, gi)
        if banded:
            add = lambda a, b: jax.lax.dynamic_update_slice_in_dim(
                a, cut(a) + b, lo, 1)
            return (add(dk, dkb), add(dv, dvb)), dqi
        return (dk + dkb, dv + dvb), dqi

    (dk, dv), dq = jax.lax.scan(
        body,
        (jnp.zeros((bh, t_k, d), f32), jnp.zeros((bh, t_k, d), f32)),
        (qs, gs, Ds, jnp.arange(nb)))
    dq = jnp.swapaxes(dq, 0, 1).reshape(bh, t_q, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _flash_diff_bwd(causal, scale, block_q, block_k, interpret, window,
                    res, g):
    q, k, v, out = res
    group = q.shape[0] // k.shape[0]
    if group > 1:
        # the chunked pass wants a K/V row per query head; the groups'
        # gradients are summed back onto the head they share
        dq, dk, dv = _flash_diff_bwd(
            causal, scale, block_q, block_k, interpret, window,
            (q, jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0),
             out), g)
        fold = lambda a, like: a.astype(jnp.float32).reshape(
            like.shape[0], group, *like.shape[1:]).sum(1).astype(like.dtype)
        return dq, fold(dk, k), fold(dv, v)
    if q.shape[1] % block_q:
        # shapes the forward kernel accepted always tile; safety net
        _, vjp = jax.vjp(
            lambda a, b, c: _dense_reference(a, b, c, causal, scale,
                                             window),
            q, k, v)
        return vjp(g)
    return _chunked_attention_bwd(q, k, v, out, g, causal, scale, block_q,
                                  window)


_flash_diff.defvjp(_flash_diff_fwd, _flash_diff_bwd)


# ---------------------------------------------------------------------------
# paged decode attention (serving/generate/ — the KV cache lives in a
# block pool, not a contiguous (B, T, H, D) array)
# ---------------------------------------------------------------------------

def _paged_gather_reference(q, k_cache, v_cache, block_tables, seq_lens,
                            scale):
    """jnp fallback + numerics oracle for the paged kernel: gather each
    sequence's blocks back into a contiguous view and run dense masked
    single-query attention.

    q: (B, H, D) — ONE query token per sequence (the decode step).
    k_cache/v_cache: (num_blocks, block_tokens, H, D) — the pool.
    block_tables: (B, max_blocks) int32 — pool block ids per sequence,
    padded with any valid id (masked out by seq_lens).
    seq_lens: (B,) int32 — tokens visible per sequence (0 = padding
    row: output is garbage and must be discarded by the caller).
    """
    b, n_max = block_tables.shape
    bt = k_cache.shape[1]
    k = jnp.take(k_cache, block_tables, axis=0)     # (B, NB, BT, H, D)
    v = jnp.take(v_cache, block_tables, axis=0)
    k = k.reshape(b, n_max * bt, *k.shape[3:])      # (B, S, H, D)
    v = v.reshape(b, n_max * bt, *v.shape[3:])
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    pos = jnp.arange(n_max * bt)[None, None, :]
    s = jnp.where(pos < seq_lens[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bshd->bhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _paged_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, block_tokens, scale):
    """One (sequence, block) program: the grid's second axis walks the
    sequence's block table (scalar-prefetched, so the BlockSpec index
    map gathers the right pool block into VMEM), folding each block
    into an online-softmax accumulator — flash attention's streaming
    trick applied across non-contiguous pool blocks.

    One query row per head leaves the MXU nothing to tile, and Mosaic
    takes no dot whose batch dim sits at different positions in its
    operands (q is (H, D), a pool block (BT, H, D)). So the scores are
    a VPU multiply + lane reduce over D, kept as (BT, H, 1) so heads
    stay on sublanes and broadcast back over D without a relayout;
    the softmax running state is the matching 2-D (H, 1)."""
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale           # (H, D)
    k_blk = k_ref[0].astype(jnp.float32)               # (BT, H, D)
    v_blk = v_ref[0].astype(jnp.float32)
    s = jnp.sum(q[None] * k_blk, axis=-1, keepdims=True)   # (BT, H, 1)
    pos = i * block_tokens + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    s = jnp.where(pos < lens_ref[b], s, NEG_INF)
    m_prev = m_ref[...]                                 # (H, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    p = jnp.exp(s - m_new[None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=0)
    acc_ref[...] = alpha * acc_ref[...] + jnp.sum(p * v_blk, axis=0)
    m_ref[...] = m_new

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _paged_call(q, k_cache, v_cache, block_tables, seq_lens, scale,
                interpret):
    from jax.experimental.pallas import tpu as pltpu

    b, h, d = q.shape
    bt = k_cache.shape[1]
    n_max = block_tables.shape[1]
    kernel = functools.partial(_paged_kernel, block_tokens=bt,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_max),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda s, i, t, sl: (s, 0, 0)),
            pl.BlockSpec((1, bt, h, d),
                         lambda s, i, t, sl: (t[s, i], 0, 0, 0)),
            pl.BlockSpec((1, bt, h, d),
                         lambda s, i, t, sl: (t[s, i], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda s, i, t, sl: (s, 0, 0)),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, d), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), seq_lens.astype(jnp.int32),
      q, k_cache, v_cache)


def paged_attention(q, k_cache, v_cache, block_tables, seq_lens,
                    scale=None, interpret=None, force=False):
    """Single-query attention over a paged KV cache (the decode-step
    kernel of serving/generate/, sibling of :func:`flash_attention`).

    q: (B, H, D) — the current token's query per in-flight sequence.
    k_cache/v_cache: (num_blocks, block_tokens, H, D) block pool.
    block_tables: (B, max_blocks) int32 pool block ids per sequence
    (rows padded with any valid block id). seq_lens: (B,) int32
    visible tokens; a 0 row is batch padding — its output is garbage
    by contract and the caller discards it.

    The one dispatch rule: on the ``tpu`` backend every head shape
    takes the Pallas kernel (the block gather is the HBM-bound half of
    decode; one program per (sequence, block) streams the table's
    blocks through VMEM) — a shape Mosaic refuses is a compile error,
    never a quiet switch to the reference. Elsewhere it is the jnp
    gather reference, unless ``force`` (parity tests run the kernel in
    interpret mode).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if force or not interpret:
        return _paged_call(q, k_cache, v_cache, block_tables, seq_lens,
                           float(scale), bool(interpret))
    return _paged_gather_reference(q, k_cache, v_cache, block_tables,
                                   seq_lens, float(scale))


# ---------------------------------------------------------------------------
# INT8 conv/FC epilogue: requantize(+relu) over int32 MXU accumulators
# (the compute body of the serving `native` INT8 lowering and of the
# subgraph rule `XLA/quantize_conv_requantize` — ops/quantized.py
# requantize + quantized_act is the numerics oracle)
# ---------------------------------------------------------------------------

# the quantization range constants ARE ops/quantized.py's — one
# source, so the kernel and its oracle cannot drift
from .quantized import INT8_RANGE, INT32_RANGE  # noqa: E402


def _int8_epilogue_reference(acc2d, in_scale, out_scale, relu):
    """jnp fallback + numerics oracle body: EXACTLY requantize-inl.h's
    `clip(rint(acc_f32 * in_scale * out_scale))` (same multiply order
    as ops/quantized.requantize, so parity is bitwise), then the int8
    relu passthrough of quantized_act."""
    q = jnp.clip(jnp.rint(acc2d.astype(jnp.float32) * in_scale
                          * out_scale),
                 -INT8_RANGE, INT8_RANGE).astype(jnp.int8)
    if relu:
        q = jnp.maximum(q, 0)
    return q


def _int8_epilogue_kernel(in_s_ref, out_s_ref, acc_ref, o_ref, *, relu):
    """One row-block program: int32 accumulators stream HBM→VMEM once,
    the requantize multiply + round + clip (+relu) runs on the VPU, and
    only int8 leaves — a quarter of the f32 write traffic the unfused
    dequantize/quantize round-trip pays."""
    a = acc_ref[...].astype(jnp.float32)
    q = jnp.rint(a * in_s_ref[0, 0] * out_s_ref[0, 0])
    q = jnp.clip(q, -INT8_RANGE, INT8_RANGE)
    if relu:
        q = jnp.maximum(q, 0.0)
    o_ref[...] = q.astype(jnp.int8)


def _row_block(m, candidates=(2048, 1024, 512, 256, 128, 64, 32, 16, 8)):
    for bm in candidates:
        if m % bm == 0:
            return bm
    return None


@functools.partial(jax.jit, static_argnames=("relu", "interpret"))
def _int8_epilogue_call(acc2d, in_scale, out_scale, relu, interpret):
    from jax.experimental.pallas import tpu as pltpu

    m, n = acc2d.shape
    bm = _row_block(m) or m
    kernel = functools.partial(_int8_epilogue_kernel, relu=relu)
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    smem = {} if interpret else {"memory_space": pltpu.SMEM}
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int8),
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), **smem),
            pl.BlockSpec((1, 1), lambda i: (0, 0), **smem),
            pl.BlockSpec((bm, n), lambda i: (i, 0), **mem),
        ],
        out_specs=pl.BlockSpec((bm, n), lambda i: (i, 0), **mem),
        interpret=interpret,
    )(in_scale.reshape(1, 1).astype(jnp.float32),
      out_scale.reshape(1, 1).astype(jnp.float32), acc2d)


def int8_conv_epilogue(acc, in_scale, out_scale, relu=False,
                       interpret=None, force=False):
    """Elementwise requantize(+relu) of int32 accumulators to int8.

    acc: any-shape int32. in_scale/out_scale: f32 scalars (float or
    0-d array; in_scale = one int32 ulp in fp, out_scale = 127 / the
    calibrated output range — the requantize-inl.h convention).
    Dispatches to the Pallas kernel on chip backends (or ``force`` —
    parity tests run it in interpret mode) and to the jnp reference
    otherwise; shapes whose trailing dims don't flatten to a multiple
    of 128 always take the reference path.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    in_scale = jnp.asarray(in_scale, jnp.float32)
    out_scale = jnp.asarray(out_scale, jnp.float32)
    size = acc.size
    # a row count no block candidate divides would make the whole
    # array ONE block — unbounded VMEM; take the reference instead
    tiles = (size % 128 == 0 and size >= 1024
             and _row_block(size // 128) is not None)
    if tiles and (force or not interpret):
        q2d = _int8_epilogue_call(acc.reshape(-1, 128), in_scale,
                                  out_scale, bool(relu),
                                  bool(interpret))
        return q2d.reshape(acc.shape)
    return _int8_epilogue_reference(acc, in_scale, out_scale,
                                    bool(relu))


def quantized_conv_epilogue(acc, min_range, max_range,
                            min_calib_range=None, max_calib_range=None,
                            relu=False, interpret=None, force=False):
    """The full requantize(+int8 relu) epilogue with range plumbing:
    the drop-in tail of ``_sg_xla_quant_conv`` and the serving native
    lowering, returning ``(int8, min, max)`` exactly like
    ops/quantized.requantize (+quantized_act). The scale bookkeeping
    mirrors requantize-inl.h; the elementwise body dispatches through
    :func:`int8_conv_epilogue`."""
    real_range = jnp.maximum(jnp.abs(min_range), jnp.abs(max_range))
    in_scale = real_range / INT32_RANGE
    if min_calib_range is not None:
        out_max = jnp.float32(max(abs(float(min_calib_range)),
                                  abs(float(max_calib_range))))
    else:
        out_max = jnp.max(jnp.abs(acc)).astype(jnp.float32) * in_scale
    out_scale = INT8_RANGE / jnp.maximum(out_max, 1e-30)
    q = int8_conv_epilogue(acc, in_scale, out_scale, relu=relu,
                           interpret=interpret, force=force)
    omin, omax = -out_max, out_max
    if relu:
        zero = jnp.zeros((), jnp.float32)
        omin, omax = jnp.maximum(omin, zero), jnp.maximum(omax, zero)
    return q, omin, omax


# ---------------------------------------------------------------------------
# fused optimizer updates: one kernel = one HBM pass over
# weight/grad/state for sgd_mom and adam (ops/optimizer_ops.py is the
# numerics oracle; the jnp fallback below restates its exact formulas)
# ---------------------------------------------------------------------------


def _clip_grad(g, clip):
    # clip_gradient < 0 disables (the dmlc param convention)
    if clip is not None and clip >= 0:
        return jnp.clip(g, -clip, clip)
    return g


def _sgd_mom_reference(weight, grad, mom, lr, momentum, wd, rescale,
                       clip):
    """= ops/optimizer_ops.sgd_mom_update, restated for the fallback
    (kept in lockstep by the tier-1 parity test)."""
    g = _clip_grad(rescale * grad, clip)
    mom = momentum * mom - lr * wd * weight - lr * g
    return weight + mom, mom


def _adam_reference(weight, grad, mean, var, lr, beta1, beta2, eps,
                    wd, rescale, clip):
    """= ops/optimizer_ops.adam_update (no in-kernel bias correction —
    the Python optimizer folds it into lr)."""
    g = _clip_grad(rescale * grad + wd * weight, clip)
    mean = beta1 * mean + (1.0 - beta1) * g
    var = beta2 * var + (1.0 - beta2) * jnp.square(g)
    out = weight - lr * mean / (jnp.sqrt(var) + eps)
    return out, mean, var


def _sgd_mom_kernel(w_ref, g_ref, m_ref, ow_ref, om_ref, *, lr,
                    momentum, wd, rescale, clip):
    w = w_ref[...]
    g = _clip_grad(rescale * g_ref[...], clip)
    m = momentum * m_ref[...] - lr * wd * w - lr * g
    ow_ref[...] = w + m
    om_ref[...] = m


def _adam_kernel(w_ref, g_ref, mean_ref, var_ref, ow_ref, omean_ref,
                 ovar_ref, *, lr, beta1, beta2, eps, wd, rescale, clip):
    w = w_ref[...]
    g = _clip_grad(rescale * g_ref[...] + wd * w, clip)
    mean = beta1 * mean_ref[...] + (1.0 - beta1) * g
    var = beta2 * var_ref[...] + (1.0 - beta2) * jnp.square(g)
    ow_ref[...] = w - lr * mean / (jnp.sqrt(var) + eps)
    omean_ref[...] = mean
    ovar_ref[...] = var


@functools.partial(jax.jit, static_argnames=("kind", "hyper",
                                             "interpret"))
def _fused_opt_call(kind, arrays2d, hyper, interpret):
    from jax.experimental.pallas import tpu as pltpu

    m, n = arrays2d[0].shape
    bm = _row_block(m) or m
    h = dict(hyper)
    if kind == "sgd_mom":
        kernel = functools.partial(_sgd_mom_kernel, **h)
        n_out = 2
    else:
        kernel = functools.partial(_adam_kernel, **h)
        n_out = 3
    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    spec = pl.BlockSpec((bm, n), lambda i: (i, 0), **mem)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((m, n), arrays2d[0].dtype)
                   for _ in range(n_out)],
        grid=(m // bm,),
        in_specs=[spec] * len(arrays2d),
        out_specs=[spec] * n_out,
        interpret=interpret,
    )(*arrays2d)


def _fused_opt_dispatch(kind, weight, arrays, hyper, reference,
                        interpret, force):
    """Common wrapper: flatten to (rows, 128) f32, run one kernel pass,
    reshape back; anything that doesn't tile (or a non-f32 master
    dtype) takes the jnp reference — the CPU hot path and the oracle."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = all(a.dtype == jnp.float32 for a in arrays)
    # see int8_conv_epilogue: an undividable row count must fall back,
    # never become one whole-array VMEM block
    tiles = (f32 and weight.size % 128 == 0 and weight.size >= 1024
             and _row_block(weight.size // 128) is not None)
    if tiles and (force or not interpret):
        shape = weight.shape
        arrays2d = tuple(a.reshape(-1, 128) for a in arrays)
        outs = _fused_opt_call(kind, arrays2d,
                               tuple(sorted(hyper.items())),
                               bool(interpret))
        return tuple(o.reshape(shape) for o in outs)
    return reference(*arrays, **hyper)


def fused_sgd_mom(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, interpret=None,
                  force=False):
    """sgd_mom_update as ONE memory pass: w/g/mom stream HBM→VMEM once
    and (w', mom') stream back — instead of the elementwise chain's
    multiple reads under op-granular dispatch. Exact formula of
    ops/optimizer_ops.sgd_mom_update (the oracle)."""
    hyper = {"lr": float(lr), "momentum": float(momentum),
             "wd": float(wd), "rescale": float(rescale_grad),
             "clip": float(clip_gradient)}
    return _fused_opt_dispatch("sgd_mom", weight, (weight, grad, mom),
                               hyper, _sgd_mom_reference, interpret,
                               force)


def fused_adam(weight, grad, mean, var, lr=0.01, beta1=0.9,
               beta2=0.999, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
               clip_gradient=-1.0, interpret=None, force=False):
    """adam_update as ONE memory pass over weight/grad/mean/var.
    Exact formula of ops/optimizer_ops.adam_update (the oracle)."""
    hyper = {"lr": float(lr), "beta1": float(beta1),
             "beta2": float(beta2), "eps": float(epsilon),
             "wd": float(wd), "rescale": float(rescale_grad),
             "clip": float(clip_gradient)}
    return _fused_opt_dispatch("adam", weight,
                               (weight, grad, mean, var), hyper,
                               _adam_reference, interpret, force)


def flash_attention(q, k, v, causal=False, scale=None, block_q=256,
                    block_k=512, interpret=None, force=False, window=0):
    """Blockwise attention, O(T) memory. q, k, v: (B, H, T, D) or
    (BH, T, D); k and v may have fewer heads than q (grouped-query
    attention: H a multiple of their head count, query head h reading
    K/V head h // group). Dispatches to the Pallas kernel for long sequences
    (>= FLASH_MIN_SEQ, where it beats XLA's dense lowering by the
    measured margins above) and to the dense jnp path otherwise or when
    the sequence doesn't tile; `force=True` always takes the kernel
    (tests). ``window`` > 0 (causal only; 0 = none): query t sees keys
    t - window + 1 ... t, itself among them; the kernel skips the blocks
    before the window as it skips those past the diagonal, the backward
    works on a band, and the dense path takes the same mask. A window
    that covers the whole sequence is plain causal attention."""
    squeeze = False
    if q.ndim == 4:
        b, h, t, d = q.shape
        q = q.reshape(b * h, t, d)
        k = k.reshape(b * k.shape[1], k.shape[2], d)
        v = v.reshape(b * v.shape[1], v.shape[2], d)
        squeeze = (b, h)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    t_q, t_k = q.shape[1], k.shape[1]
    window = int(window or 0)
    if window < 0 or (window and not causal):
        raise ValueError(f"flash_attention: window {window} needs causal "
                         "attention and a length >= 0")
    if window >= t_k:
        window = 0          # every causal pair is inside it
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tiles = not (t_q % block_q or t_k % block_k or
                 (causal and t_q != t_k))
    if 2 * t_k * q.shape[-1] * q.dtype.itemsize > VMEM_BUDGET_BYTES:
        tiles = False  # K+V won't fit VMEM; see VMEM_BUDGET_BYTES
    if interpret and jax.typeof(q).vma:
        # pallas interpret mode cannot propagate shard_map
        # varying-axis metadata through its dynamic slices
        # (jax issue); the CPU test mesh takes the dense path —
        # compiled TPU kernels are unaffected
        tiles = False
    if tiles and (force or t_q >= FLASH_MIN_SEQ):
        out = _flash_diff(q, k, v, bool(causal), float(scale),
                          int(block_q), int(block_k), bool(interpret),
                          window)
    else:
        group = q.shape[0] // k.shape[0]
        if group > 1:
            k, v = jnp.repeat(k, group, axis=0), jnp.repeat(v, group, axis=0)
        out = _dense_reference(q, k, v, causal, scale, window)
    if squeeze:
        b, h = squeeze
        out = out.reshape(b, h, t_q, -1)
    return out
