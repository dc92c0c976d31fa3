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
    q, k: (BH, T, D), v: (BH, T, D_v). ``window`` > 0 (causal only): a query sees the
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


def _seen(q0, k0, shape, window):
    """The causal (and window) mask of one [queries, keys] score tile
    whose first query is ``q0`` and first key ``k0``."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    seen = k_pos <= q_pos
    if window:
        seen &= k_pos > q_pos - window
    return seen


def _k_blocks(qi, block_q, block_k, n_k, causal, window):
    """The K blocks the rows of query block ``qi`` see (the forward's loop
    and the backward's), as four bounds ``a <= b <= c <= d``: blocks
    ``[a, d)`` hold a visible key, and those of ``[b, c)`` hold no hidden
    one (they need no mask)."""
    if not causal:
        return 0, 0, n_k, n_k
    lo, hi = qi * block_q, (qi + 1) * block_q
    # blocks strictly above the diagonal contribute nothing, nor do
    # those that end before the window of the block's first row
    a = jnp.maximum(lo - window + 1, 0) // block_k if window else 0
    d = jnp.minimum((hi + block_k - 1) // block_k, n_k)
    b = (jnp.maximum(hi - window, 0) + block_k - 1) // block_k \
        if window else 0
    b = jnp.minimum(jnp.maximum(b, a), d)
    c = jnp.minimum(jnp.maximum((lo + 1) // block_k, b), d)
    return a, b, c, d


def _masked_then_plain(bounds, step, carry):
    """Run ``step(masked)`` over the blocks of ``bounds``: with the mask
    on the edges, without it in between."""
    a, b, c, d = bounds
    for lo, hi, masked in ((a, b, True), (b, c, False), (c, d, True)):
        if isinstance(lo, int) and isinstance(hi, int) and lo == hi:
            continue                    # not causal: no edge to mask
        carry = jax.lax.fori_loop(lo, hi, step(masked), carry)
    return carry


_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _row_of(col):
    """An (n, 1) column as a (1, n) row without a relayout (Mosaic's own,
    from sublanes to lanes, costs the forward kernel 13 % at 256 rows a
    program; this, 2 - 5 %: PERF.md PR 35): 128 rows at a time on the
    diagonal of a square tile, summed over the sublanes. Exact: every
    sum adds zeros to one value."""
    n = col.shape[0]
    w = min(n, 128)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (w, w), 0) ==
           jax.lax.broadcasted_iota(jnp.int32, (w, w), 1))
    return jnp.concatenate(
        [jnp.sum(jnp.where(eye, col[c:c + w], 0.0), axis=0, keepdims=True)
         for c in range(0, n, w)], axis=1)


def _dot(a, b, dims):
    # the products take the operands in their own type (bfloat16 rides
    # the MXU in one pass) and accumulate in float32
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_q, block_k,
                  causal, scale, window=0):
    """One (batch*head, q-block) program: stream K/V blocks through
    VMEM folding each into an online-softmax accumulator (Dao 2022).
    Under ``window`` the loop starts at the block that holds the oldest
    key the program's first query sees, and both edges are masked.
    Beside the output it writes each row's log-sum-exp, the one
    statistic the backward kernel rebuilds the weights from. V and the
    output have lanes of their own (D_v), which may differ from the
    scores' (D)."""
    qi = pl.program_id(1)
    q = q_ref[0]                                       # (BQ, D)
    n_k = k_ref.shape[1] // block_k

    def body(j, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(j * block_k, block_k), :]   # (BK, D)
        v_blk = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = _dot(q, k_blk, _NT) * scale                    # (BQ, BK)
        if causal:
            s = jnp.where(_seen(qi * block_q, j * block_k, s.shape,
                                window), s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = alpha[:, None] * acc + _dot(p.astype(v_blk.dtype), v_blk,
                                              _NN)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    a0 = jnp.zeros((block_q, v_ref.shape[2]), jnp.float32)
    # a row whose window starts past the first block's end folds that
    # block in as all-masked (weight exp(0) under the running maximum
    # NEG_INF); its first visible key then rescales that by exp(-1e30)
    # = 0, and every row sees its own position at the latest
    first, _, _, n_live = _k_blocks(qi, block_q, block_k, n_k, causal,
                                    window)
    m, l, acc = jax.lax.fori_loop(first, n_live, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l[:, None]).astype(o_ref.dtype)
    lse_ref[0] = _row_of((m + jnp.log(l))[:, None])


def _kernel_specs(interpret):
    """BlockSpec with the kernels' memory space (none in interpret mode)
    and the output declaration that inherits an operand's varying mesh
    axes (under shard_map an output must say how it varies)."""
    from jax.experimental.pallas import tpu as pltpu

    mem = {} if interpret else {"memory_space": pltpu.VMEM}
    spec = lambda shape, index: pl.BlockSpec(shape, index, **mem)
    like = lambda shape, dtype, a: jax.ShapeDtypeStruct(
        shape, dtype, vma=jax.typeof(a).vma)
    return spec, like


@functools.partial(jax.jit, static_argnames=("causal", "scale",
                                             "block_q", "block_k",
                                             "interpret", "window"))
def _flash_call(q, k, v, causal, scale, block_q, block_k, interpret,
                window=0):
    """-> (out [bh, t_q, d_v], lse [bh, 1, t_q] float32); q and k are
    ``d`` lanes wide, v ``d_v``."""
    bh, t_q, d = q.shape
    t_k, d_v = k.shape[1], v.shape[2]
    # grouped-query heads: consecutive ``group`` query heads read one
    # K/V head, picked by the index map; nothing is repeated in HBM
    group = bh // k.shape[0]
    kernel = functools.partial(_flash_kernel, block_q=block_q,
                               block_k=block_k, causal=causal, scale=scale,
                               window=window)
    spec, like = _kernel_specs(interpret)
    return pl.pallas_call(
        kernel,
        out_shape=(like((bh, t_q, d_v), q.dtype, q),
                   like((bh, 1, t_q), jnp.float32, q)),
        grid=(bh, t_q // block_q),
        in_specs=[
            spec((1, block_q, d), lambda b, i: (b, i, 0)),
            spec((1, t_k, d), lambda b, i: (b // group, 0, 0)),
            spec((1, t_k, d_v), lambda b, i: (b // group, 0, 0)),
        ],
        out_specs=(spec((1, block_q, d_v), lambda b, i: (b, i, 0)),
                   spec((1, 1, block_q), lambda b, i: (b, 0, i))),
        interpret=interpret,
    )(q, k, v)


def _flash_bwd_kernel(q_ref, do_ref, o_ref, lse_ref, k_ref, v_ref, dq_ref,
                      dk_ref, dv_ref, dk_acc, dv_acc, *, block_q, block_k,
                      causal, scale, window):
    """One (K/V head, query head of its group, query block) program, the
    forward's own loop over the K blocks its rows see (Dao 2022,
    algorithm 4, in one pass): the score tile again from q and k, the
    weights from the saved log-sum-exp (no second softmax), then the
    five products. ``dq`` accumulates here and is written once; ``dk``
    and ``dv`` accumulate in float32 over the whole key length in VMEM,
    across every program of the K/V head (its ``group`` query heads
    among them: nothing is repeated or folded in HBM), and are written
    once, by the last. ``dq``, ``dk`` are D lanes wide as q and k are,
    ``dv`` D_v as v, ``do`` and ``o`` are."""
    g, qi = pl.program_id(1), pl.program_id(2)
    q, do = q_ref[0], do_ref[0]                        # (BQ, D), (BQ, D_v)
    lse = lse_ref[0, 0][:, None]                       # (BQ, 1)
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)

    @pl.when((g == 0) & (qi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        def body(j, dq):
            cols = pl.ds(j * block_k, block_k)
            k_blk, v_blk = k_ref[0, cols, :], v_ref[0, cols, :]
            s = _dot(q, k_blk, _NT) * scale            # (BQ, BK)
            if masked:
                s = jnp.where(_seen(qi * block_q, j * block_k, s.shape,
                                    window), s, NEG_INF)
            p = jnp.exp(s - lse)
            ds = (p * (_dot(do, v_blk, _NT) - delta)).astype(q.dtype)
            dv_acc[cols, :] += _dot(p.astype(do.dtype), do, _TN)
            dk_acc[cols, :] += _dot(ds, q, _TN)
            return dq + _dot(ds, k_blk, _NN)
        return body

    dq = _masked_then_plain(
        _k_blocks(qi, block_q, block_k, k_ref.shape[1] // block_k, causal,
                  window),
        step, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)

    @pl.when((g == pl.num_programs(1) - 1) & (qi == pl.num_programs(2) - 1))
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _lanes(d):
    """A row of ``d`` lanes as VMEM holds it: whole 128-lane tiles."""
    return -(-d // 128) * 128


def _bwd_vmem_bytes(t_k, d, block_q, block_k, itemsize, d_v=None):
    """What one backward program keeps in VMEM, each operand at its own
    width (q, k and their gradients ``d`` lanes, v, o, do and ``dv``
    ``d_v``; a row pads to whole lane tiles): K and V of its head and the
    ``dk``, ``dv`` blocks staged twice (the pipeline's two buffers), the
    two float32 accumulators, the per-block operands twice, and the
    float32 tiles of a step (scores, weights, their gradients)."""
    lanes = _lanes(d) + _lanes(d if d_v is None else d_v)
    per_row = lanes * (2 * 2 * itemsize + 4)
    return (t_k + block_q) * per_row + 6 * block_q * block_k * 4


@functools.partial(jax.jit, static_argnames=("causal", "scale",
                                             "block_q", "block_k",
                                             "interpret", "window"))
def _flash_bwd_call(q, k, v, out, lse, do, causal, scale, block_q, block_k,
                    interpret, window=0):
    """-> (dq, dk, dv) from the forward's residuals and ``do``, in one
    kernel (:func:`_flash_bwd_kernel`); ``dv``, ``o`` and ``do`` are v's
    width, ``dq`` and ``dk`` q's."""
    from jax.experimental.pallas import tpu as pltpu

    bh, t_q, d = q.shape
    bh_k, t_k, _ = k.shape
    d_v = v.shape[2]
    group = bh // bh_k
    spec, like = _kernel_specs(interpret)
    rows = lambda h, g, i: (h * group + g, i, 0)
    head = lambda h, g, i: (h, 0, 0)
    # Mosaic's default scope holds 16 MiB; the accumulators alone are
    # 8 MiB at 8,192 x 128. Ask for what the shapes need and its half
    # again for the compiler's own temporaries.
    need = _bwd_vmem_bytes(t_k, d, block_q, block_k, q.dtype.itemsize, d_v)
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=need + need // 2)}
    return pl.pallas_call(
        functools.partial(_flash_bwd_kernel, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          window=window),
        out_shape=(like(q.shape, q.dtype, q), like(k.shape, k.dtype, k),
                   like(v.shape, v.dtype, v)),
        grid=(bh_k, group, t_q // block_q),
        in_specs=[
            spec((1, block_q, d), rows),
            spec((1, block_q, d_v), rows),
            spec((1, block_q, d_v), rows),
            spec((1, 1, block_q), lambda h, g, i: (h * group + g, 0, i)),
            spec((1, t_k, d), head),
            spec((1, t_k, d_v), head),
        ],
        out_specs=(spec((1, block_q, d), rows), spec((1, t_k, d), head),
                   spec((1, t_k, d_v), head)),
        scratch_shapes=[pltpu.VMEM((t_k, d), jnp.float32),
                        pltpu.VMEM((t_k, d_v), jnp.float32)],
        interpret=interpret,
        # the instruction's name in a capture, whatever transform wraps
        # the call; the forward's events keep ``_flash_call``
        name="_flash_bwd_call",
        **params,
    )(q, do, out, lse, k, v)


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
                       window=window)[0]


def _flash_diff_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                    window):
    out, lse = _flash_call(q, k, v, causal, scale, block_q, block_k,
                           interpret, window=window)
    return out, (q, k, v, out, lse)


def _flash_diff_bwd(causal, scale, block_q, block_k, interpret, window,
                    res, g):
    # the backward's query block is twice the forward's where that tiles
    # (a program's read-modify-write of the dk, dv accumulators and its
    # K, V block loads are shared by twice the rows: 512 x 512 tiles at
    # the defaults; measured, PERF.md PR 35)
    if res[0].shape[1] % (2 * block_q) == 0:
        block_q *= 2
    return _flash_bwd_call(*res, g, causal, scale, block_q, block_k,
                           interpret, window=window)


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


# ---------------------------------------------------------------------------
# the gated delta rule (ops/nn.py ``gated_delta_rule`` owns the dispatch and
# the chunked XLA path, which is the oracle of these kernels' tests)
# ---------------------------------------------------------------------------

# the same products with a leading batch axis: a program's chunks
_BNT = (((2,), (2,)), ((0,), (0,)))
_BNN = (((2,), (1,)), ((0,), (0,)))
_BTN = (((1,), (1,)), ((0,), (0,)))


def _mm(a, b, dims):
    """A product accumulated in float32: 16-bit operands in one pass of the
    MXU, float32 operands at full float32 precision."""
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
        else None)


def _chunk_iota(c):
    return (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0),
            jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _col_of(row):
    """[n, 1, c] rows as [n, c, 1] columns: each row on the diagonal of a
    square tile, summed over the lanes (exact, as :func:`_row_of`)."""
    ii, jj = _chunk_iota(row.shape[-1])
    return jnp.sum(jnp.where(ii == jj, row, 0.0), axis=-1, keepdims=True)


_SOLVE_BLOCK = 16


def _diagonal_inverses(a):
    """The inverses of ``I +`` the 16 x 16 diagonal blocks of ``a``
    [n, c, c], block-diagonal [n, c, c], by forward substitution proper
    on the VPU: every block's rows on the same 16 sublanes, each block at
    its own lanes ([n, 16, c]: a chunk's blocks fill two registers), and
    with ``T = I`` column after column ``T <- T - a[:, j] T[j, :]``."""
    n, c = a.shape[0], a.shape[-1]
    w = _SOLVE_BLOCK
    lane = jax.lax.broadcasted_iota(jnp.int32, (w, c), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (w, c), 0)
    own = jnp.zeros((n, w, c), jnp.float32)
    for r in range(c // w):
        own = jnp.where(lane // w == r, a[:, r * w:(r + 1) * w, :], own)
    # column j of every block along its block's lanes, for every j at once:
    # a product with zeros and ones, exact over the three bfloat16 pieces
    # of a float32
    src = jax.lax.broadcasted_iota(jnp.int32, (c, (w - 1) * c), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (c, (w - 1) * c), 1)
    pick = jnp.where((src // w == (dst % c) // w) & (src % w == dst // c),
                     1.0, 0.0).astype(jnp.bfloat16)
    rest, cols = own.reshape(n * w, c), 0.0
    for _ in range(3):
        piece = rest.astype(jnp.bfloat16)
        rest = rest - piece.astype(jnp.float32)
        cols = cols + _dot(piece, pick, _NN)
    cols = cols.reshape(n, w, (w - 1) * c)
    t = jnp.where(lane % w == row, 1.0, 0.0) + jnp.zeros_like(own)
    for j in range(w - 1):
        t = t - cols[:, :, j * c:(j + 1) * c] * t[:, j:j + 1, :]
    return jnp.concatenate(
        [jnp.where(lane // w == r, t, 0.0) for r in range(c // w)], axis=1)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular float32 ``a`` [n, c, c],
    c = 16 * 2^m (Mosaic has no triangular solve): the 16 x 16 diagonal
    blocks by forward substitution, then pairs of blocks merged by products
    at full float32 precision, ``[[T1, 0], [-T2 a21 T1, T2]]``, until one
    block is left."""
    c = a.shape[-1]
    t = _diagonal_inverses(a)
    size = _SOLVE_BLOCK
    while size < c:
        # of a pair of blocks of ``size`` rows the second alone changes, at
        # the first's lanes. The second blocks of every pair at once, half
        # a chunk's rows: a's (their pair's lanes alone) times T, laid back
        # on their rows, then their own rows of T times that
        blocks = lambda x: [x[:, r:r + size] for r in range(0, x.shape[1],
                                                            size)]
        second = lambda x: jnp.concatenate(blocks(x)[1::2], axis=1)
        half = (c // 2, c)
        pair = (jax.lax.broadcasted_iota(jnp.int32, half, 1) // size ==
                2 * (jax.lax.broadcasted_iota(jnp.int32, half, 0) // size))
        x = _mm(jnp.where(pair, second(a), 0.0), t, _BNN)
        zero = jnp.zeros_like(x[:, :size])
        on_rows = jnp.concatenate(
            [y for block in blocks(x) for y in (zero, block)], axis=1)
        new = second(t) - _mm(second(t), on_rows, _BNN)
        t = jnp.concatenate(
            [y for both in zip(blocks(t)[::2], blocks(new)) for y in both],
            axis=1)
        size *= 2
    return t


def _gdn_keys(q_ref, k_ref, per, eps, lo):
    """A program's ``per`` chunks of its key head, [per, c, dk]: q and k
    at unit length in float32 (q scaled by ``dk^-1/2`` besides), what the
    gradient of that needs, and the two products every value head of the
    key head shares."""
    def unit(ref):
        x = ref[0].astype(jnp.float32)
        x = x.reshape(per, x.shape[0] // per, x.shape[1])
        r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)
        return x * r, r

    scale = q_ref.shape[2] ** -0.5
    (q_unit, q_by), (k, k_by) = unit(q_ref), unit(k_ref)
    q = q_unit * scale
    ql, kl = q.astype(lo), k.astype(lo)
    return dict(q=q, k=k, q_unit=q_unit, q_by=q_by * scale, k_by=k_by, ql=ql,
                kl=kl, gram=_mm(kl, kl, _BNT), qk=_mm(ql, kl, _BNT))


def _gdn_local(keys, v, g_row, b_row, t=None):
    """What is local to each of a value head's n chunks of the rule
    (``ops/nn.py`` ``gated_delta_rule``), all n at once so that one chunk's
    products fill the MXU while another's are on their way: ``keys`` from
    :func:`_gdn_keys`, ``v`` [n, c, dv], ``g_row`` (the decay's running sum
    inside the chunk) and ``b_row`` [n, 1, c] float32. Every exponent is of
    a difference that is <= 0. The products take their operands in ``v``'s
    type. ``t``: the inverses the forward saved, where the caller has
    them."""
    f32, lo = jnp.float32, v.dtype
    q, k = keys["q"], keys["k"]
    c = q.shape[1]
    ii, jj = _chunk_iota(c)
    g_col, b_col = _col_of(g_row), _col_of(b_row)
    decay = jnp.exp(jnp.where(ii >= jj, g_col - g_row, -jnp.inf))
    a = jnp.where(ii > jj, b_col * keys["gram"] * decay, 0.0)
    if t is None:
        t = _unit_lower_inverse(a)
    e_g = jnp.exp(g_col)
    g_last = jnp.sum(jnp.where(jj[:1] == c - 1, g_row, 0.0), axis=-1,
                     keepdims=True)                     # [n, 1, 1]
    e_out = jnp.exp(g_last - g_col)
    rhs = jnp.concatenate([k * (b_col * e_g), v.astype(f32) * b_col], axis=2)
    return dict(
        b_col=b_col, decay=decay, a=a, t=t, e_g=e_g, e_out=e_out,
        wu=_mm(t, rhs, _BNN).astype(lo),
        p=(keys["qk"] * decay).astype(lo), q_in=(q * e_g).astype(lo),
        k_out=(k * e_out).astype(lo), keep=jnp.exp(g_last),
        # Mosaic broadcasts along sublanes or along lanes, not both
        keep_row=jnp.exp(jnp.broadcast_to(g_last, g_last.shape[:2] +
                                          (v.shape[2],))))


def _gdn_fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, per,
                    eps):
    """One (batch, key head, ``per`` chunks) program for the value heads
    the key head serves: q and k are read and normalised once for them, the
    chunks of a row come one after another, and each value head's state
    [dk, dv] stays in VMEM between them, zeroed at the row's first. Where
    the gradient will follow, each chunk's incoming state and its inverse
    ``(I + A)^-1`` are written out beside ``o`` (the inverse is the larger
    part of a chunk's local work and a quarter of a state's bytes)."""
    s_ref = rest[-1]
    saved, t_ref = rest[:2] if len(rest) == 3 else (None, None)
    heads, dk, dv = s_ref.shape
    c = q_ref.shape[1] // per
    lo = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    keys = _gdn_keys(q_ref, k_ref, per, eps, lo)
    for h in range(heads):
        lanes = slice(h * dv, (h + 1) * dv)
        x = _gdn_local(keys, v_ref[0, :, lanes].reshape(per, c, dv),
                       g_ref[0, h], b_ref[0, h])
        if t_ref is not None:
            t_ref[0, h] = x["t"]
        s = s_ref[h]
        for j in range(per):
            if saved is not None:
                saved[0, h, j] = s
            sl = s.astype(lo)
            wu = x["wu"][j]
            v_new = (wu[:, dk:].astype(jnp.float32) -
                     _mm(wu[:, :dk], sl, _NN)).astype(lo)
            o_ref[0, j * c:(j + 1) * c, lanes] = (
                _mm(x["q_in"][j], sl, _NN) +
                _mm(x["p"][j], v_new, _NN)).astype(o_ref.dtype)
            s = x["keep_row"][j] * s + _mm(x["k_out"][j], v_new, _TN)
        s_ref[h] = s


def _gdn_bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, t_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, per,
                    eps):
    """The forward's programs in reverse: the chunks' local arrays again
    from their inputs and the saved inverses, then chunk before chunk with
    the state that came into it and the gradient of the state [dk, dv] in
    VMEM, zero after a row's last. The chain through the inverse needs no
    second one: with ``T = (I + A)^-1`` and ``[W | U] = T R``, ``dR = T^T
    [dW | dU]`` and ``dA = -tril(dR [W | U]^T)``. A key head's ``dq`` and
    ``dk`` are summed here over the value heads it serves and taken back
    through the unit lengths; the decay's and ``beta``'s gradients leave as
    rows."""
    f32 = jnp.float32
    heads, dk, dv = ds_ref.shape
    c = q_ref.shape[1] // per
    lo = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    keys = _gdn_keys(q_ref, k_ref, per, eps, lo)
    q, k = keys["q"], keys["k"]
    rowsum = lambda y: jnp.sum(y, axis=-1, keepdims=True)
    colsum = lambda y: jnp.sum(y, axis=-2, keepdims=True)
    ii, jj = _chunk_iota(c)
    d_q = d_k = 0.0
    for h in range(heads):
        lanes = slice(h * dv, (h + 1) * dv)
        v = v_ref[0, :, lanes].reshape(per, c, dv)
        x = _gdn_local(keys, v, g_ref[0, h], b_ref[0, h], t_ref[0, h])
        # chunk before chunk: what the state's gradient passes through
        ds = ds_ref[h]
        d_qin, d_p, d_kout, d_wu, d_keep = ([None] * per for _ in range(5))
        for j in reversed(range(per)):
            do, s = do_ref[0, j * c:(j + 1) * c, lanes], s_ref[0, h, j]
            w, u = x["wu"][j][:, :dk], x["wu"][j][:, dk:]
            sl, dsl = s.astype(lo), ds.astype(lo)
            v_new = (u.astype(f32) - _mm(w, sl, _NN)).astype(lo)
            # o = q_in s + p v_new;  s' = keep s + k_out^T v_new
            d_qin[j] = _mm(do, sl, _NT)
            d_p[j] = _mm(do, v_new, _NT)
            d_kout[j] = _mm(v_new, dsl, _NT)
            d_vnew = _mm(x["p"][j], do, _TN) + _mm(x["k_out"][j], dsl, _NN)
            d_keep[j] = jnp.sum(rowsum(ds * s), axis=0, keepdims=True)
            # v_new = u - w s
            d_vl = d_vnew.astype(lo)
            d_wu[j] = jnp.concatenate([-_mm(d_vl, sl, _NT), d_vnew], axis=1)
            ds = (x["keep_row"][j] * ds + _mm(x["q_in"][j], do, _TN) -
                  _mm(w, d_vl, _TN))
        ds_ref[h] = ds
        d_qin, d_p, d_kout, d_wu, d_keep = (
            jnp.stack(y) for y in (d_qin, d_p, d_kout, d_wu, d_keep))
        # the rest is local to a chunk again, all of them at once:
        # [w | u] = T rhs, T = (I + a)^-1
        d_rhs = _mm(x["t"], d_wu, _BTN)
        d_a = jnp.where(ii > jj, -_mm(d_rhs.astype(lo), x["wu"], _BNT), 0.0)
        d_rw, d_ru = d_rhs[:, :, :dk], d_rhs[:, :, dk:]
        b_col, e_g, e_out = x["b_col"], x["e_g"], x["e_out"]
        from_w = rowsum(d_rw * k) * e_g
        d_b = from_w + rowsum(d_ru * v.astype(f32))
        d_g = from_w * b_col
        # a = beta gram decay, p = qk decay: an entry's share of the
        # decay's gradient goes to its row's gamma and from its column's
        d_b = d_b + rowsum(d_a * keys["gram"] * x["decay"])
        by_decay = d_a * x["a"] + d_p * (keys["qk"] * x["decay"])
        d_g = d_g + rowsum(by_decay)
        d_g_row = -colsum(by_decay)
        d_gram = (d_a * b_col * x["decay"]).astype(lo)
        d_qk = (d_p * x["decay"]).astype(lo)
        d_q = d_q + _mm(d_qk, keys["kl"], _BNN) + d_qin * e_g
        d_k = (d_k + d_rw * (b_col * e_g) + _mm(d_qk, keys["ql"], _BTN) +
               _mm(d_gram, keys["kl"], _BNN) + _mm(d_gram, keys["kl"], _BTN) +
               d_kout * e_out)
        d_g = d_g + rowsum(d_qin * q) * e_g
        from_out = rowsum(d_kout * k) * e_out
        d_g = d_g - from_out
        d_last = (jnp.sum(from_out, axis=1, keepdims=True) +
                  d_keep * x["keep"])
        d_g_row = d_g_row + jnp.where(jj[:1] == c - 1, d_last, 0.0)
        d_v = d_ru * b_col
        for j in range(per):
            dv_ref[0, j * c:(j + 1) * c, lanes] = d_v[j].astype(dv_ref.dtype)
            dg_ref[0, h, j] = _row_of(d_g[j]) + d_g_row[j]
            db_ref[0, h, j] = _row_of(d_b[j])
    # through the unit lengths: x^ = u scale with u = x by / scale, |u| = 1
    for ref, u, by, d in ((dq_ref, keys["q_unit"], keys["q_by"], d_q),
                          (dk_ref, k, keys["k_by"], d_k)):
        d = (d - u * rowsum(u * d)) * by
        ref[0] = d.reshape(per * c, dk).astype(ref.dtype)


def _gdn_specs(hk, hv, dk, dv, n, chunk, per, interpret, back=False):
    """The grid and the block specifications the two rule kernels share:
    (batch, key head, blocks of ``per`` chunks) with the key head's value
    heads side by side in a block, the chunks in reverse for the
    gradient."""
    from jax.experimental.pallas import tpu as pltpu

    group = hv // hk
    last = n // per - 1
    at = (lambda i: last - i) if back else (lambda i: i)
    spec, _ = _kernel_specs(interpret)
    rows = per * chunk
    keys = spec((1, rows, dk), lambda b, h, i: (b, at(i), h))
    values = spec((1, rows, group * dv), lambda b, h, i: (b, at(i), h))
    by_chunk = lambda *tail: spec((1, group, per) + tail,
                                  lambda b, h, i: (b, h, at(i), 0, 0))
    params = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))}
    return (keys, values, by_chunk(1, chunk), by_chunk(dk, dv),
            by_chunk(chunk, chunk), params)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "per", "eps",
                                             "save", "interpret"))
def _gdn_fwd_call(q, k, v, gamma, beta, heads, chunk, per, eps, save,
                  interpret):
    """``q``, ``k`` [b, t, hk * dk], ``v`` [b, t, hv * dv], ``gamma``,
    ``beta`` [b, hv, t / chunk, 1, chunk] float32 -> ``o`` like ``v`` and,
    with ``save``, the state before each chunk [b, hv, t / chunk, dk, dv]
    and each chunk's inverse [b, hv, t / chunk, chunk, chunk], float32."""
    from jax.experimental.pallas import tpu as pltpu

    hk, hv = heads
    b, t = q.shape[:2]
    dk, dv, n = q.shape[2] // hk, v.shape[2] // hv, t // chunk
    keys, values, scalars, states, inverses, params = _gdn_specs(
        hk, hv, dk, dv, n, chunk, per, interpret)
    _, like = _kernel_specs(interpret)
    out_shape, out_specs = [like(v.shape, v.dtype, v)], [values]
    if save:
        out_shape += [like((b, hv, n, dk, dv), jnp.float32, v),
                      like((b, hv, n, chunk, chunk), jnp.float32, v)]
        out_specs += [states, inverses]
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, per=per, eps=eps),
        out_shape=out_shape, grid=(b, hk, n // per),
        in_specs=[keys, keys, values, scalars, scalars],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((hv // hk, dk, dv), jnp.float32)],
        interpret=interpret, name="_gdn_fwd_call", **params,
    )(q, k, v, gamma, beta)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "per", "eps",
                                             "interpret"))
def _gdn_bwd_call(q, k, v, gamma, beta, states, inverses, do, heads, chunk,
                  per, eps, interpret):
    """-> ``dq``, ``dk`` like ``q``, ``dv`` like ``v``, ``dgamma``,
    ``dbeta`` like ``gamma``, from the forward's arguments, its saved
    states and inverses, and ``do``."""
    from jax.experimental.pallas import tpu as pltpu

    hk, hv = heads
    b, t = q.shape[:2]
    dk, dv, n = q.shape[2] // hk, v.shape[2] // hv, t // chunk
    keys, values, scalars, states_at, inverses_at, params = _gdn_specs(
        hk, hv, dk, dv, n, chunk, per, interpret, back=True)
    _, like = _kernel_specs(interpret)
    return pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, per=per, eps=eps),
        out_shape=(like(q.shape, q.dtype, v), like(k.shape, k.dtype, v),
                   like(v.shape, v.dtype, v),
                   like(gamma.shape, jnp.float32, v),
                   like(gamma.shape, jnp.float32, v)),
        grid=(b, hk, n // per),
        in_specs=[keys, keys, values, scalars, scalars, states_at,
                  inverses_at, values],
        out_specs=(keys, keys, values, scalars, scalars),
        scratch_shapes=[pltpu.VMEM((hv // hk, dk, dv), jnp.float32)],
        interpret=interpret, name="_gdn_bwd_call", **params,
    )(q, k, v, gamma, beta, states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _gdn_diff(q, k, v, gamma, beta, heads, chunk, per, eps, interpret):
    return _gdn_fwd_call(q, k, v, gamma, beta, heads, chunk, per, eps, False,
                         interpret)[0]


def _gdn_diff_fwd(q, k, v, gamma, beta, heads, chunk, per, eps, interpret):
    o, states, inverses = _gdn_fwd_call(q, k, v, gamma, beta, heads, chunk,
                                        per, eps, True, interpret)
    return o, (q, k, v, gamma, beta, states, inverses)


def _gdn_diff_bwd(heads, chunk, per, eps, interpret, res, do):
    return _gdn_bwd_call(*res, do, heads, chunk, per, eps, interpret)


_gdn_diff.defvjp(_gdn_diff_fwd, _gdn_diff_bwd)


def delta_rule_tiles(dk, dv, chunk):
    """The shapes the rule's kernels take: heads of whole 128-lane tiles
    and a chunk of 16 x 16 blocks that merge in pairs (a function of
    shapes alone; the length is padded to whole chunks either way)."""
    return (dk % 128 == 0 and dv % 128 == 0 and chunk % _SOLVE_BLOCK == 0 and
            chunk & (chunk - 1) == 0)


def delta_rule(query, key, value, g, beta, chunk=64, eps=1e-6,
               interpret=False):
    """The gated delta rule of ``ops/nn.py`` ``gated_delta_rule`` (its
    arguments, its result) through the kernels ``_gdn_fwd_call`` and, for
    the gradient, ``_gdn_bwd_call``; only the decay's running sum inside a
    chunk and the padding to whole chunks are plain JAX round them."""
    b, t, hk, dk = query.shape
    hv, dv = value.shape[2:]
    c = min(int(chunk), t)
    n = -(-t // c)
    # sixteen chunks' local arrays to a program where the heads allow: at
    # Qwen3-Next's shape eight chunks a program ran the forward kernel in
    # 4.1 ms where four took 4.7 (some 2 us a program beside its work)
    per = next(p for p in (8, 4, 2, 1)
               if n % p == 0 and (p * (hv // hk) <= 16 or p == 1))
    pad = n * c - t

    def flat(x):
        """[b, t, heads, d] -> [b, whole chunks, heads * d]; a padded
        token has q = k = v = 0, beta = 0 and g = 0."""
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, n * c, -1)

    def by_chunk(x):
        """[b, t, hv] -> [b, hv, chunks, 1, c] float32: a chunk's
        scalars are a row of lanes"""
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
        return jnp.swapaxes(x, 1, 2).reshape(b, hv, n, 1, c)

    o = _gdn_diff(flat(query), flat(key), flat(value),
                  jnp.cumsum(by_chunk(g), axis=-1), by_chunk(beta), (hk, hv),
                  c, per, float(eps), bool(interpret))
    return o.reshape(b, n * c, hv, dv)[:, :t]


def flash_attention(q, k, v, causal=False, scale=None, block_q=256,
                    block_k=512, interpret=None, force=False, window=0):
    """Blockwise attention, O(T) memory. q, k: (B, H, T, D) or (BH, T, D);
    v: the same with a width of its own, D_v, which is the output's
    (latent attention scores over 192 lanes and reads values of 128; a
    width that is no multiple of 128 is the whole last dimension of its
    block); k and v may have fewer heads than q (grouped-query
    attention: H a multiple of their head count, query head h reading
    K/V head h // group). Dispatches to the Pallas kernel for long sequences
    (>= FLASH_MIN_SEQ, where it beats XLA's dense lowering by the
    measured margins above) and to the dense jnp path otherwise or when
    the sequence doesn't tile; `force=True` always takes the kernel
    (tests). ``window`` > 0 (causal only; 0 = none): query t sees keys
    t - window + 1 ... t, itself among them; the kernel skips the blocks
    before the window as it skips those past the diagonal, and the dense
    path takes the same mask. A window that covers the whole sequence is
    plain causal attention.

    Differentiated, the kernel path runs a second kernel
    (``_flash_bwd_call`` in a capture) over the same blocks: the forward
    saves each row's log-sum-exp beside its output, and one pass rebuilds
    a tile's weights from it and accumulates ``dq``, ``dk`` and ``dv`` in
    VMEM (float32; the products take the operands' type), the grouped
    K/V heads' gradients summed there and written once."""
    squeeze = False
    if q.ndim == 4:
        b, h, t, d = q.shape
        q = q.reshape(b * h, t, d)
        k = k.reshape(b * k.shape[1], k.shape[2], d)
        v = v.reshape(b * v.shape[1], v.shape[2], v.shape[3])
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
    if t_k * (q.shape[-1] + v.shape[-1]) * q.dtype.itemsize > \
            VMEM_BUDGET_BYTES:
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
