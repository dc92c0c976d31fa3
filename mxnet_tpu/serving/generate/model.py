"""The decode plane's model path: a gluon decoder LM + the compiled
prefill / decode-step executables that read and write the paged cache.

The model is a plain pre-norm transformer decoder built from
``gluon.nn`` blocks (Embedding, Dense, LayerNorm) — parameters are
gluon :class:`Parameter` objects (census-tagged ``parameter`` at init,
like every other gluon model), and the *reference* path is the block's
own ``hybrid_forward`` full causal forward through the framework's op
registry (``F.flash_attention`` et al). The *serving* path extracts
the same parameter values into a pytree and compiles two pure steps
per replica lane:

- ``prefill``: one request's (padded) prompt through the stack with
  causal :func:`~mxnet_tpu.ops.pallas_kernels.flash_attention`,
  scattering every layer's K/V into the request's pool blocks and
  returning the first greedy token;
- ``decode``: one token per in-flight request (iteration-level batch),
  K/V written at each request's position, attention over the paged
  cache via :func:`~mxnet_tpu.ops.pallas_kernels.paged_attention`,
  next greedy tokens out.

Both are ``jax.jit`` executables warmed per (prompt bucket / batch
bucket) at registration — steady-state decode never retraces — with
cache arrays donated on chip backends (an un-donated cache would
double the pool's HBM every step). The layer math itself is the
framework's registered ops (``ops.nn.fully_connected`` /
``layer_norm`` / ``activation``, ``ops.tensor.embedding``) — the same
functions eager dispatch jits — so the cost/memory ledgers attribute
decode the way they attribute everything else.

:func:`reference_generate` is the correctness oracle: an *unpaged*
single-request greedy decode that re-runs the gluon block's full
causal forward per emitted token (no cache, no paging, no batching).
The gateway's paged output must match it token-for-token — the
tier-1 bitwise-greedy contract.
"""
from __future__ import annotations

import numpy as np

from ...base import MXNetError
from ...ops.nn import activation as _act
from ...ops.nn import fully_connected as _fc
from ...ops.nn import layer_norm as _ln
from ...ops.tensor import embedding as _embed


def _build_block(vocab_size, d_model, num_layers, num_heads, ff_mult,
                 dtype):
    """The gluon block: pre-norm causal transformer LM."""
    from ... import gluon
    from ...gluon import nn

    class DecoderLayer(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.ln1 = nn.LayerNorm(in_channels=d_model)
                self.qkv = nn.Dense(3 * d_model, flatten=False,
                                    in_units=d_model, dtype=dtype)
                self.proj = nn.Dense(d_model, flatten=False,
                                     in_units=d_model, dtype=dtype)
                self.ln2 = nn.LayerNorm(in_channels=d_model)
                self.ff1 = nn.Dense(ff_mult * d_model, flatten=False,
                                    in_units=d_model, dtype=dtype)
                self.ff2 = nn.Dense(d_model, flatten=False,
                                    in_units=ff_mult * d_model,
                                    dtype=dtype)

        def hybrid_forward(self, F, x):
            b, t, _ = x.shape
            h = self.ln1(x)
            qkv = self.qkv(h).reshape(b, t, 3, num_heads,
                                      d_model // num_heads)
            q = F.transpose(F.slice_axis(qkv, axis=2, begin=0, end=1)
                            .reshape(b, t, num_heads, -1),
                            axes=(0, 2, 1, 3))
            k = F.transpose(F.slice_axis(qkv, axis=2, begin=1, end=2)
                            .reshape(b, t, num_heads, -1),
                            axes=(0, 2, 1, 3))
            v = F.transpose(F.slice_axis(qkv, axis=2, begin=2, end=3)
                            .reshape(b, t, num_heads, -1),
                            axes=(0, 2, 1, 3))
            a = F.flash_attention(q, k, v, causal=True)
            a = F.transpose(a, axes=(0, 2, 1, 3)).reshape(b, t, d_model)
            x = x + self.proj(a)
            h2 = self.ln2(x)
            return x + self.ff2(F.Activation(self.ff1(h2),
                                             act_type="relu"))

    class DecoderLM(gluon.HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.embed = nn.Embedding(vocab_size, d_model,
                                          dtype=dtype)
                self.layers = []
                for i in range(num_layers):
                    layer = DecoderLayer()
                    setattr(self, "layer%d" % i, layer)
                    self.layers.append(layer)
                self.ln_f = nn.LayerNorm(in_channels=d_model)
                self.head = nn.Dense(vocab_size, use_bias=False,
                                     flatten=False, in_units=d_model,
                                     dtype=dtype)

        def hybrid_forward(self, F, tokens):
            x = self.embed(tokens)
            for layer in self.layers:
                x = layer(x)
            return self.head(self.ln_f(x))

    return DecoderLM()


class GenerativeDecoder:
    """Model + config bundle for ``Gateway.register_generator``.

    ``num_heads * head_dim == d_model``; ``max_prompt_tokens`` and the
    per-request ``max_new_tokens`` cap bound the block-table width
    (static shapes — the compiled steps never retrace in steady
    state). Parameters initialize through gluon (seed them with
    ``mx.random.seed`` for determinism).
    """

    def __init__(self, vocab_size, d_model=64, num_layers=2,
                 num_heads=4, ff_mult=4, max_prompt_tokens=64,
                 eos_id=None, dtype="float32"):
        if d_model % num_heads:
            raise MXNetError(
                f"generate: d_model {d_model} not divisible by "
                f"num_heads {num_heads}")
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = self.d_model // self.num_heads
        self.ff_mult = int(ff_mult)
        self.max_prompt_tokens = int(max_prompt_tokens)
        self.eos_id = eos_id
        self.dtype = dtype
        self.block = _build_block(self.vocab_size, self.d_model,
                                  self.num_layers, self.num_heads,
                                  self.ff_mult, dtype)
        self.block.initialize()

    # -- parameter extraction ------------------------------------------------
    def param_tree(self):
        """Structured pytree of the gluon parameters' device values
        (the compiled steps' first argument)."""
        def _v(p):
            return p.data()._data

        b = self.block
        layers = []
        for layer in b.layers:
            layers.append({
                "ln1_g": _v(layer.ln1.gamma), "ln1_b": _v(layer.ln1.beta),
                "qkv_w": _v(layer.qkv.weight), "qkv_b": _v(layer.qkv.bias),
                "proj_w": _v(layer.proj.weight),
                "proj_b": _v(layer.proj.bias),
                "ln2_g": _v(layer.ln2.gamma), "ln2_b": _v(layer.ln2.beta),
                "ff1_w": _v(layer.ff1.weight), "ff1_b": _v(layer.ff1.bias),
                "ff2_w": _v(layer.ff2.weight), "ff2_b": _v(layer.ff2.bias),
            })
        return {"embed_w": _v(b.embed.weight), "layers": layers,
                "lnf_g": _v(b.ln_f.gamma), "lnf_b": _v(b.ln_f.beta),
                "head_w": _v(b.head.weight)}

    def full_logits(self, tokens):
        """Reference path: the gluon block's own full causal forward.
        ``tokens``: int array (B, T) → logits NDArray (B, T, vocab)."""
        from ... import nd
        return self.block(nd.array(np.asarray(tokens, np.int32)))


# ---------------------------------------------------------------------------
# pure layer math (shared by prefill and decode; framework ops only)
# ---------------------------------------------------------------------------

def _layer_tail(lp, x, attn_flat):
    """Residual + projection + pre-norm MLP. Shapes (..., d)."""
    y = x + _fc(attn_flat, lp["proj_w"], lp["proj_b"], flatten=False)
    h = _ln(y, lp["ln2_g"], lp["ln2_b"])
    z = _act(_fc(h, lp["ff1_w"], lp["ff1_b"], flatten=False), "relu")
    return y + _fc(z, lp["ff2_w"], lp["ff2_b"], flatten=False)


def _final_logits(params, x):
    h = _ln(x, params["lnf_g"], params["lnf_b"])
    return _fc(h, params["head_w"], None, no_bias=True, flatten=False)


class CompiledDecodeSteps:
    """One lane's jitted prefill/decode executables, bound to a device
    and a :class:`~.kvcache.BlockPool` geometry."""

    def __init__(self, decoder, pool, table_width, device=None,
                 layout=None):
        import jax

        from ...profiling import memory as _mem

        self.decoder = decoder
        self.pool = pool
        self.table_width = int(table_width)
        self.device = device
        # donation is an HBM-residency optimization; the CPU backend
        # ignores it with a warning per call — skip it there (same
        # call as parallel/train_step.py)
        donate = jax.default_backend() != "cpu"
        if pool.mesh is not None:
            # mesh-sliced lane: parameters land under the layout
            # table's NamedShardings over the slice (the SAME table
            # training resolves through — qkv/mlp-in column-parallel,
            # proj/mlp-out row-parallel, embed/head vocab-sharded);
            # the jitted steps become one SPMD program per slice
            from ...parallel.layout import SpecLayout
            layout = layout if layout is not None \
                else SpecLayout.default()
            self.layout = layout
            shardings = layout.resolve(decoder.param_tree(), pool.mesh)
            self.params = jax.tree_util.tree_map(
                lambda a, sh: _mem.tag_role(jax.device_put(a, sh),
                                            "parameter"),
                decoder.param_tree(), shardings)
        else:
            self.layout = None
            self.params = jax.tree_util.tree_map(
                lambda a: _mem.tag_role(jax.device_put(a, device),
                                        "parameter"),
                decoder.param_tree())
        sizes = dict(num_heads=decoder.num_heads,
                     block_tokens=pool.block_tokens)

        # a functools.partial has no __name__ and jits as
        # ``jit__unknown``; named functions make the two programs
        # readable on a capture's ``XLA Modules`` line
        def mx_prefill(params, k_cache, v_cache, tokens, n_valid, blocks):
            return _prefill_impl(params, k_cache, v_cache, tokens, n_valid,
                                 blocks, **sizes)

        def mx_decode(params, k_cache, v_cache, tokens, positions, tables):
            return _decode_impl(params, k_cache, v_cache, tokens, positions,
                                tables, **sizes)

        self._prefill = jax.jit(
            mx_prefill, donate_argnums=(1, 2) if donate else ())
        self._decode = jax.jit(
            mx_decode, donate_argnums=(1, 2) if donate else ())

    def prefill(self, tokens, n_valid, blocks):
        """Run one request's padded prompt; the pool adopts the
        written-through cache. Returns the first greedy token id (a
        device scalar — the caller's reply transfer reads it)."""
        tok, k, v = self._prefill(
            self.params, self.pool.k, self.pool.v,
            np.asarray(tokens, np.int32)[None, :],
            np.int32(n_valid), np.asarray(blocks, np.int32))
        self.pool.swap(k, v)
        return tok

    def decode(self, tokens, positions, tables):
        """One iteration-level decode step over the padded in-flight
        batch. Returns next-token ids (device array (B,))."""
        tok, k, v = self._decode(
            self.params, self.pool.k, self.pool.v,
            np.asarray(tokens, np.int32),
            np.asarray(positions, np.int32),
            np.asarray(tables, np.int32))
        self.pool.swap(k, v)
        return tok


def _prefill_impl(params, k_cache, v_cache, tokens, n_valid, blocks,
                  *, num_heads, block_tokens):
    """tokens (1, Tpad) int32, n_valid scalar, blocks (Tpad//BT,)
    int32 (tail entries = pad sink). Returns (first_token, k, v)."""
    import jax.numpy as jnp

    from ...ops.pallas_kernels import flash_attention

    b, t = tokens.shape
    x = _embed(tokens, params["embed_w"])              # (1, T, d)
    d = x.shape[-1]
    hd = d // num_heads
    nblk = t // block_tokens
    for li, lp in enumerate(params["layers"]):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        qkv = _fc(h, lp["qkv_w"], lp["qkv_b"], flatten=False)
        q, k, v = jnp.split(qkv, 3, axis=-1)           # (1, T, d) each
        qh = q.reshape(b, t, num_heads, hd).transpose(0, 2, 1, 3)
        kh = k.reshape(b, t, num_heads, hd).transpose(0, 2, 1, 3)
        vh = v.reshape(b, t, num_heads, hd).transpose(0, 2, 1, 3)
        k_cache = k_cache.at[li, blocks].set(
            k.reshape(nblk, block_tokens, num_heads, hd))
        v_cache = v_cache.at[li, blocks].set(
            v.reshape(nblk, block_tokens, num_heads, hd))
        a = flash_attention(qh, kh, vh, causal=True)
        a = a.transpose(0, 2, 1, 3).reshape(b, t, d)
        x = _layer_tail(lp, x, a)
    logits = _final_logits(params, x)                  # (1, T, V)
    first = jnp.argmax(logits[0, n_valid - 1], axis=-1).astype(jnp.int32)
    return first, k_cache, v_cache


def _decode_impl(params, k_cache, v_cache, tokens, positions, tables,
                 *, num_heads, block_tokens):
    """tokens/positions (B,) int32, tables (B, NBMAX) int32. Padding
    rows carry position 0 and an all-pad-sink table; their output is
    discarded host-side. Returns (next_tokens, k, v)."""
    import jax.numpy as jnp

    from ...ops.pallas_kernels import paged_attention

    bsz = tokens.shape[0]
    x = _embed(tokens, params["embed_w"])              # (B, d)
    d = x.shape[-1]
    hd = d // num_heads
    rows = jnp.arange(bsz)
    blk = tables[rows, positions // block_tokens]      # (B,)
    slot = positions % block_tokens
    seq_lens = positions + 1
    for li, lp in enumerate(params["layers"]):
        h = _ln(x, lp["ln1_g"], lp["ln1_b"])
        qkv = _fc(h, lp["qkv_w"], lp["qkv_b"], flatten=False)
        q, k, v = jnp.split(qkv, 3, axis=-1)           # (B, d) each
        qh = q.reshape(bsz, num_heads, hd)
        kh = k.reshape(bsz, num_heads, hd)
        vh = v.reshape(bsz, num_heads, hd)
        # the token's own K/V lands in the cache BEFORE attention —
        # position p attends over [0, p] including itself
        k_cache = k_cache.at[li, blk, slot].set(kh)
        v_cache = v_cache.at[li, blk, slot].set(vh)
        a = paged_attention(qh, k_cache[li], v_cache[li], tables,
                            seq_lens)                  # (B, H, Dh)
        x = _layer_tail(lp, x, a.reshape(bsz, d))
    logits = _final_logits(params, x)                  # (B, V)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
        k_cache, v_cache


def reference_generate(decoder, prompt, max_new_tokens):
    """Unpaged single-request greedy oracle: re-run the gluon block's
    full causal forward for every emitted token (quadratic and proud —
    no cache, no paging, no batching; the thing the decode plane must
    match token-for-token). Tokens are padded to one fixed length so
    the eager dispatch compiles a single shape."""
    prompt = [int(t) for t in np.asarray(prompt).ravel()]
    total = len(prompt) + int(max_new_tokens)
    out = []
    toks = list(prompt)
    for _ in range(int(max_new_tokens)):
        padded = np.zeros((1, total), np.int32)
        padded[0, :len(toks)] = toks
        logits = decoder.full_logits(padded).asnumpy()
        nxt = int(np.argmax(logits[0, len(toks) - 1]))
        out.append(nxt)
        toks.append(nxt)
        if decoder.eos_id is not None and nxt == decoder.eos_id:
            break
    return out
