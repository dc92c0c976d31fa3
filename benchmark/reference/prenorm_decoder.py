"""Plain reference of a pre-norm transformer decoder (OPT's block):
float32, ``jax.numpy`` only, full causal attention over the whole
sequence, no cache, no paging, no batching tricks, no kernels. It
imports nothing of the program.

Published description: Zhang et al., "OPT: Open Pre-trained Transformer
Language Models", arXiv:2205.01068, and the ``config.json`` of
facebook/opt-1.3b (``do_layer_norm_before``, ReLU, biases).
Departures, the same on both sides of every comparison (the program has
no such part): no learned positional embedding; q, k and v come from one
fused (3d, d) projection whose rows are [q; k; v]; the output head is a
matrix of its own, not the embedding.

Weights are made here from a seed: 16-bit leaves are drawn in float32
and rounded to the served type, so the reference computes in float32
with exactly the values the program serves. Every layer has its own key,
so the reference makes a layer's weights, uses them and drops them.
"""
import functools

import jax
import jax.numpy as jnp

W_STD = 0.02          # OPT's init_std
LN_EPS = 1e-5


def base_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def init_layer(key, cfg):
    """One layer's leaves in the served types: dense weights (out, in)
    and biases in ``cfg["dtype"]``, LayerNorm in
    ``cfg["layer_norm_dtype"]`` (float32 where it is not given)."""
    d, f, dt = cfg["hidden_size"], cfg["ffn_dim"], cfg["dtype"]
    ln = cfg.get("layer_norm_dtype", "float32")
    k = jax.random.split(key, 12)
    return {
        "ln1_g": (1.0 + _normal(k[0], (d,), 0.05, jnp.float32)).astype(ln),
        "ln1_b": _normal(k[1], (d,), W_STD, ln),
        "qkv_w": _normal(k[2], (3 * d, d), W_STD, dt),
        "qkv_b": _normal(k[3], (3 * d,), W_STD, dt),
        "proj_w": _normal(k[4], (d, d), W_STD, dt),
        "proj_b": _normal(k[5], (d,), W_STD, dt),
        "ln2_g": (1.0 + _normal(k[6], (d,), 0.05, jnp.float32)).astype(ln),
        "ln2_b": _normal(k[7], (d,), W_STD, ln),
        "ff1_w": _normal(k[8], (f, d), W_STD, dt),
        "ff1_b": _normal(k[9], (f,), W_STD, dt),
        "ff2_w": _normal(k[10], (d, f), W_STD, dt),
        "ff2_b": _normal(k[11], (d,), W_STD, dt),
    }


def init_ends(key, cfg):
    """Embedding, final LayerNorm and output head."""
    d, v, dt = cfg["hidden_size"], cfg["vocab_size"], cfg["dtype"]
    ln = cfg.get("layer_norm_dtype", "float32")
    k = jax.random.split(key, 4)
    return {"embed_w": _normal(k[0], (v, d), W_STD, dt),
            "lnf_g": (1.0 + _normal(k[1], (d,), 0.05,
                                    jnp.float32)).astype(ln),
            "lnf_b": _normal(k[2], (d,), W_STD, ln),
            "head_w": _normal(k[3], (v, d), W_STD, dt)}


def _keys(seed, cfg):
    base = base_key(seed)
    n = cfg["num_hidden_layers"]
    return [jax.random.fold_in(base, i) for i in range(n)], \
        jax.random.fold_in(base, n)


@functools.lru_cache(maxsize=None)
def _makers(cfg_items):
    cfg = dict(cfg_items)
    return (jax.jit(lambda key: init_layer(key, cfg)),
            jax.jit(lambda key: init_ends(key, cfg)))


def layer_params(seed, cfg, i):
    """Layer ``i``'s leaves in the served types, in one jitted call."""
    return _makers(_cfg_items(cfg))[0](_keys(seed, cfg)[0][i])


def end_params(seed, cfg):
    return _makers(_cfg_items(cfg))[1](_keys(seed, cfg)[1])


def init_params(seed, cfg):
    """The whole tree in the served types: what the benchmark loads into
    the program, a layer at a time so that the layer it replaces can go
    first (two copies of a large model do not fit beside each other)."""
    tree = end_params(seed, cfg)
    tree["layers"] = [layer_params(seed, cfg, i)
                      for i in range(cfg["num_hidden_layers"])]
    return tree


def _ln(x, g, b):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _fake_quant(x, low):
    """The values 8-bit arithmetic with one scale per row of the last
    axis would see: symmetric ``int8`` (the integer products and their
    int32 sum are then exact), or ``fp8`` (e4m3, its largest finite
    value 448 at the row's largest magnitude)."""
    top = jnp.max(jnp.abs(x), -1, keepdims=True)
    top = jnp.where(top == 0, 1.0, top)
    if low == "int8":
        return jnp.round(x / top * 127.0) * (top / 127.0)
    if low == "fp8":
        q = (x / top * 448.0).astype(jnp.float8_e4m3fn)
        return q.astype(jnp.float32) * (top / 448.0)
    raise ValueError("unknown lower precision %r" % low)


def _mm(x, w, low):
    """x (..., in) times w (out, in) transposed."""
    if low:
        x, w = _fake_quant(x, low), _fake_quant(w, low)
    return jnp.einsum("...i,oi->...o", x, w)


def _layer(x, lp, heads, low):
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    b, t, d = x.shape
    hd = d // heads
    h = _ln(x, lp["ln1_g"], lp["ln1_b"])
    qkv = _mm(h, lp["qkv_w"], low) + lp["qkv_b"]
    q, k, v = [a.reshape(b, t, heads, hd) for a in jnp.split(qkv, 3, -1)]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, t, d)
    x = x + _mm(a, lp["proj_w"], low) + lp["proj_b"]
    h2 = _ln(x, lp["ln2_g"], lp["ln2_b"])
    z = jax.nn.relu(_mm(h2, lp["ff1_w"], low) + lp["ff1_b"])
    return x + _mm(z, lp["ff2_w"], low) + lp["ff2_b"]


@functools.lru_cache(maxsize=None)
def _programs(cfg_items, low):
    cfg = dict(cfg_items)
    heads = cfg["num_attention_heads"]

    def embed(tokens, end_key):
        w = init_ends(end_key, cfg)["embed_w"].astype(jnp.float32)
        return w[tokens]

    def layer(x, key):
        with jax.default_matmul_precision("highest"):
            return _layer(x, init_layer(key, cfg), heads, low)

    def head(x, positions, end_key):
        ends = {k: v.astype(jnp.float32)
                for k, v in init_ends(end_key, cfg).items()}
        rows = jnp.take_along_axis(x, positions[:, :, None], 1)
        with jax.default_matmul_precision("highest"):
            h = _ln(rows, ends["lnf_g"], ends["lnf_b"])
            return _mm(h, ends["head_w"], low)

    return jax.jit(embed), jax.jit(layer), jax.jit(head)


def _cfg_items(cfg):
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str))))


def logits_at(seed, cfg, tokens, positions, low=None):
    """Logits (B, P, vocab) in float32 of the full causal forward over
    ``tokens`` (B, T), read at ``positions`` (B, P). Layer by layer, so
    that one layer's weights live at a time. ``low`` (``"fp8"`` or
    ``"int8"``) is the control: the same forward with both operands of
    every dense product in that type."""
    embed, layer, head = _programs(_cfg_items(cfg), low)
    layer_keys, end_key = _keys(seed, cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    x = embed(tokens, end_key)
    for key in layer_keys:
        x = layer(x, key)
    return head(x, jnp.asarray(positions, jnp.int32), end_key)


def served_gaps(logits, served):
    """For each position, how far the served token's logit lies below
    the reference's best, over the largest magnitude in that row.
    ``logits`` (P, vocab), ``served`` (P,) token ids."""
    logits = jnp.asarray(logits)
    served = jnp.asarray(served, jnp.int32)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], 1)[:, 0]
    return (best - got) / jnp.max(jnp.abs(logits), -1)
