"""Plain reference of the DeepSeek-V3 decoder (Moonlight-16B-A3B) with its
training step: float32, ``jax.numpy`` only, one function from parameters
and one sequence to the loss, ``jax.grad`` for the gradients, Adam as MXNet
defines it. It imports nothing of the program and uses no kernel and no
sorting: attention is computed a block of queries at a time with its mask
written out (so that 8,192 x 8,192 scores of 16 heads fit a chip), the
routed experts are a ``lax.scan`` over the held experts with a mask. What
it shares with the other references (the seed's key, the norm, the batch's
mean, Adam) is imported from them.

Published description: moonshotai/Moonlight-16B-A3B ``config.json``
(``model_type: deepseek_v3``, ``q_lora_rank: null``) and the DeepSeek-V3
modeling code. Every product is without bias:

- ``N(x; w) = w * x / sqrt(mean(x^2) + eps)``, ``w`` born one; ``eps`` is
  ``rms_norm_eps`` in the layer norms and 1e-6 in the latent norm (the
  modeling code's default for ``kv_a_layernorm``).
- Layer ``i``: ``u = N(x; w_in)``, ``h = x + Attn(u)``, ``n = N(h;
  w_post)``, ``y = h + FF_i(n)``. ``logits = W_head . N(x_L; w_f)``.
- ``Attn`` (H heads, ``d_n``, ``d_r``, ``d_v`` lanes, rank ``r``): ``q =
  W_q u`` [H, d_n + d_r] split into ``q_n``, ``q_r``; ``[c; k_r] = W_kva
  u`` [r + d_r]; ``c <- N(c; w_c)``; ``[k_n; v] = W_kvb c`` [H, d_n +
  d_v]. Rotary encoding at ``rope_theta`` of ``q_r`` (each head) and of
  ``k_r`` (one head for all): frequencies ``f_j = theta^(-2j / d_r)``; the
  lanes de-interleaved first, ``x.view(d_r / 2, 2).T`` (pairs ``(2j, 2j +
  1)`` become ``(j, j + d_r / 2)``), then ``x cos + rotate_half(x) sin``.
  ``q = [q_n; q_r]``, ``k_h = [k_n,h; k_r]``; ``o = softmax(q k^T /
  sqrt(d_n + d_r)) v``, causal; ``Attn = W_o concat(o)``.
- ``FF_i``: for ``i < first_k_dense_replace`` ``W_2(silu(W_1 n) * W_3
  n)``. Else ``s = sigmoid(W_r n)`` over all the published experts;
  ``sel = top_k(s + b)`` (``b = e_score_correction_bias``, the selection
  only); ``w_e = s_e / (sum_sel s + 1e-20)`` (the modeling code's
  denominator); ``FF = S(n) + routed_scaling_factor * sum over the selected
  experts that are HELD of w_e E_e(n)``, ``E_e`` SwiGLU blocks (``w1`` the
  gate's matrix, ``w3`` the up product's, ``w2`` the down product's), ``S``
  the ``n_shared_experts`` shared experts as one SwiGLU of their summed
  width. The share ``held = (first, count)`` is the configuration's: what
  the absent experts would add is left out, here as in the program.
- The loss is the mean token cross-entropy over the vocabulary slice; Adam
  is ``mxnet.optimizer.Adam``'s (``benchmark/reference/smallthinker.py``).

``fault`` plants one fault for the limits' sake: ``no_latent_norm`` (``c``
not normalised), ``rotate_half_pairs`` (no de-interleave: the pairs taken
as ``(j, j + d_r / 2)``), ``scale_128`` (scores over ``sqrt(d_n)``),
``bias_in_weights`` (the weights read from ``s + b``), ``softmax_router``
(softmax scores), ``no_routed_scale``, ``one_shared_expert`` (the first
shared expert alone). ``dtype`` below float32 is the control's.
"""
import functools
import math

import jax
import jax.numpy as jnp

from benchmark.reference.laguna import _rms, _swiglu
from benchmark.reference.smallthinker import (  # noqa: F401
    base_key, batch_grad, make_adam)

STORE = jnp.bfloat16      # the configuration's storage type
LATENT_EPS = 1e-6
QUERY_BLOCK = 1024        # queries a block of the attention


def held_of(cfg):
    return tuple(cfg.get("held") or (0, cfg["n_routed_experts"]))


def n_routed(cfg):
    return cfg.get("published_num_experts", cfg["n_routed_experts"])


def routes(cfg, i):
    """Does layer ``i`` route (``first_k_dense_replace``,
    ``moe_layer_freq``)?"""
    return i >= cfg.get("first_k_dense_replace", 0) and \
        i % cfg.get("moe_layer_freq", 1) == 0


def leaves(cfg):
    """(name, shape, kind) of every leaf in the order of gluon's
    ``collect_params``. ``kind``: embedding / matrix / norm1 (stored about
    one) / bias (the selection's buffer)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    r, f = cfg["kv_lora_rank"], cfg["moe_intermediate_size"]
    count, fd = held_of(cfg)[1], cfg["intermediate_size"]
    fs = cfg.get("n_shared_experts", 1) * f
    out = [("embed", (cfg["vocab_size"], d), "embedding"),
           ("norm", (d,), "norm1"),
           ("head", (cfg["vocab_size"], d), "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        out += [(p + "input_norm", (d,), "norm1"),
                (p + "attn.q_proj", (h * (dn + dr), d), "matrix"),
                (p + "attn.kv_a_proj_with_mqa", (r + dr, d), "matrix"),
                (p + "attn.kv_a_layernorm", (r,), "norm1"),
                (p + "attn.kv_b_proj", (h * (dn + dv), r), "matrix"),
                (p + "attn.o_proj", (d, h * dv), "matrix"),
                (p + "post_norm", (d,), "norm1")]
        if not routes(cfg, i):
            out += [(p + "mlp.w1", (fd, d), "matrix"),
                    (p + "mlp.w3", (fd, d), "matrix"),
                    (p + "mlp.w2", (d, fd), "matrix")]
        else:
            out += [(p + "moe.w1", (count, d, f), "matrix"),
                    (p + "moe.w3", (count, d, f), "matrix"),
                    (p + "moe.w2", (count, f, d), "matrix"),
                    (p + "moe.router", (n_routed(cfg), d), "matrix"),
                    (p + "moe.expert_bias", (n_routed(cfg),), "bias"),
                    (p + "moe.shared.w1", (fs, d), "matrix"),
                    (p + "moe.shared.w3", (fs, d), "matrix"),
                    (p + "moe.shared.w2", (d, fs), "matrix")]
    return out


def trainable(cfg):
    return [n for n, _, kind in leaves(cfg) if kind != "bias"]


def init_params(seed, cfg):
    """{name: float32 array}, drawn in one jitted call: matrices N(0,
    0.02), norm weights 1 + 0.05 N(0, 1), the embedding N(0, 1) (as
    ``benchmark/reference/laguna.py``), ``e_score_correction_bias`` 0.01
    N(0, 1), so the selection-only path is live. Every leaf but the bias
    (a float32 buffer in the program) is rounded to the storage type's
    grid by ``reduce_precision``, which the compiler keeps (a round trip
    through bfloat16 inside the call it may drop), so the program's 16-bit
    weights and the reference's float32 ones start equal."""
    return _draw(tuple(leaves(cfg)))(base_key(seed))


@functools.lru_cache(maxsize=None)
def _draw(spec):
    """The jitted draw of the leaves ``spec``, compiled once a process."""
    bits = {"exponent_bits": jnp.finfo(STORE).nexp,
            "mantissa_bits": jnp.finfo(STORE).nmant}

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            x = {"matrix": 0.02 * x, "embedding": x, "bias": 0.01 * x,
                 "norm1": 1.0 + 0.05 * x}[kind]
            out[name] = x if kind == "bias" else \
                jax.lax.reduce_precision(x, **bits)
        return out

    return make


def _rope(x, theta, deinterleave=True):
    """x [T, heads, d]: DeepSeek-V3's rotary encoding over all d lanes."""
    t, d = x.shape[0], x.shape[-1]
    if deinterleave:
        x = jnp.swapaxes(x.reshape(t, x.shape[1], d // 2, 2), -1,
                         -2).reshape(x.shape)
    f = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang).astype(x.dtype) + \
        half * jnp.sin(ang).astype(x.dtype)


def _attn(p, n, u, cfg, fault):
    w = lambda k: p[n + k].astype(u.dtype)
    h = cfg["num_attention_heads"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    r, theta = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    t = u.shape[0]
    q = (u @ w("attn.q_proj").T).reshape(t, h, dn + dr)
    ckr = u @ w("attn.kv_a_proj_with_mqa").T
    c, k_r = ckr[:, :r], ckr[:, None, r:]
    if fault != "no_latent_norm":
        c = _rms(c, p[n + "attn.kv_a_layernorm"], LATENT_EPS)
    kv = (c @ w("attn.kv_b_proj").T).reshape(t, h, dn + dv)
    pairs = fault != "rotate_half_pairs"
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta, pairs)], -1)
    k_r = jnp.broadcast_to(_rope(k_r, theta, pairs), (t, h, dr))
    k = jnp.concatenate([kv[..., :dn], k_r], -1)
    v = kv[..., dn:]
    scale = (dn if fault == "scale_128" else dn + dr) ** -0.5
    blk = math.gcd(t, QUERY_BLOCK)

    @jax.checkpoint
    def block(args):                    # [blk] queries, every key
        i, qb = args
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        rows = i * blk + jnp.arange(blk)[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (jnp.arange(t // blk),
                            q.reshape(t // blk, blk, h, dn + dr)))
    return o.reshape(t, h * dv) @ w("attn.o_proj").T


def select(p, n, x, cfg, fault=None):
    """(sel [T, k], weight [T, k]) of one expert layer, the routed scale
    taken in."""
    logits = x @ p[n + "moe.router"].astype(x.dtype).T
    if fault == "softmax_router":
        scores = jax.nn.softmax(logits, -1)
    else:
        scores = jax.nn.sigmoid(logits)
    biased = scores + p[n + "moe.expert_bias"].astype(scores.dtype)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(biased),
                           cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(biased if fault == "bias_in_weights" else scores,
                            sel, 1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, 1, keepdims=True) + 1e-20)
    if fault != "no_routed_scale":
        w = w * cfg.get("routed_scaling_factor", 1.0)
    return sel, w


def routed_ff(p, n, x, cfg, fault, held):
    """(this share's part of the routed sum over ``x``, selections)."""
    sel, w = select(p, n, x, cfg, fault)
    first, count = held

    @jax.checkpoint
    def expert(out, e):             # a dense loop with a mask: no sorting
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), 1)
        w1, w3, w2 = (p[n + k][e].astype(x.dtype)
                      for k in ("moe.w1", "moe.w3", "moe.w2"))
        y = (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
        return out + we[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))
    return out, sel


def shared_ff(p, n, x, cfg, fault=None):
    w1, w3, w2 = (p[n + "moe.shared." + k].astype(x.dtype)
                  for k in ("w1", "w3", "w2"))
    if fault == "one_shared_expert":
        f = cfg["moe_intermediate_size"]
        w1, w3, w2 = w1[:f], w3[:f], w2[:, :f]
    return _swiglu(x, w1, w3, w2)


def _layer(p, i, x, cfg, fault, held):
    n = "layer%d." % i
    eps = cfg["rms_norm_eps"]
    h = x + _attn(p, n, _rms(x, p[n + "input_norm"], eps), cfg, fault)
    m = _rms(h, p[n + "post_norm"], eps)
    if not routes(cfg, i):
        w = lambda k: p[n + k].astype(x.dtype)
        return h + _swiglu(m, w("mlp.w1"), w("mlp.w3"), w("mlp.w2")), None
    ff, sel = routed_ff(p, n, m, cfg, fault, held)
    return h + shared_ff(p, n, m, cfg, fault) + ff, sel


def forward(p, ids, cfg, dtype=jnp.float32, fault=None, held=None):
    """One sequence ``ids`` [T] -> logits [T, vocab] and the selections
    [T, k] of each expert layer. Everything is computed in ``dtype``,
    statistics, scores and softmax included: float32 for the reference;
    the control's lower type is lower throughout."""
    held = held or held_of(cfg)
    x = p["embed"][ids].astype(dtype)
    sels = []
    for i in range(cfg["num_hidden_layers"]):
        x, sel = jax.checkpoint(
            lambda p_, x_, i=i: _layer(p_, i, x_, cfg, fault, held))(p, x)
        if sel is not None:
            sels.append(sel)
    x = _rms(x, p["norm"], cfg["rms_norm_eps"])
    return x @ p["head"].astype(dtype).T, sels


def sequence_loss(p, ids, labels, cfg, dtype=jnp.float32, fault=None):
    logits, sels = forward(p, ids, cfg, dtype, fault)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return jnp.mean(nll).astype(jnp.float32), sels


def make_grad(cfg, dtype=jnp.float32, fault=None):
    """Jitted ``(params, ids [T], labels [T]) -> ((loss, selections),
    gradients of the trainable leaves)`` of one sequence, float32 at
    ``highest`` matmul precision (a lower ``dtype`` is the control's)."""
    precision = "highest" if dtype == jnp.float32 else "default"
    names = trainable(cfg)

    @jax.jit
    def grad(params, ids, labels):
        with jax.default_matmul_precision(precision):
            fixed = {n: v for n, v in params.items() if n not in names}
            return jax.value_and_grad(
                lambda t: sequence_loss(dict(t, **fixed), ids, labels, cfg,
                                        dtype, fault),
                has_aux=True)({n: params[n] for n in names})

    return grad
