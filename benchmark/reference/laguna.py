"""Plain reference of the Laguna decoder with its training step: float32,
``jax.numpy`` only, one function from parameters and one sequence to the
loss, ``jax.grad`` for the gradients, Adam as MXNet defines it. It imports
nothing of the program and uses no kernel and no sorting: attention is
dense with its mask written out (a head at a time), the routed experts a
``lax.scan`` over the held experts with a mask. What it shares with the
SmallThinker reference (the seed's key, the mask, the batch's mean, Adam)
is imported from there.

Published description: poolside/Laguna-S-2.1 ``config.json``
(``model_type: laguna``). The equations, as the program's docstring has
them; every product is without bias:

- ``N(x; w) = w * x / sqrt(mean(x^2) + rms_norm_eps)``, ``w`` born one.
- Layer ``i`` on ``x``: ``u = N(x; w_in)``, ``h = x + Attn_i(u)``, ``n =
  N(h; w_post)``, ``y = h + FF_i(n)``. ``logits = W_head . N(x_L; w_f)``;
  ``W_head`` is untied.
- ``Attn_i`` (``H_i = num_attention_heads_per_layer[i]`` over
  ``num_key_value_heads``, ``head_dim`` lanes): ``q = W_q u``, ``k = W_k
  u``, ``v = W_v u``. Rotary encoding, rotate-half form, over the first
  ``partial_rotary_factor * head_dim = r`` lanes of q and k, by
  ``rope_parameters[layer_types[i]]``: frequencies ``f_j =
  rope_theta^(-2j/r)``; for ``rope_type: yarn``, ``c(b) = r ln(original /
  (2 pi b)) / (2 ln rope_theta)``, ``low = floor(c(beta_fast))``, ``high =
  ceil(c(beta_slow))``, ``e_j = 1 - clip((j - low) / (high - low), 0,
  1)``, the frequency ``f_j (1 - e_j) / factor + f_j e_j``, and cos and
  sin both times ``attention_factor``. A ``sliding_attention`` query
  ``t`` sees keys ``t - sliding_window + 1 ... t``, a ``full_attention``
  one every earlier key. ``o = softmax(q k^T / sqrt(head_dim)) v``, each
  K/V head serving ``H_i / num_key_value_heads`` consecutive query heads;
  ``o_h <- sigmoid((W_g u)_h) o_h``; ``Attn = W_o concat(o)``.
- ``FF_i``: ``dense``: ``W_2(silu(W_1 n) * W_3 n)``. ``sparse``: ``p =
  softmax(W_r n)`` over all the published experts; ``sel = top_k(p)``;
  ``w_e = p_e / sum_sel p``; ``FF = S(n) + moe_routed_scaling_factor * sum
  over the selected experts that are HELD of w_e E_e(n)``, ``E_e`` and
  ``S`` SwiGLU blocks (``w1`` the gate's matrix, ``w3`` the up product's,
  ``w2`` the down product's). The share ``held = (first, count)`` is the
  configuration's: what the absent experts would add is left out, here as
  in the program.
- The loss is the mean token cross-entropy over the vocabulary slice; Adam
  is ``mxnet.optimizer.Adam``'s (``benchmark/reference/smallthinker.py``).

Assumed, the published file having no key for it: softmax router scores,
the shared expert ungated, silu in every gated product, the gate reads
``u``, no norm on q or k (``docs/laguna.md``).

``fault`` plants one fault for the limits' sake: ``no_window`` (the window
layers see every earlier key), ``no_yarn`` (the full layers rotated by
plain ``rope_theta`` frequencies, no attention factor), ``no_head_gate``,
``sigmoid_router`` (sigmoid scores, normalised over the selected),
``no_routed_scale``, ``top9`` (one expert fewer per token),
``half_batch`` (the second half of the sequence's tokens left out of the
loss: the cell's batch is one sequence). ``dtype`` below float32 is the
control's.
"""
import math

import jax
import jax.numpy as jnp

from benchmark.reference.smallthinker import (  # noqa: F401
    base_key, batch_grad, make_adam, seen)

STORE = jnp.bfloat16      # the configuration's storage type


def held_of(cfg):
    return tuple(cfg.get("held") or (0, cfg["num_experts"]))


def n_routed(cfg):
    return cfg.get("published_num_experts", cfg["num_experts"])


def heads_of(cfg):
    return cfg.get("num_attention_heads_per_layer") or \
        [cfg["num_attention_heads"]] * cfg["num_hidden_layers"]


def leaves(cfg):
    """(name, shape, kind) of every leaf in the order of gluon's
    ``collect_params``. ``kind``: embedding / matrix / norm1 (stored about
    one)."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], \
        cfg["num_key_value_heads"]
    count, f = held_of(cfg)[1], cfg["moe_intermediate_size"]
    fs, fd = cfg["shared_expert_intermediate_size"], \
        cfg["intermediate_size"]
    out = [("embed", (cfg["vocab_size"], d), "embedding"),
           ("norm", (d,), "norm1"),
           ("head", (cfg["vocab_size"], d), "matrix")]
    for i, h in enumerate(heads_of(cfg)):
        p = "layer%d." % i
        out += [(p + "input_norm", (d,), "norm1"),
                (p + "attn.q_proj", (h * hd, d), "matrix"),
                (p + "attn.k_proj", (kv * hd, d), "matrix"),
                (p + "attn.v_proj", (kv * hd, d), "matrix"),
                (p + "attn.g_proj", (h, d), "matrix"),
                (p + "attn.o_proj", (d, h * hd), "matrix"),
                (p + "post_norm", (d,), "norm1")]
        if cfg["mlp_layer_types"][i] == "dense":
            out += [(p + "mlp.w1", (fd, d), "matrix"),
                    (p + "mlp.w3", (fd, d), "matrix"),
                    (p + "mlp.w2", (d, fd), "matrix")]
        else:
            out += [(p + "moe.w1", (count, d, f), "matrix"),
                    (p + "moe.w3", (count, d, f), "matrix"),
                    (p + "moe.w2", (count, f, d), "matrix"),
                    (p + "moe.router", (n_routed(cfg), d), "matrix"),
                    (p + "moe.shared.w1", (fs, d), "matrix"),
                    (p + "moe.shared.w3", (fs, d), "matrix"),
                    (p + "moe.shared.w2", (d, fs), "matrix")]
    return out


def trainable(cfg):
    return [n for n, _, _ in leaves(cfg)]


def init_params(seed, cfg):
    """{name: float32 array}, drawn in one jitted call: matrices N(0,
    0.02), norm weights 1 + 0.05 N(0, 1), the embedding N(0, 1) as
    ``torch.nn.Embedding`` draws it (under N(0, 0.02) attention's rank
    collapse at initialization sends nearly every token to the same
    experts: ``benchmark/reference/smallthinker.py``). Every leaf is then
    rounded to the storage type in a call of its own, so the program's
    16-bit weights and the reference's float32 ones start equal."""
    spec = leaves(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = {"matrix": 0.02 * x, "embedding": x,
                         "norm1": 1.0 + 0.05 * x}[kind]
        return out

    made = make(base_key(seed))
    return {name: made[name].astype(STORE).astype(jnp.float32)
            for name, _, _ in spec}


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        w.astype(x.dtype)


def frequencies(rope, head_dim, yarn=True):
    """(rotated lanes r, float32 [r / 2] frequencies, factor on cos and
    sin) of one entry of ``rope_parameters``, written out from YaRN's
    definition (``yarn`` False: the plain frequencies alone)."""
    theta = float(rope["rope_theta"])
    r = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    f = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    if not yarn or rope.get("rope_type", "default") != "yarn":
        return r, f, 1.0
    c = lambda b: r * math.log(rope["original_max_position_embeddings"] /
                               (2 * math.pi * b)) / (2 * math.log(theta))
    low, high = math.floor(c(rope["beta_fast"])), math.ceil(
        c(rope["beta_slow"]))
    j = jnp.arange(r // 2, dtype=jnp.float32)
    e = 1.0 - jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return r, f * (1.0 - e) / rope["factor"] + f * e, \
        rope["attention_factor"]


def _rope(x, r, f, factor):
    """x [T, heads, d]; rotate-half over the first ``r`` lanes."""
    t = x.shape[0]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * f
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    y = x[..., :r]
    half = jnp.concatenate([-y[..., r // 2:], y[..., :r // 2]], -1)
    cos = (jnp.cos(ang) * factor).astype(x.dtype)
    sin = (jnp.sin(ang) * factor).astype(x.dtype)
    return jnp.concatenate([y * cos + half * sin, x[..., r:]], -1)


def _attn(p, n, u, cfg, i, fault):
    w = lambda k: p[n + k].astype(u.dtype)
    h, kv, d = heads_of(cfg)[i], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    kind = cfg["layer_types"][i]
    t = u.shape[0]
    q = (u @ w("attn.q_proj").T).reshape(t, h, d)
    k = (u @ w("attn.k_proj").T).reshape(t, kv, d)
    v = (u @ w("attn.v_proj").T).reshape(t, kv, d)
    rot = frequencies(cfg["rope_parameters"][kind], d,
                      yarn=fault != "no_yarn")
    q, k = _rope(q, *rot), _rope(k, *rot)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    window = cfg["sliding_window"] \
        if kind == "sliding_attention" and fault != "no_window" else 0
    mask = seen(t, window)

    @jax.checkpoint
    def head(qkv):                      # one head: [T, d] each
        q, k, v = qkv
        s = jnp.where(mask, q @ k.T / d ** 0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    o = jnp.swapaxes(jax.lax.map(
        head, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v))), 0, 1)
    if fault != "no_head_gate":
        o = o * jax.nn.sigmoid(u @ w("attn.g_proj").T)[:, :, None]
    return o.reshape(t, h * d) @ w("attn.o_proj").T


def _swiglu(x, w1, w3, w2):
    """``w2(silu(w1 x) * w3 x)`` with gluon's [out, in] matrices."""
    return (jax.nn.silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def select(p, n, x, cfg, fault=None):
    """(sel [T, k], weight [T, k]) of one expert layer, the routed scale
    taken in."""
    k = cfg["num_experts_per_tok"] - (fault == "top9")
    logits = x @ p[n + "moe.router"].astype(x.dtype).T
    if fault == "sigmoid_router":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, -1)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(scores), k)
    w = jnp.take_along_axis(scores, sel, 1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, 1, keepdims=True)
    if fault != "no_routed_scale":
        w = w * cfg.get("moe_routed_scaling_factor", 1.0)
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


def shared_ff(p, n, x):
    w = lambda k: p[n + k].astype(x.dtype)
    return _swiglu(x, w("moe.shared.w1"), w("moe.shared.w3"),
                   w("moe.shared.w2"))


def _layer(p, i, x, cfg, fault, held):
    n = "layer%d." % i
    eps = cfg["rms_norm_eps"]
    h = x + _attn(p, n, _rms(x, p[n + "input_norm"], eps), cfg, i, fault)
    m = _rms(h, p[n + "post_norm"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        w = lambda k: p[n + k].astype(x.dtype)
        return h + _swiglu(m, w("mlp.w1"), w("mlp.w3"), w("mlp.w2")), None
    ff, sel = routed_ff(p, n, m, cfg, fault, held)
    return h + shared_ff(p, n, m) + ff, sel


def forward(p, ids, cfg, dtype=jnp.float32, fault=None, held=None):
    """One sequence ``ids`` [T] -> logits [T, vocab] and the selections
    [T, k] of each sparse layer. Everything is computed in ``dtype``,
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
    if fault == "half_batch":
        nll = nll[:nll.shape[0] // 2]
    return jnp.mean(nll).astype(jnp.float32), sels


def make_grad(cfg, dtype=jnp.float32, fault=None):
    """Jitted ``(params, ids [T], labels [T]) -> ((loss, selections),
    gradients of the trainable leaves)`` of one sequence, float32 at
    ``highest`` matmul precision (a lower ``dtype`` is the control's)."""
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad(params, ids, labels):
        with jax.default_matmul_precision(precision):
            return jax.value_and_grad(
                lambda t: sequence_loss(t, ids, labels, cfg, dtype, fault),
                has_aux=True)(params)

    return grad
