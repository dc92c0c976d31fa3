"""Plain reference of the LFM2-MoE decoder with its training step: float32,
``jax.numpy`` only, one function from parameters and one sequence to the
loss, ``jax.grad`` for the gradients, Adam as MXNet defines it. It imports
nothing of the program, uses no kernel and no sorting: the expert layer is
a loop over the held experts with a mask.

Published description: LiquidAI/LFM2-24B-A2B ``config.json``
(``model_type: lfm2_moe``). The equations, as the program's docstring has
them:

- ``RMSNorm(x; w) = x / sqrt(mean(x^2) + eps) * w``. Layer: ``h = x +
  Op(RMSNorm(x; w_op))``, ``y = h + FF(RMSNorm(h; w_ffn))``. After the last
  layer ``logits = E . RMSNorm(x; w_f)`` with the embedding ``E``, tied.
- ``conv``: ``[B, C, X] = split3(W_in u)``; ``z = B * X``; ``c_t = sum_j
  k[:, j] * z_{t-(L-1)+j}`` (depthwise, causal, zero left padding, no
  bias); ``Op = W_out (C * c)``.
- ``full_attention``: ``q = W_q u`` (heads x d), ``k = W_k u``, ``v = W_v
  u`` (kv_heads x d); RMSNorm over d on q and on k, weights of their own;
  rotary encoding (rotate-half over all d) on q, k; causal ``softmax(q k^T
  / sqrt(d)) v``, each K/V head serving heads / kv_heads query heads;
  ``Op = W_o concat``.
- dense ``FF``: ``W2 (silu(W1 n) * W3 n)``.
- expert ``FF``: ``s = sigmoid(W_r n)`` over all the published experts;
  ``sel = top_k(s + b)`` with the ``expert_bias`` buffer ``b`` used for the
  selection only; ``w_e = s_e / (sum_{e in sel} s_e + 1e-6)`` times
  ``routed_scaling_factor``; ``FF = sum over the selected experts that are
  HELD of w_e W2e (silu(W1e n) * W3e n)``. The share ``held = (first,
  count)`` is the configuration's: what the absent experts would add is
  left out, here as in the program. No token is dropped.
- The loss is the mean token cross-entropy over the vocabulary slice. Adam
  (``mxnet.optimizer.Adam``): ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 -
  b2) g^2``, ``w -= lr sqrt(1 - b2^t) / (1 - b1^t) m / (sqrt(v) + eps)``.

``fault`` plants one of two faults for the limits' sake (``top3``: one
expert fewer per token; ``no_bias``: ``expert_bias`` left out of the
selection); ``dtype`` below float32 is the control's.
"""
import functools

import jax
import jax.numpy as jnp

STORE = jnp.bfloat16      # the configuration's storage type


def held_of(cfg):
    return tuple(cfg.get("held") or (0, cfg["num_experts"]))


def n_routed(cfg):
    return cfg.get("published_num_experts", cfg["num_experts"])


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def leaves(cfg):
    """(name, shape, kind) of every leaf in the order of gluon's
    ``collect_params``. ``kind``: matrix / norm / bias (the buffer)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    count = held_of(cfg)[1]
    out = [("embed", (cfg["vocab_size"], d), "matrix"),
           ("norm", (d,), "norm")]
    for i, kind in enumerate(cfg["layer_types"]):
        p = "layer%d." % i
        out.append((p + "operator_norm", (d,), "norm"))
        if kind == "conv":
            out += [(p + "conv.in_proj", (3 * d, d), "matrix"),
                    (p + "conv.kernel", (d, cfg["conv_L_cache"]), "matrix"),
                    (p + "conv.out_proj", (d, d), "matrix")]
        elif kind == "full_attention":
            out += [(p + "attn.q_proj", (h * hd, d), "matrix"),
                    (p + "attn.k_proj", (kv * hd, d), "matrix"),
                    (p + "attn.v_proj", (kv * hd, d), "matrix"),
                    (p + "attn.o_proj", (d, h * hd), "matrix"),
                    (p + "attn.q_norm", (hd,), "norm"),
                    (p + "attn.k_norm", (hd,), "norm")]
        else:
            raise ValueError("layer type %r" % kind)
        out.append((p + "ffn_norm", (d,), "norm"))
        if i < cfg["num_dense_layers"]:
            f = cfg["intermediate_size"]
            out += [(p + "mlp.w1", (f, d), "matrix"),
                    (p + "mlp.w3", (f, d), "matrix"),
                    (p + "mlp.w2", (d, f), "matrix")]
        else:
            f = cfg["moe_intermediate_size"]
            out += [(p + "moe.w1", (count, d, f), "matrix"),
                    (p + "moe.w3", (count, d, f), "matrix"),
                    (p + "moe.w2", (count, f, d), "matrix"),
                    (p + "moe.router", (n_routed(cfg), d), "matrix")]
            if cfg.get("use_expert_bias"):
                out.append((p + "moe.expert_bias", (n_routed(cfg),), "bias"))
    return out


def trainable(cfg):
    return [n for n, _, kind in leaves(cfg) if kind != "bias"]


def base_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def init_params(seed, cfg):
    """{name: float32 array}: matrices N(0, 0.02), norm weights 1 + 0.05
    N(0, 1), ``expert_bias`` N(0, 0.02), drawn in one jitted call. Every
    leaf but the float32 buffer is then rounded to the storage type, so
    the program's 16-bit weights and the reference's float32 ones start
    equal. The rounding is two calls of its own: inside the jitted draw
    the compiler may keep the excess precision (XLA:TPU does, and a norm
    weight near 1 then starts 0.002 off its 16-bit twin)."""
    spec = leaves(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            out[name] = 1.0 + 0.05 * x if kind == "norm" else 0.02 * x
        return out

    made = make(base_key(seed))
    return {name: made[name] if kind == "bias"
            else made[name].astype(STORE).astype(jnp.float32)
            for name, _, kind in spec}


def _rms(x, w, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * w.astype(x.dtype)


def _silu_mul(a, b):
    return a * jax.nn.sigmoid(a) * b


def _rope(x, theta):
    """x [T, heads, d]; rotate-half over all d."""
    t, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang).astype(x.dtype) + half * jnp.sin(ang).astype(x.dtype)


def _conv_op(p, n, u, cfg):
    w = lambda k: p[n + k].astype(u.dtype)
    b, c, x = jnp.split(u @ w("conv.in_proj").T, 3, axis=-1)
    z = b * x
    width = cfg["conv_L_cache"]
    t = z.shape[0]
    zp = jnp.pad(z, ((width - 1, 0), (0, 0)))
    kern = w("conv.kernel")
    conv = sum(zp[j:j + t] * kern[:, j] for j in range(width))
    return (c * conv) @ w("conv.out_proj").T


def _attn_op(p, n, u, cfg):
    w = lambda k: p[n + k].astype(u.dtype)
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        head_dim(cfg)
    t = u.shape[0]
    theta = float((cfg.get("rope_parameters") or cfg)["rope_theta"])
    eps = cfg["norm_eps"]
    q = (u @ w("attn.q_proj").T).reshape(t, h, d)
    k = (u @ w("attn.k_proj").T).reshape(t, kv, d)
    v = (u @ w("attn.v_proj").T).reshape(t, kv, d)
    q = _rope(_rms(q, p[n + "attn.q_norm"], eps), theta)
    k = _rope(_rms(k, p[n + "attn.k_norm"], eps), theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / d ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, -1)
    o = jnp.einsum("hts,shd->thd", a, v).reshape(t, h * d)
    return o @ w("attn.o_proj").T


def _dense_ff(p, n, x):
    w = lambda k: p[n + k].astype(x.dtype)
    return _silu_mul(x @ w("mlp.w1").T, x @ w("mlp.w3").T) @ w("mlp.w2").T


def select(p, n, x, cfg, fault=None):
    """(sel [T, k], weight [T, k]) of one expert layer."""
    k = cfg["num_experts_per_tok"] - (fault == "top3")
    s = jax.nn.sigmoid(x @ p[n + "moe.router"].astype(x.dtype).T)
    biased = s
    if cfg.get("use_expert_bias") and fault != "no_bias":
        biased = s + p[n + "moe.expert_bias"].astype(s.dtype)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(biased), k)
    w = jnp.take_along_axis(s, sel, 1)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, 1, keepdims=True) + 1e-6)
    return sel, w * cfg.get("routed_scaling_factor", 1.0)


def _expert_ff(p, n, x, cfg, fault, held):
    sel, w = select(p, n, x, cfg, fault)
    first, count = held
    out = jnp.zeros_like(x)
    for e in range(count):          # a dense loop with a mask: no sorting
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), 1)
        y = _silu_mul(x @ p[n + "moe.w1"][e].astype(x.dtype),
                      x @ p[n + "moe.w3"][e].astype(x.dtype)) \
            @ p[n + "moe.w2"][e].astype(x.dtype)
        out = out + we[:, None] * y
    return out, sel


def _layer(p, i, x, cfg, fault, held):
    n = "layer%d." % i
    eps = cfg["norm_eps"]
    u = _rms(x, p[n + "operator_norm"], eps)
    op = _conv_op if cfg["layer_types"][i] == "conv" else _attn_op
    h = x + op(p, n, u, cfg)
    m = _rms(h, p[n + "ffn_norm"], eps)
    if i < cfg["num_dense_layers"]:
        return h + _dense_ff(p, n, m), None
    ff, sel = _expert_ff(p, n, m, cfg, fault, held)
    return h + ff, sel


def forward(p, ids, cfg, dtype=jnp.float32, fault=None, held=None):
    """One sequence ``ids`` [T] -> logits [T, vocab] and the selections
    [T, k] of each expert layer. Everything is computed in ``dtype``,
    statistics, scores and softmax included: float32 for the reference;
    the control's lower type is lower throughout."""
    held = held or held_of(cfg)
    x = p["embed"][ids].astype(dtype)
    sels = []
    for i in range(len(cfg["layer_types"])):
        x, sel = jax.checkpoint(
            lambda p_, x_, i=i: _layer(p_, i, x_, cfg, fault, held))(p, x)
        if sel is not None:
            sels.append(sel)
    x = _rms(x, p["norm"], cfg["norm_eps"])
    return x @ p["embed"].astype(dtype).T, sels


def sequence_loss(p, ids, labels, cfg, dtype=jnp.float32, fault=None):
    logits, sels = forward(p, ids, cfg, dtype, fault)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return jnp.mean(nll).astype(jnp.float32), sels


def make_grad(cfg, dtype=jnp.float32, fault=None):
    """Jitted ``(params, ids [T], labels [T]) -> ((loss, selections),
    gradients of the trainable leaves)`` of one sequence, float32 at
    ``highest`` matmul precision (a lower ``dtype`` is the control's)."""
    names = trainable(cfg)
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad(params, ids, labels):
        train = {n: params[n] for n in names}
        rest = {n: v for n, v in params.items() if n not in train}
        with jax.default_matmul_precision(precision):
            return jax.value_and_grad(
                lambda t: sequence_loss({**t, **rest}, ids, labels, cfg,
                                        dtype, fault), has_aux=True)(train)

    return grad


def batch_grad(grad, params, ids, labels):
    """Mean loss, mean gradient and every expert layer's selections
    ([B * T, k] each) over a batch [B, T], a sequence at a time."""
    total, acc, sels = 0.0, None, []
    for b in range(ids.shape[0]):
        (loss, s), g = grad(params, ids[b], labels[b])
        total += float(loss)
        acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        sels.append(s)
    n = ids.shape[0]
    acc = jax.tree_util.tree_map(lambda a: a / n, acc)
    return total / n, acc, [jnp.concatenate(layer) for layer in zip(*sels)]


def make_adam(opt):
    """Jitted ``(params, grads, m, v, t) -> (params, m, v)`` over the
    trainable leaves; the state is float32 whatever the parameters are.
    The old parameters and state are donated: at the published widths
    there is no room for two copies."""
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt.get("wd", 0.0)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def step(params, grads, m, v, t):
        lr_t = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new_p, new_m, new_v = dict(params), {}, {}
        for n, g in grads.items():
            w = params[n]
            g = g.astype(jnp.float32) + wd * w.astype(jnp.float32)
            new_m[n] = b1 * m[n] + (1 - b1) * g
            new_v[n] = b2 * v[n] + (1 - b2) * g * g
            new_p[n] = (w.astype(jnp.float32) - lr_t * new_m[n] /
                        (jnp.sqrt(new_v[n]) + eps)).astype(w.dtype)
        return new_p, new_m, new_v

    return step
