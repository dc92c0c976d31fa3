"""Plain reference of the SmallThinker decoder with its training step:
float32, ``jax.numpy`` only, one function from parameters and one sequence
to the loss, ``jax.grad`` for the gradients, Adam as MXNet defines it. It
imports nothing of the program, uses no kernel and no sorting: attention is
dense with its mask written out (a head at a time, so that 8,192 x 8,192
scores of 28 heads fit), the expert layer a ``lax.scan`` over the held
experts with a mask.

Published description: PowerInfer/SmallThinker-21BA3B-Instruct
``config.json`` (``model_name: smallthinker_21b_instruct``). The equations,
as the program's docstring has them; every product is without bias:

- ``N(x; w) = w * x / sqrt(mean(x^2) + rms_norm_eps)``, ``w`` born one.
- Layer ``i`` (0-based) on ``x``: ``u = N(x; w_in)``; ``r = u``, the
  router's input; ``h = x + Attn_i(u)``; ``n = N(h; w_post)``; ``y = h +
  MoE(r, n)``. ``logits = W_head . N(x_L; w_f)``; ``W_head`` is untied.
- ``Attn_i`` (``num_attention_heads`` over ``num_key_value_heads``,
  ``head_dim`` lanes): ``q = W_q u``, ``k = W_k u``, ``v = W_v u``. Where
  ``rope_layout[i] == 1``: rotary encoding, rotate-half form,
  ``rope_theta``, over all the lanes of q and k; where 0: none. Query
  ``t`` sees key ``s`` where ``s <= t`` and, where
  ``sliding_window_layout[i] == 1``, ``s > t - sliding_window_size``.
  ``softmax(q k^T / sqrt(head_dim)) v``, each K/V head serving heads /
  kv_heads consecutive query heads; ``Attn = W_o concat(o)``.
- ``MoE(r, n)``: ``l = W_r r`` over all the published experts; ``sel =
  top_k(l)`` with ``k = moe_num_active_primary_experts``; ``w =
  softmax(l[sel])`` (``moe_primary_router_apply_softmax``,
  ``norm_topk_prob``); ``MoE = sum over the selected experts that are HELD
  of w_e W_down,e(relu(W_gate,e n) * W_up,e n)`` (``w1`` is the gate's
  matrix, ``w3`` the up product's, ``w2`` the down product's). The share
  ``held = (first, count)`` is the configuration's: what the absent
  experts would add is left out, here as in the program. No shared
  expert, no selection bias, no token dropped.
- The loss is the mean token cross-entropy over the vocabulary slice. Adam
  (``mxnet.optimizer.Adam``): ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 -
  b2) g^2``, ``w -= lr sqrt(1 - b2^t) / (1 - b1^t) m / (sqrt(v) + eps)``.

Assumed, the published file having no key for it: the router reads ``u``
("router placed before attention"), ReLU in the experts' gate ("sparse
ReGLU"), no projection bias, no q/k norm, no secondary experts, no
auxiliary loss.

``fault`` plants one fault for the limits' sake: ``no_window`` (the window
layers see every earlier key), ``rope_all`` (the full layers are rotated
too), ``router_after`` (the router reads ``n``), ``silu_experts``,
``top5`` (one expert fewer per token), ``half_batch`` (the second half of
the sequence's tokens left out of the loss: the cell's batch is one
sequence). ``dtype`` below float32 is the control's.
"""
import functools

import jax
import jax.numpy as jnp

STORE = jnp.bfloat16      # the configuration's storage type


def held_of(cfg):
    return tuple(cfg.get("held") or (0, cfg["moe_num_primary_experts"]))


def n_routed(cfg):
    return cfg.get("published_num_experts", cfg["moe_num_primary_experts"])


def leaves(cfg):
    """(name, shape, kind) of every leaf in the order of gluon's
    ``collect_params``. ``kind``: embedding / matrix / norm1 (stored about
    one)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    count, f = held_of(cfg)[1], cfg["moe_ffn_hidden_size"]
    out = [("embed", (cfg["vocab_size"], d), "embedding"),
           ("norm", (d,), "norm1"),
           ("head", (cfg["vocab_size"], d), "matrix")]
    for i in range(cfg["num_hidden_layers"]):
        p = "layer%d." % i
        out += [(p + "input_norm", (d,), "norm1"),
                (p + "attn.q_proj", (h * hd, d), "matrix"),
                (p + "attn.k_proj", (kv * hd, d), "matrix"),
                (p + "attn.v_proj", (kv * hd, d), "matrix"),
                (p + "attn.o_proj", (d, h * hd), "matrix"),
                (p + "post_norm", (d,), "norm1"),
                (p + "moe.w1", (count, d, f), "matrix"),
                (p + "moe.w3", (count, d, f), "matrix"),
                (p + "moe.w2", (count, f, d), "matrix"),
                (p + "moe.router", (n_routed(cfg), d), "matrix")]
    return out


def trainable(cfg):
    return [n for n, _, _ in leaves(cfg)]


def base_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def init_params(seed, cfg):
    """{name: float32 array}, drawn in one jitted call: matrices N(0,
    0.02), norm weights 1 + 0.05 N(0, 1), the embedding N(0, 1) as
    ``torch.nn.Embedding`` draws it. (An embedding of N(0, 0.02) is a sixth
    of the first attention layer's output, an eighth of whose energy is one
    vector common to every token; each later attention amplifies that
    vector, and from the third layer on nearly every token picks the same
    experts: attention's rank collapse at initialization, Dong et al.,
    arXiv:2103.03404. Measured on the chip: the busiest held expert at 3.3
    to 6.3 times the mean.) Every leaf is then rounded to
    the storage type, in a call of its own (inside the jitted draw the
    compiler may keep the excess precision), so the program's 16-bit
    weights and the reference's float32 ones start equal."""
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


def _rope(x, theta):
    """x [T, heads, d]; rotate-half over all d lanes."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(ang).astype(x.dtype) + \
        half * jnp.sin(ang).astype(x.dtype)


def seen(t, window):
    """[t, t] bool: query row sees key column. ``window`` 0: every earlier
    key and itself; else the last ``window`` of those."""
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    mask = j <= i
    return mask & (j > i - window) if window else mask


def _attn(p, n, u, cfg, i, fault):
    w = lambda k: p[n + k].astype(u.dtype)
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    t = u.shape[0]
    q = (u @ w("attn.q_proj").T).reshape(t, h, d)
    k = (u @ w("attn.k_proj").T).reshape(t, kv, d)
    v = (u @ w("attn.v_proj").T).reshape(t, kv, d)
    if cfg["rope_layout"][i] or fault == "rope_all":
        theta = float(cfg["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][i] and fault != "no_window" else 0
    mask = seen(t, window)

    @jax.checkpoint
    def head(qkv):                      # one head: [T, d] each
        q, k, v = qkv
        s = jnp.where(mask, q @ k.T / d ** 0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    o = jax.lax.map(head, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v)))
    return jnp.swapaxes(o, 0, 1).reshape(t, h * d) @ w("attn.o_proj").T


def select(p, n, r, cfg, fault=None):
    """(sel [T, k], weight [T, k]) of one expert layer from the router's
    input ``r``."""
    k = cfg["moe_num_active_primary_experts"] - (fault == "top5")
    logits = r @ p[n + "moe.router"].astype(r.dtype).T
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(logits), k)
    picked = jnp.take_along_axis(logits, sel, 1)
    if cfg.get("moe_primary_router_apply_softmax", True):
        # softmax over all, renormalised over the selected, is the
        # softmax over the selected
        w = jax.nn.softmax(picked, -1)
        if not cfg.get("norm_topk_prob", True):
            w = w * jnp.sum(jnp.take_along_axis(
                jax.nn.softmax(logits, -1), sel, 1), 1, keepdims=True)
    else:
        w = jax.nn.sigmoid(picked)
        if cfg.get("norm_topk_prob", True):
            w = w / (jnp.sum(w, 1, keepdims=True) + 1e-6)
    return sel, w


def routed_ff(p, n, r, x, cfg, fault, held):
    """(this share's part of the routed sum over ``x``, selections): the
    router reads ``r``."""
    sel, w = select(p, n, r, cfg, fault)
    first, count = held
    act = jax.nn.silu if fault == "silu_experts" else jax.nn.relu

    @jax.checkpoint
    def expert(out, e):             # a dense loop with a mask: no sorting
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), 1)
        w1, w3, w2 = (p[n + k][e].astype(x.dtype)
                      for k in ("moe.w1", "moe.w3", "moe.w2"))
        y = (act(x @ w1) * (x @ w3)) @ w2
        return out + we[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))
    return out, sel


def _layer(p, i, x, cfg, fault, held):
    n = "layer%d." % i
    eps = cfg["rms_norm_eps"]
    u = _rms(x, p[n + "input_norm"], eps)
    h = x + _attn(p, n, u, cfg, i, fault)
    m = _rms(h, p[n + "post_norm"], eps)
    ff, sel = routed_ff(p, n, m if fault == "router_after" else u, m, cfg,
                        fault, held)
    return h + ff, sel


def forward(p, ids, cfg, dtype=jnp.float32, fault=None, held=None):
    """One sequence ``ids`` [T] -> logits [T, vocab] and the selections
    [T, k] of each layer. Everything is computed in ``dtype``, statistics,
    scores and softmax included: float32 for the reference; the control's
    lower type is lower throughout."""
    held = held or held_of(cfg)
    x = p["embed"][ids].astype(dtype)
    sels = []
    for i in range(cfg["num_hidden_layers"]):
        x, sel = jax.checkpoint(
            lambda p_, x_, i=i: _layer(p_, i, x_, cfg, fault, held))(p, x)
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


@functools.partial(jax.jit, donate_argnums=0)
def _add(acc, g):
    return jax.tree_util.tree_map(jnp.add, acc, g)


def batch_grad(grad, params, ids, labels):
    """Mean loss, mean gradient and every layer's selections ([B * T, k]
    each) over a batch [B, T], a sequence at a time."""
    total, acc, sels = 0.0, None, []
    for b in range(ids.shape[0]):
        (loss, s), g = grad(params, ids[b], labels[b])
        total += float(loss)
        acc = g if acc is None else _add(acc, g)
        del g
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
