"""Plain reference of the Qwen3-Next decoder with its training step:
float32, ``jax.numpy`` only, one function from parameters and one sequence
to the loss, ``jax.grad`` for the gradients, Adam as MXNet defines it. It
imports nothing of the program, uses no kernel, no chunked form and no
sorting: the gated delta rule is its token-by-token recurrence (a
``lax.scan`` over tokens, nested and checkpointed by blocks of 64 so that
its gradient fits at 4,096 tokens), attention is dense (a head at a time),
the expert layer a loop over the held experts with a mask (a ``lax.scan``
too: unrolled, 32 experts in 4 layers at ``highest`` precision made a
program of 269 MB that took minutes to compile).

Published description: Qwen/Qwen3-Next-80B-A3B-Instruct ``config.json``
(``model_type: qwen3_next``). The equations, as the program's docstring
has them; every product is without bias:

- ``N0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)``, ``w`` born zero: the
  two layer norms, the final norm, the per-head norms of q and k.
  ``Ng(o, z; w) = w * o / sqrt(mean(o^2) + eps) * silu(z)`` over
  ``linear_value_head_dim``, ``w`` born one. ``eps`` = ``rms_norm_eps``.
- Layer ``i``: ``h = x + Mix_i(N0(x))``, ``y = h + MoE(N0(h))``. ``logits =
  W_head . N0(x_last)``; ``W_head`` is untied.
- ``full_attention``: ``[q | gate] = split per head(W_q u)``, ``k = W_k
  u``, ``v = W_v u``; ``q = N0(q; w_q)``, ``k = N0(k; w_k)`` over the head;
  rotary encoding (rotate-half, ``rope_theta``) on the first
  ``partial_rotary_factor * head_dim`` lanes of q and k; causal ``softmax(q
  k^T / sqrt(head_dim)) v``, each K/V head serving heads / kv_heads query
  heads; ``Mix = W_o (concat(o) * sigmoid(gate))``.
- ``linear_attention``: ``[q, k, v, z] = W_qkvz u``, ``[b, a] = W_ba u``;
  ``[q, k, v] <- silu(causal depthwise conv of width
  linear_conv_kernel_dim over concat(q, k, v))``; per value head ``beta =
  sigmoid(b)``, ``g = -exp(A_log) * softplus(a + dt_bias)``; each key head
  serves value_heads / key_heads consecutive value heads; ``q^ = q /
  sqrt(sum q^2 + 1e-6) / sqrt(dk)``, ``k^ = k / sqrt(sum k^2 + 1e-6)``;
  with a state ``S`` [dk, dv] per value head, zero before the first token,
  for t = 1 ... T: ``S <- exp(g_t) S``; ``d_t = beta_t (v_t - S^T k^_t)``;
  ``S <- S + k^_t d_t^T``; ``o_t = S^T q^_t``. ``Mix = W_out . concat_h
  Ng(o_h, z_h; w)``.
- Expert layer: ``p = softmax(W_r n)`` over all the published experts;
  ``sel = top_k(p)``; ``w_e = p_e / sum_{e in sel} p_e``; ``routed = sum
  over the selected experts that are HELD of w_e W2_e(silu(W1_e n) * W3_e
  n)``; ``shared = sigmoid(w_sg . n) * W2_s(silu(W1_s n) * W3_s n)``;
  ``MoE = routed + shared``. The share ``held = (first, count)`` is the
  configuration's: what the absent experts would add is left out, here as
  in the program. No token is dropped.
- The loss is the mean token cross-entropy over the vocabulary slice. Adam
  (``mxnet.optimizer.Adam``): ``m = b1 m + (1 - b1) g``, ``v = b2 v + (1 -
  b2) g^2``, ``w -= lr sqrt(1 - b2^t) / (1 - b1^t) m / (sqrt(v) + eps)``.

``fault`` plants one fault for the limits' sake (``top9``: one expert
fewer per token; ``no_decay``: ``g = 0``; ``no_shared``: the shared expert
left out); ``dtype`` below float32 is the control's.
"""
import functools

import jax
import jax.numpy as jnp

STORE = jnp.bfloat16      # the configuration's storage type
BLOCK = 64                # tokens of the recurrence kept per checkpoint


def held_of(cfg):
    return tuple(cfg.get("held") or (0, cfg["num_experts"]))


def n_routed(cfg):
    return cfg.get("published_num_experts", cfg["num_experts"])


def layer_types(cfg):
    interval = cfg.get("full_attention_interval", 4)
    return cfg.get("layer_types") or [
        "full_attention" if (i + 1) % interval == 0 else "linear_attention"
        for i in range(cfg["num_hidden_layers"])]


def _rule_sizes(cfg):
    """(key heads, value heads, key lanes, value lanes)."""
    return (cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
            cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])


def leaves(cfg):
    """(name, shape, kind) of every leaf in the order of gluon's
    ``collect_params``. ``kind``: matrix / norm0 (stored about zero) /
    norm1 (about one) / a_log / dt_bias."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv, dk, dv = _rule_sizes(cfg)
    keys, values = hk * dk, hv * dv
    count, f = held_of(cfg)[1], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    out = [("embed", (cfg["vocab_size"], d), "matrix"),
           ("norm", (d,), "norm0"),
           ("head", (cfg["vocab_size"], d), "matrix")]
    for i, kind in enumerate(layer_types(cfg)):
        p = "layer%d." % i
        out.append((p + "input_norm", (d,), "norm0"))
        if kind == "linear_attention":
            out += [(p + "gdn.in_proj_qkvz", (2 * keys + 2 * values, d),
                     "matrix"),
                    (p + "gdn.in_proj_ba", (2 * hv, d), "matrix"),
                    (p + "gdn.conv", (2 * keys + values,
                                      cfg["linear_conv_kernel_dim"]),
                     "matrix"),
                    (p + "gdn.A_log", (hv,), "a_log"),
                    (p + "gdn.dt_bias", (hv,), "dt_bias"),
                    (p + "gdn.norm", (dv,), "norm1"),
                    (p + "gdn.out_proj", (d, values), "matrix")]
        elif kind == "full_attention":
            out += [(p + "attn.q_proj", (2 * h * hd, d), "matrix"),
                    (p + "attn.k_proj", (kv * hd, d), "matrix"),
                    (p + "attn.v_proj", (kv * hd, d), "matrix"),
                    (p + "attn.o_proj", (d, h * hd), "matrix"),
                    (p + "attn.q_norm", (hd,), "norm0"),
                    (p + "attn.k_norm", (hd,), "norm0")]
        else:
            raise ValueError("layer type %r" % kind)
        out += [(p + "post_norm", (d,), "norm0"),
                (p + "moe.shared_gate", (1, d), "matrix"),
                (p + "moe.w1", (count, d, f), "matrix"),
                (p + "moe.w3", (count, d, f), "matrix"),
                (p + "moe.w2", (count, f, d), "matrix"),
                (p + "moe.router", (n_routed(cfg), d), "matrix"),
                (p + "moe.shared.w1", (fs, d), "matrix"),
                (p + "moe.shared.w3", (fs, d), "matrix"),
                (p + "moe.shared.w2", (d, fs), "matrix")]
    return out


def trainable(cfg):
    return [n for n, _, _ in leaves(cfg)]


def base_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def init_params(seed, cfg):
    """{name: float32 array}, drawn in one jitted call: matrices N(0,
    0.02); norm weights 0.05 N(0, 1) about their birth value (0 or 1);
    ``A_log = log(uniform(1, 16))``; ``dt_bias`` the inverse softplus of a
    step drawn log-uniform over [0.001, 0.1] (so that a head's decay per
    token lies between forgetting nothing and forgetting most, as the
    family's trained models do; the published initialiser, ones, wipes the
    state every token). Every leaf is then rounded to the storage type, in
    a call of its own (inside the jitted draw the compiler may keep the
    excess precision), so the program's 16-bit weights and the reference's
    float32 ones start equal."""
    spec = leaves(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "a_log":
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, jnp.log(0.001), jnp.log(0.1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            else:
                x = jax.random.normal(k, shape, jnp.float32)
                out[name] = {"matrix": 0.02 * x, "norm0": 0.05 * x,
                             "norm1": 1.0 + 0.05 * x}[kind]
        return out

    made = make(base_key(seed))
    return {name: made[name].astype(STORE).astype(jnp.float32)
            for name, _, _ in spec}


def _rms(x, w, eps, zero_centered=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    w = w.astype(x.dtype)
    return y * (1 + w if zero_centered else w)


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _rope(x, theta, lanes):
    """x [T, heads, d]; rotate-half over the first ``lanes`` lanes."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, lanes, 2, dtype=jnp.float32) / lanes)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    r, rest = x[..., :lanes], x[..., lanes:]
    half = jnp.concatenate([-r[..., lanes // 2:], r[..., :lanes // 2]], -1)
    r = r * jnp.cos(ang).astype(x.dtype) + half * jnp.sin(ang).astype(x.dtype)
    return jnp.concatenate([r, rest], -1)


def _attn_op(p, n, u, cfg):
    w = lambda k: p[n + k].astype(u.dtype)
    h, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    t, eps = u.shape[0], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    lanes = int(d * cfg.get("partial_rotary_factor", 1.0))
    qg = (u @ w("attn.q_proj").T).reshape(t, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (u @ w("attn.k_proj").T).reshape(t, kv, d)
    v = (u @ w("attn.v_proj").T).reshape(t, kv, d)
    q = _rope(_rms(q, p[n + "attn.q_norm"], eps), theta, lanes)
    k = _rope(_rms(k, p[n + "attn.k_norm"], eps), theta, lanes)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    mask = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def head(qkv):                      # one head: [T, d] each
        q, k, v = qkv
        s = jnp.where(mask, q @ k.T / d ** 0.5, -jnp.inf)
        return jax.nn.softmax(s, -1) @ v

    o = jax.lax.map(head, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v)))
    o = jnp.swapaxes(o, 0, 1) * jax.nn.sigmoid(gate)
    return o.reshape(t, h * d) @ w("attn.o_proj").T


def delta_rule(q, k, v, g, beta):
    """The recurrence, a token at a time: ``q``, ``k`` [T, heads, dk]
    (normalised, one row per value head), ``v`` [T, heads, dv], ``g``,
    ``beta`` [T, heads] -> ``o`` [T, heads, dv]."""
    t, heads, dk = q.shape
    pad = -t % BLOCK          # a padded token (k = 0, beta = 0, g = 0)
    xs = [jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
          .reshape((-1, BLOCK) + x.shape[1:]) for x in (q, k, v, g, beta)]

    def token(state, x):
        q, k, v, g, b = x
        state = jnp.exp(g)[:, None, None] * state
        d = b[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q)

    block = jax.checkpoint(lambda state, x: jax.lax.scan(token, state, x))
    _, o = jax.lax.scan(block, jnp.zeros((heads, dk, v.shape[2]), q.dtype),
                        xs)
    return o.reshape((-1,) + o.shape[2:])[:t]


def _gdn_op(p, n, u, cfg, fault):
    w = lambda k: p[n + k].astype(u.dtype)
    hk, hv, dk, dv = _rule_sizes(cfg)
    keys, values = hk * dk, hv * dv
    t, width = u.shape[0], cfg["linear_conv_kernel_dim"]
    qkvz = u @ w("gdn.in_proj_qkvz").T
    qkv, z = qkvz[:, :2 * keys + values], qkvz[:, 2 * keys + values:]
    ba = u @ w("gdn.in_proj_ba").T
    b, a = ba[:, :hv], ba[:, hv:]
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    kern = w("gdn.conv")
    qkv = _silu(sum(padded[j:j + t] * kern[:, j] for j in range(width)))
    q = qkv[:, :keys].reshape(t, hk, dk)
    k = qkv[:, keys:2 * keys].reshape(t, hk, dk)
    v = qkv[:, 2 * keys:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w("gdn.A_log")) * jax.nn.softplus(a + w("gdn.dt_bias"))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) / dk ** 0.5, hv // hk, axis=1)
    k = jnp.repeat(unit(k), hv // hk, axis=1)
    o = delta_rule(q, k, v, g, beta)
    o = _rms(o, p[n + "gdn.norm"], cfg["rms_norm_eps"], zero_centered=False)
    o = o * _silu(z.reshape(t, hv, dv))
    return o.reshape(t, values) @ w("gdn.out_proj").T


def _mlp(x, w1, w3, w2):
    return (_silu(x @ w1.T) * (x @ w3.T)) @ w2.T


def select(p, n, x, cfg, fault=None):
    """(sel [T, k], weight [T, k]) of one expert layer."""
    k = cfg["num_experts_per_tok"] - (fault == "top9")
    s = jax.nn.softmax(x @ p[n + "moe.router"].astype(x.dtype).T, -1)
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(s), k)
    w = jnp.take_along_axis(s, sel, 1)
    if cfg.get("norm_topk_prob", True):
        w = w / jnp.sum(w, 1, keepdims=True)
    return sel, w


def routed_ff(p, n, x, cfg, fault, held):
    """(this share's part of the routed sum, selections)."""
    sel, w = select(p, n, x, cfg, fault)
    first, count = held

    @jax.checkpoint
    def expert(out, e):             # a dense loop with a mask: no sorting
        we = jnp.sum(jnp.where(sel == first + e, w, 0.0), 1)
        y = _mlp(x, p[n + "moe.w1"][e].astype(x.dtype).T,
                 p[n + "moe.w3"][e].astype(x.dtype).T,
                 p[n + "moe.w2"][e].astype(x.dtype).T)
        return out + we[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(count))
    return out, sel


def shared_ff(p, n, x):
    w = lambda k: p[n + k].astype(x.dtype)
    return jax.nn.sigmoid(x @ w("moe.shared_gate").T) * _mlp(
        x, w("moe.shared.w1"), w("moe.shared.w3"), w("moe.shared.w2"))


def _layer(p, i, x, cfg, fault, held):
    n = "layer%d." % i
    eps = cfg["rms_norm_eps"]
    u = _rms(x, p[n + "input_norm"], eps)
    if layer_types(cfg)[i] == "full_attention":
        h = x + _attn_op(p, n, u, cfg)
    else:
        h = x + _gdn_op(p, n, u, cfg, fault)
    m = _rms(h, p[n + "post_norm"], eps)
    ff, sel = routed_ff(p, n, m, cfg, fault, held)
    if fault != "no_shared":
        ff = ff + shared_ff(p, n, m)
    return h + ff, sel


def forward(p, ids, cfg, dtype=jnp.float32, fault=None, held=None):
    """One sequence ``ids`` [T] -> logits [T, vocab] and the selections
    [T, k] of each layer. Everything is computed in ``dtype``, statistics,
    scores, state and softmax included: float32 for the reference; the
    control's lower type is lower throughout."""
    held = held or held_of(cfg)
    x = p["embed"][ids].astype(dtype)
    sels = []
    for i in range(len(layer_types(cfg))):
        x, sel = jax.checkpoint(
            lambda p_, x_, i=i: _layer(p_, i, x_, cfg, fault, held))(p, x)
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
