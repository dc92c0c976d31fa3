"""Plain reference of bottleneck ResNet v1 with its training step:
float32, ``jax.numpy`` / ``lax`` only, one function from parameters and
a batch to the loss, ``jax.grad`` for the gradients, and SGD with
momentum and weight decay as MXNet defines it. It imports nothing of
the program.

Published description: He et al., "Deep Residual Learning for Image
Recognition", arXiv:1512.03385, as built by the reference framework's
``python/mxnet/gluon/model_zoo/vision/resnet.py`` (``BottleneckV1``: the
stride sits on the first 1x1 convolution, no convolution has a bias,
BatchNorm eps 1e-5 and momentum 0.9 with the biased batch variance in
the running statistics). The step is ``train_imagenet.py``'s: softmax
cross-entropy averaged over the batch; ``mom = mu * mom - lr * (g + wd *
w); w += mom`` on every trainable leaf (gluon gives every parameter
``wd_mult`` 1).

Activations are NHWC here (the program's are NCHW); weights keep the
program's OIHW layout, so one seed gives both sides the same arrays.
"""
import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5
BN_MOMENTUM = 0.9
LAST_GAMMA = 0.2


def leaves(cfg):
    """(name, shape, kind) of every leaf in construction order: the
    order in which gluon's ``collect_params`` lists them. ``kind`` is
    conv / gamma / beta / mean / var / fc_w / fc_b."""
    out = []

    def conv(name, c_out, c_in, k):
        out.append((name + ".weight", (c_out, c_in, k, k), "conv"))

    def bn(name, c):
        for kind in ("gamma", "beta", "mean", "var"):
            out.append((name + "." + kind, (c,), kind))

    chans = cfg["channels"]
    conv("stem.conv", chans[0], cfg["in_channels"], 7)
    bn("stem.bn", chans[0])
    c_in = chans[0]
    for s, (blocks, c_out) in enumerate(zip(cfg["layers"], chans[1:])):
        mid = c_out // 4
        for b in range(blocks):
            p = "stage%d.block%d." % (s + 1, b)
            conv(p + "conv1", mid, c_in, 1)
            bn(p + "bn1", mid)
            conv(p + "conv2", mid, mid, 3)
            bn(p + "bn2", mid)
            conv(p + "conv3", c_out, mid, 1)
            bn(p + "bn3", c_out)
            if b == 0:
                conv(p + "down.conv", c_out, c_in, 1)
                bn(p + "down.bn", c_out)
            c_in = c_out
    out.append(("fc.weight", (cfg["classes"], c_in), "fc_w"))
    out.append(("fc.bias", (cfg["classes"],), "fc_b"))
    return out


def base_key(seed):
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31 - 1)),
                              seed // (2 ** 31 - 1))


def init_params(seed, cfg):
    """{name: float32 array}, in one jitted call. He-normal
    convolutions, BatchNorm scale near one and shift near nought (not
    exactly, so that no two channels are alike), running statistics at
    their start values."""
    spec = leaves(cfg)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shape, kind) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            if kind == "conv":
                std = (2.0 / (shape[1] * shape[2] * shape[3])) ** 0.5
                out[name] = std * jax.random.normal(k, shape, jnp.float32)
            elif kind == "gamma":
                # a block's last scale is small, so that the residual
                # stream does not grow sixteen-fold and the first SGD
                # steps are stable at the configuration's rate
                scale = LAST_GAMMA if name.endswith("bn3.gamma") else 1.0
                out[name] = scale * (1.0 + 0.1 * jax.random.normal(k, shape))
            elif kind in ("beta", "fc_b"):
                out[name] = 0.1 * jax.random.normal(k, shape)
            elif kind == "mean":
                out[name] = 0.3 * jax.random.normal(k, shape)
            elif kind == "var":
                out[name] = 1.0 + 0.5 * jax.random.uniform(k, shape)
            else:
                out[name] = 0.01 * jax.random.normal(k, shape)
        return out

    return make(base_key(seed))


def trainable(cfg):
    return [n for n, _, kind in leaves(cfg) if kind not in ("mean", "var")]


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OIHW", "NHWC"))


def _bn(x, p, name, stats):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, (0, 1, 2))
    var = jnp.mean(jnp.square(x32 - mean), (0, 1, 2))
    stats[name + ".mean"] = BN_MOMENTUM * p[name + ".mean"] + \
        (1 - BN_MOMENTUM) * mean
    stats[name + ".var"] = BN_MOMENTUM * p[name + ".var"] + \
        (1 - BN_MOMENTUM) * var
    y = (x32 - mean) * (lax.rsqrt(var + BN_EPS) * p[name + ".gamma"]) + \
        p[name + ".beta"]
    return y.astype(x.dtype)


def forward(params, x_nchw, cfg, dtype=jnp.float32):
    """Logits and the new running statistics, BatchNorm in training
    mode. ``dtype`` below float32 is the control's: activations and the
    operands of every product in that type."""
    stats = {}
    p = params
    x = jnp.transpose(x_nchw, (0, 2, 3, 1)).astype(dtype)
    x = _conv(x, p["stem.conv.weight"], 2, 3)
    x = jax.nn.relu(_bn(x, p, "stem.bn", stats))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          ((0, 0), (1, 1), (1, 1), (0, 0)))
    for s, blocks in enumerate(cfg["layers"]):
        for b in range(blocks):
            n = "stage%d.block%d." % (s + 1, b)
            stride = 2 if (b == 0 and s > 0) else 1
            y = _conv(x, p[n + "conv1.weight"], stride, 0)
            y = jax.nn.relu(_bn(y, p, n + "bn1", stats))
            y = _conv(y, p[n + "conv2.weight"], 1, 1)
            y = jax.nn.relu(_bn(y, p, n + "bn2", stats))
            y = _conv(y, p[n + "conv3.weight"], 1, 0)
            y = _bn(y, p, n + "bn3", stats)
            if b == 0:
                x = _conv(x, p[n + "down.conv.weight"], stride, 0)
                x = _bn(x, p, n + "down.bn", stats)
            x = jax.nn.relu(x + y)
    x = jnp.mean(x.astype(jnp.float32), (1, 2)).astype(dtype)
    logits = x @ p["fc.weight"].astype(dtype).T + p["fc.bias"].astype(dtype)
    return logits.astype(jnp.float32), stats


def loss_fn(params, x, labels, cfg, dtype=jnp.float32):
    logits, stats = forward(params, x, cfg, dtype)
    logp = jax.nn.log_softmax(logits, -1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return jnp.mean(nll), stats


def make_step(cfg, opt, dtype=jnp.float32):
    """One jitted training step: (params, mom, x, labels) -> (loss,
    params, mom). ``opt``: learning_rate, momentum, wd. Float32 runs at
    ``highest`` matmul precision; a lower ``dtype`` is the control."""
    lr, mu, wd = opt["learning_rate"], opt["momentum"], opt["wd"]
    names = trainable(cfg)
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def step(params, mom, x, labels):
        with jax.default_matmul_precision(precision):
            (loss, stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, x, labels, cfg, dtype)
        new_p, new_m = dict(params), {}
        new_p.update(stats)
        for n in names:
            w = params[n]
            g = grads[n].astype(w.dtype) + wd * w
            new_m[n] = mu * mom[n] - lr * g
            new_p[n] = w + new_m[n]
        return loss, new_p, new_m

    return step


def zero_momentum(params, cfg):
    return {n: jnp.zeros_like(params[n]) for n in trainable(cfg)}


def leaf_norms(w0, w1, w3, names, lr, wd):
    """Per trainable leaf: the norm of the first gradient as the
    optimizer got it, recovered from the first step's move (momentum
    starts at nought, so w1 - w0 = -lr * (g + wd * w0)), and per leaf
    of any kind the norm of the move over the three steps. Works on the
    program's arrays and on the reference's alike."""
    @jax.jit
    def norms(w0, w1, w3):
        f32 = [[a.astype(jnp.float32) for a in w] for w in (w0, w1, w3)]
        g = [jnp.linalg.norm(((a - b) / lr - wd * a).ravel())
             for a, b in zip(f32[0], f32[1])]
        d = [jnp.linalg.norm((c - a).ravel())
             for a, c in zip(f32[0], f32[2])]
        return jnp.stack(g), jnp.stack(d)

    g, d = norms(list(w0), list(w1), list(w3))
    return dict(zip(names, [float(v) for v in g])), \
        dict(zip(names, [float(v) for v in d]))
