"""Program builders that the report tools and tests compile as fixtures.

- :func:`build_forward` — the model-zoo ResNet-50 (or another zoo
  model) hybridized into one jitted inference program, bf16 by default,
- :func:`build_train` — a jitted ResNet-50 training step (loss,
  backward, SGD-momentum; params and momentum donated),
- :func:`_tiny_train_step` — a two-conv train step that compiles in
  seconds.

``tools/hlo_audit.py`` and ``tools/memory_report.py --capture`` compile
them; nothing measures with them (``benchmark/run.py`` is the
benchmark).
"""
from __future__ import annotations


def build_forward(batch, dtype=None, layout="NCHW", fuse=False,
                  stem="standard", model="resnet50_v1", hw=224):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx  # noqa: F401  (registers ops)
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.gluon.block import _flatten, infer_shapes
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ndarray.ndarray import NDArray

    if model == "resnet50_v1":
        net = vision.resnet50_v1(layout=layout, stem=stem)
    else:
        if layout != "NCHW" or stem != "standard":
            # a silently-NCHW vgg16 recorded under an NHWC label would
            # be a wrong number, not a slow one
            raise MXNetError(
                f"build_forward: layout/stem variants only exist for "
                f"resnet50_v1, not {model!r}")
        net = vision.get_model(model)
    net.initialize()
    infer_shapes(net, (batch, 3, hw, hw))
    net.hybridize()
    if fuse:
        # conv+BN fold via the XLA subgraph property on the hybridize
        # path (optimize_for without the eager warm-forward — shapes
        # are already resolved by infer_shapes above)
        net._optimized_backend = "XLA"

    plist = sorted(net.collect_params().items())
    pvals = tuple(p.data()._data for _, p in plist)
    x = NDArray(jnp.zeros((batch, 3, hw, hw), jnp.float32))
    _, in_spec = _flatten([x])
    jfn, _o, _a = net._build_cached(plist, in_spec, training=False)
    key = jax.random.PRNGKey(0)

    if dtype is None or dtype == jnp.bfloat16:
        # bf16 activations/weights; BN stats stay fp32 inside the layers
        pvals = tuple(v.astype(jnp.bfloat16)
                      if v.dtype == jnp.float32 else v for v in pvals)

    def forward(param_vals, data):
        outs, _aux = jfn(param_vals, key, data)
        return outs[0]

    return jax.jit(forward), pvals


def build_train(batch, layout="NCHW", stem="standard"):
    """Jitted ResNet-50 training step: forward + softmax-CE loss +
    backward + SGD-momentum, params/momentum donated so updates are
    in-place on device (the reference's training benchmark analogue,
    ref: docs/faq/perf.md:183-219 publishes *training* img/s).
    bf16 activations, fp32 master params (multi-precision SGD)."""
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu.gluon.block import _flatten, infer_shapes
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = vision.resnet50_v1(layout=layout, stem=stem)
    net.initialize()
    infer_shapes(net, (batch, 3, 224, 224))
    net.hybridize()

    plist = sorted(net.collect_params().items())
    pvals = tuple(p.data()._data for _, p in plist)
    x = NDArray(jnp.zeros((batch, 3, 224, 224), jnp.float32))
    _, in_spec = _flatten([x])
    jfn, _o, _a = net._build_cached(plist, in_spec, training=True)
    key = jax.random.PRNGKey(0)

    def loss_fn(param_vals, data, labels):
        # bf16 compute off fp32 masters; loss reduced in fp32
        cast = tuple(v.astype(jnp.bfloat16) if v.dtype == jnp.float32
                     else v for v in param_vals)
        outs, _aux = jfn(cast, key, data)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, labels[:, None], axis=1)
        return jnp.mean(nll)

    grad_fn = jax.value_and_grad(loss_fn)

    def step(params, moms, data, labels):
        loss, grads = grad_fn(params, data, labels)
        moms = tuple(0.9 * m + g.astype(jnp.float32)
                     for m, g in zip(moms, grads))
        params = tuple(p - 0.05 * m for p, m in zip(params, moms))
        return params, moms, loss

    moms = tuple(jnp.zeros_like(v) for v in pvals)
    return (jax.jit(step, donate_argnums=(0, 1)),
            jax.device_put(pvals), jax.device_put(moms))


def _tiny_train_step():
    import jax
    import jax.numpy as jnp

    def loss_fn(w1, w2, x):
        y = jax.lax.conv_general_dilated(
            x, w1, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        y = jnp.maximum(y, 0)
        y = jax.lax.conv_general_dilated(
            y, w2, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        return jnp.mean(y * y)

    def step(w1, w2, x):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            w1, w2, x)
        return (w1 - 0.01 * grads[0], w2 - 0.01 * grads[1], loss)

    w1 = jnp.zeros((16, 3, 3, 3), jnp.float32)
    w2 = jnp.zeros((16, 16, 3, 3), jnp.float32)
    x = jnp.zeros((8, 3, 32, 32), jnp.float32)
    return jax.jit(step), (w1, w2, x), 8
