"""Neural-network ops: the MXU-heavy core of the framework.

Mirrors src/operator/nn/*.cc (Convolution, FullyConnected, BatchNorm, Pooling,
Activation, Dropout, LRN, LayerNorm, UpSampling, Softmax...). Where the
reference dispatches to MKL-DNN primitives with opaque blocked layouts
(src/operator/nn/mkldnn/), this framework lowers every op to XLA HLO:
convolutions/matmuls hit the MXU via lax.conv_general_dilated / dot_general,
and surrounding elementwise work is fused by XLA — the conv+bn+relu fusion the
reference implements by hand in its subgraph backend falls out of the compiler
here (and is *verified* by the subgraph tests rather than hand-scheduled).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..base import MXNetError
from .registry import register

# ---------------------------------------------------------------------------
# FullyConnected (ref: src/operator/nn/fully_connected.cc)
# ---------------------------------------------------------------------------


@register("FullyConnected")
def fully_connected(data, weight, bias=None, num_hidden=0, no_bias=False,
                    flatten=True, out_dtype=None):
    x = data.reshape(data.shape[0], -1) if flatten else data
    # weight layout (num_hidden, in_units) as in the reference
    # bf16 operands ride the MXU, which accumulates in fp32 internally;
    # the output stays in the input dtype unless ``out_dtype`` asks for
    # the accumulator's (float32 logits from 16-bit operands)
    out = lax.dot_general(
        x, weight,
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=out_dtype and jnp.dtype(out_dtype))
    if not no_bias and bias is not None:
        out = out + bias
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (ref: src/operator/nn/convolution.cc)
# ---------------------------------------------------------------------------

_CONV_DNUMS = {1: ("NCH", "OIH", "NCH"),
               2: ("NCHW", "OIHW", "NCHW"),
               3: ("NCDHW", "OIDHW", "NCDHW")}

# channel-last data layouts (TPU-preferred: C rides the 128-lane minor
# dim so XLA needs no relayout copies around each conv — the analogue of
# the reference's MKL-DNN blocked layouts, src/ndarray/ndarray.cc:389).
# Weights stay in the reference's OIHW storage convention either way;
# dnums tell XLA where C lives, so no weight transpose materializes.
_CHANNEL_LAST = {"NWC": "H", "NHWC": "HW", "NDHWC": "DHW"}


_CHANNEL_FIRST = {"NCW": 1, "NCHW": 2, "NCDHW": 3}


def _conv_layout(layout, nd):
    """(data_spec, weight_spec, channel_axis) for an MXNet layout string."""
    default = _CONV_DNUMS[nd][0]
    if layout is None or layout == default \
            or _CHANNEL_FIRST.get(layout) == nd:
        # MXNet spells 1-d channel-first "NCW"; the jax spec uses "NCH"
        return _CONV_DNUMS[nd] + (1,)
    spatial = _CHANNEL_LAST.get(layout)
    if spatial is None or len(spatial) != nd:
        raise MXNetError(f"Convolution: unsupported layout {layout!r} "
                         f"for {nd}-d kernel")
    spec = "N" + spatial + "C"
    return (spec, "OI" + spatial, spec, nd + 1)


@register("Convolution")
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                layout=None, workspace=1024, cudnn_tune=None,
                cudnn_off=False):
    nd = len(kernel)
    stride = tuple(stride) or (1,) * nd
    dilate = tuple(dilate) or (1,) * nd
    pad = tuple(pad) or (0,) * nd
    lhs_spec, w_spec, out_spec, c_axis = _conv_layout(layout, nd)
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=tuple((p, p) for p in pad),
        rhs_dilation=dilate,
        dimension_numbers=(lhs_spec, w_spec, out_spec),
        feature_group_count=num_group,
    ).astype(data.dtype)
    if not no_bias and bias is not None:
        bshape = tuple(-1 if i == c_axis else 1 for i in range(nd + 2))
        out = out + bias.reshape(bshape)
    return out


@register("_contrib_s2d_stem_conv")
def s2d_stem_conv(data, weight, stride=2, pad=3, block=2, layout="NCHW"):
    """Space-to-depth stem convolution (the MLPerf ResNet TPU trick).

    A KxK stride-s conv on a C_in=3 image runs the MXU at <3% lane
    utilization (3 input channels vs 128 lanes). Rearranging the input
    into bxb blocks (space-to-depth) and the SAME OIHW weight into an
    equivalent (K/b)x(K/b) conv over C_in*b*b channels computes the
    identical result with b*b-fold better lane utilization. The weight
    stays in the reference's OIHW storage convention — the rearrange is
    part of the graph, so checkpoints interoperate freely with the
    standard stem. (ref analogue: the reference reorders weights into
    MKL-DNN blocked layouts at the same seam, mkldnn_base-inl.h
    GetWeights; here the 'blocked layout' is the s2d form.)
    """
    O, C, KH, KW = weight.shape
    b = int(block)
    s = int(stride)
    p = int(pad)
    if s % b != 0:
        raise MXNetError("s2d stem: block must divide stride")
    front = (-KH) % b
    if (p + front) % b != 0:
        # exact equivalence needs the blocked window start b*(t*sp - pl)
        # to equal the reference's t*s - (p + front) — i.e. b | (p+front).
        # Flooring pl instead would silently shift every output pixel.
        raise MXNetError(
            "s2d stem: pad %d with kernel %d is not block-%d alignable"
            % (p, KH, b))
    w8 = jnp.pad(weight, ((0, 0), (0, 0), (front, 0), (front, 0)))
    K8 = KH + front
    Kp = K8 // b
    # (O, C, kh', py, kw', px) -> (O, py, px, C, kh', kw') -> OIHW'
    wp = w8.reshape(O, C, Kp, b, Kp, b).transpose(0, 3, 5, 1, 2, 4) \
        .reshape(O, C * b * b, Kp, Kp)

    # reuse the standard layout table so bad layout strings raise
    # instead of silently computing on the wrong axes
    lhs_spec, _w_spec, out_spec, c_axis = _conv_layout(layout, 2)
    channel_last = c_axis == 3
    if channel_last:
        N, H, W, _ = data.shape
        xp = data.reshape(N, H // b, b, W // b, b, C) \
            .transpose(0, 1, 3, 2, 4, 5) \
            .reshape(N, H // b, W // b, b * b * C)
    else:
        N, _, H, W = data.shape
        xp = data.reshape(N, C, H // b, b, W // b, b) \
            .transpose(0, 3, 5, 1, 2, 4) \
            .reshape(N, C * b * b, H // b, W // b)

    sp = s // b
    pl = (p + front) // b
    # per-axis right pad: pr only cancels across axes when stride==block
    def _pr(size):
        out_sz = (size + 2 * p - KH) // s + 1
        return (out_sz - 1) * sp + Kp - size // b - pl

    out = lax.conv_general_dilated(
        xp, wp, (sp, sp), ((pl, _pr(H)), (pl, _pr(W))),
        dimension_numbers=(lhs_spec, "OIHW", out_spec),
    ).astype(data.dtype)
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), target_shape=(), num_filter=0, num_group=1,
                  no_bias=True, layout=None, workspace=1024, cudnn_tune=None,
                  cudnn_off=False):
    if layout in _CHANNEL_LAST:
        raise MXNetError(
            f"Deconvolution: channel-last layout {layout!r} not supported")
    nd = len(kernel)
    stride = tuple(stride) or (1,) * nd
    pad = tuple(pad) or (0,) * nd
    adj = tuple(adj) or (0,) * nd
    # transposed conv == gradient of conv wrt input: lhs-dilate by stride.
    # weight layout (in_ch, out_ch/group, *k) per the reference; flip spatial
    # dims and swap io to express as a regular conv.
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if num_group > 1:
        cin, cog = w.shape[0], w.shape[1]
        w = w.reshape((num_group, cin // num_group) + w.shape[1:])
        w = jnp.swapaxes(w, 1, 2)
        w = w.reshape((num_group * cog, cin // num_group) + w.shape[3:])
    else:
        w = jnp.swapaxes(w, 0, 1)
    k = tuple(kernel)
    padding = tuple(
        (k[i] - 1 - pad[i], k[i] - 1 - pad[i] + adj[i]) for i in range(nd)
    )
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=stride,
        dimension_numbers=_CONV_DNUMS[nd],
        feature_group_count=num_group,
    ).astype(data.dtype)
    if not no_bias and bias is not None:
        out = out + bias.reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling (ref: src/operator/nn/pooling.cc)
# ---------------------------------------------------------------------------


@register("Pooling")
def pooling(data, kernel=(), pool_type="max", stride=(), pad=(),
            global_pool=False, pooling_convention="valid", cudnn_off=False,
            p_value=2, count_include_pad=True, layout=None):
    nd = data.ndim - 2
    channel_last = layout in _CHANNEL_LAST
    # spatial dims: 2..ndim-1 for NC-first, 1..ndim-2 for channel-last
    sp0 = 1 if channel_last else 2
    spatial_axes = tuple(range(sp0, sp0 + nd))
    if global_pool:
        kernel = tuple(data.shape[a] for a in spatial_axes)
        stride = (1,) * nd
        pad = (0,) * nd
    kernel = tuple(kernel)
    stride = tuple(stride) or (1,) * nd
    pad = tuple(pad) or (0,) * nd

    def _place(vals, fill):
        out = [fill] * data.ndim
        for a, v in zip(spatial_axes, vals):
            out[a] = v
        return tuple(out)

    window = _place(kernel, 1)
    strides = _place(stride, 1)
    if pooling_convention == "full":
        # ceil-mode: pad on the high side so the last partial window counts
        pads = []
        for i in range(nd):
            in_i = data.shape[spatial_axes[i]] + 2 * pad[i]
            rem = (in_i - kernel[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if in_i > kernel[i] else 0
            pads.append((pad[i], pad[i] + extra))
    else:
        pads = [(p, p) for p in pad]
    padding = _place(pads, (0, 0))

    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        summed = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return summed
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return summed / denom
        ones = jnp.ones_like(data)
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return summed / counts
    if pool_type == "lp":
        powd = jnp.power(jnp.abs(data), p_value)
        summed = lax.reduce_window(powd, 0.0, lax.add, window, strides, padding)
        return jnp.power(summed, 1.0 / p_value)
    raise MXNetError(f"pool_type {pool_type!r} unsupported")


# ---------------------------------------------------------------------------
# Activations (ref: src/operator/nn/activation.cc, ../leaky_relu.cc)
# ---------------------------------------------------------------------------


@register("Activation")
def activation(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jnp.log1p(jnp.exp(-jnp.abs(data))) + jnp.maximum(data, 0)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    raise MXNetError(f"act_type {act_type!r} unsupported")


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if data.ndim > 2 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        # eval-mode rrelu uses the mean slope (train-mode randomness lives in
        # the layer, which passes an explicit slope)
        return jnp.where(data >= 0, data, (lower_bound + upper_bound) / 2 * data)
    raise MXNetError(f"LeakyReLU act_type {act_type!r} unsupported")


@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None):
    x = data / temperature if temperature else data
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data / temperature if temperature else data
    return jax.nn.log_softmax(x, axis=axis)


@register("SoftmaxActivation")
def softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    flat = data.reshape(data.shape[0], -1)
    return jax.nn.softmax(flat, axis=-1).reshape(data.shape)


@functools.lru_cache(maxsize=None)
def _softmax_output_closure(grad_scale, ignore_label, use_ignore, multi_output,
                            normalization, smooth_alpha):
    axis = 1 if multi_output else -1

    def fwd(data, label):
        return jax.nn.softmax(data, axis=axis)

    @jax.custom_vjp
    def f(data, label):
        return fwd(data, label)

    def f_fwd(data, label):
        out = fwd(data, label)
        return out, (out, label)

    def f_bwd(res, g):
        """The reference's signature trick (src/operator/softmax_output-inl.h):
        grad wrt data is (softmax - onehot(label)) * grad_scale, independent
        of the incoming head gradient."""
        out, label = res
        nclass = out.shape[axis]
        onehot = jax.nn.one_hot(label.astype(jnp.int32), nclass, axis=axis,
                                dtype=out.dtype)
        if smooth_alpha:
            onehot = onehot * (1 - smooth_alpha) + \
                smooth_alpha / (nclass - 1) * (1 - onehot)
        grad = out - onehot
        if use_ignore:
            keep = (label != ignore_label).astype(out.dtype)
            grad = grad * jnp.expand_dims(keep, axis)
        scale = grad_scale
        if normalization == "batch":
            scale = scale / out.shape[0]
        elif normalization == "valid" and use_ignore:
            nvalid = jnp.maximum(jnp.sum(label != ignore_label), 1)
            scale = scale / nvalid
        return grad * scale, jnp.zeros_like(label)

    f.defvjp(f_fwd, f_bwd)
    return f


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   use_ignore=False, multi_output=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    if not multi_output and label.ndim == data.ndim and \
            label.shape[-1] == 1:
        label = label.reshape(label.shape[:-1])  # (N,1) labels, as CSVIter
    f = _softmax_output_closure(grad_scale, ignore_label, use_ignore,
                                multi_output, normalization, smooth_alpha)
    return f(data, label)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked)


def _regression_closure(grad_scale, fwd, bwd):
    """Loss-layer contract shared by the regression heads (ref:
    src/operator/regression_output-inl.h:190-206): forward transforms the
    data, backward REPLACES the head gradient with
    BackwardOp(out, label) * grad_scale / num_output."""

    @jax.custom_vjp
    def f(data, label):
        return fwd(data)

    def f_fwd(data, label):
        out = fwd(data)
        return out, (out, label)

    def f_bwd(res, g):
        out, label = res
        lab = label.reshape(out.shape) if label.size == out.size \
            else jnp.broadcast_to(label.reshape(label.shape + (1,) * (
                out.ndim - label.ndim)), out.shape)
        num_output = max(int(np.prod(out.shape[1:])), 1)
        grad = bwd(out, lab) * (grad_scale / num_output)
        return grad.astype(out.dtype), jnp.zeros_like(label)

    f.defvjp(f_fwd, f_bwd)
    return f


@functools.lru_cache(maxsize=None)
def _linear_reg_closure(grad_scale):
    return _regression_closure(grad_scale, lambda d: d, lambda o, l: o - l)


@functools.lru_cache(maxsize=None)
def _mae_reg_closure(grad_scale):
    return _regression_closure(grad_scale, lambda d: d,
                               lambda o, l: jnp.sign(o - l))


@functools.lru_cache(maxsize=None)
def _logistic_reg_closure(grad_scale):
    return _regression_closure(grad_scale, jax.nn.sigmoid,
                               lambda o, l: o - l)


@register("LinearRegressionOutput")
def linear_regression_output(data, label, grad_scale=1.0):
    return _linear_reg_closure(float(grad_scale))(data, label)


@register("MAERegressionOutput")
def mae_regression_output(data, label, grad_scale=1.0):
    return _mae_reg_closure(float(grad_scale))(data, label)


@register("LogisticRegressionOutput")
def logistic_regression_output(data, label, grad_scale=1.0):
    return _logistic_reg_closure(float(grad_scale))(data, label)


# ---------------------------------------------------------------------------
# Normalization (ref: src/operator/nn/batch_norm.cc, layer_norm.cc,
# ../instance_norm.cc, ../l2_normalization.cc)
# ---------------------------------------------------------------------------


@register("BatchNorm")
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False, training=False):
    """Normalize; batch statistics when training (moving-stat update is
    managed functionally by the BatchNorm layer / executor, since this op is
    pure — the reference mutates aux states in-place instead)."""
    axis = axis % data.ndim
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = tuple(data.shape[axis] if i == axis else 1 for i in range(data.ndim))
    if training and not use_global_stats:
        mean = jnp.mean(data, axis=reduce_axes)
        var = jnp.var(data, axis=reduce_axes)
    else:
        mean, var = moving_mean, moving_var
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape)) * (inv * g).reshape(bshape) + beta.reshape(bshape)
    # fp32 gamma/beta/stats with fp16/bf16 data must not widen the graph
    # downstream — the reference's BN kernel emits data-dtype output
    # while keeping its parameters fp32 (mixed-precision contract)
    out = out.astype(data.dtype)
    if output_mean_var:
        return out, mean, var
    return out


@register("LayerNorm")
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    inv = lax.rsqrt(var + eps)
    ax = axis % data.ndim
    bshape = tuple(data.shape[ax] if i == ax else 1 for i in range(data.ndim))
    out = (data - mean) * inv * gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)
    return out


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    ax = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=ax, keepdims=True)
    var = jnp.var(data, axis=ax, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = nsize // 2
    summed = lax.reduce_window(
        sq, 0.0, lax.add,
        window_dimensions=(1, nsize, 1, 1),
        window_strides=(1, 1, 1, 1),
        padding=((0, 0), (half, half), (0, 0), (0, 0)),
    )
    return data / jnp.power(knorm + alpha / nsize * summed, beta)


# ---------------------------------------------------------------------------
# Dropout (ref: src/operator/nn/dropout.cc) — RNG op: key injected by runtime
# ---------------------------------------------------------------------------


@register("Dropout", needs_rng=True)
def dropout(key, data, p=0.5, mode="training", axes=(), training=True,
            cudnn_off=False):
    if (not training and mode != "always") or p <= 0:
        return data
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, shape).astype(data.dtype) / keep
    return data * mask


# ---------------------------------------------------------------------------
# UpSampling / resize (ref: src/operator/nn/upsampling.cc,
# contrib/bilinear_resize.cc)
# ---------------------------------------------------------------------------


@register("UpSampling", num_inputs=None)
def upsampling(*args, scale=1, sample_type="nearest", num_args=1,
               num_filter=0, multi_input_mode="concat", workspace=512):
    data = args[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        if num_args > 1 and multi_input_mode == "concat":
            outs = [out]
            for extra in args[1:]:
                s = out.shape[2] // extra.shape[2]
                outs.append(jnp.repeat(jnp.repeat(extra, s, axis=2), s, axis=3))
            out = jnp.concatenate(outs, axis=1)
        return out
    if sample_type == "bilinear":
        weight = args[1] if len(args) > 1 else None
        n, c, h, w = data.shape
        return jax.image.resize(data, (n, c, h * scale, w * scale), "bilinear")
    raise MXNetError(f"sample_type {sample_type!r} unsupported")


@register("CTCLoss", aliases=("ctc_loss", "_contrib_CTCLoss"),
          optional_arrays=("data_lengths", "label_lengths"))
def ctc_loss(data, label, data_lengths=None, label_lengths=None,
             use_data_lengths=False, use_label_lengths=False,
             blank_label="first"):
    """Connectionist Temporal Classification loss
    (ref: src/operator/nn/ctc_loss.cc:51 — warp-ctc semantics).

    data: (seq_len, batch, alphabet) pre-softmax activations.
    label: (batch, max_label_len) int indices. When blank_label is
    "first" blank is id 0, labels use 1..alphabet-1 and padding is 0;
    when "last" blank is alphabet-1 and padding is -1.

    Lowering: optax.ctc_loss (the same log-semiring scan the reference's
    warp-ctc computes), with MXNet's length flags mapped onto optax's
    padding masks; differentiable through jax autodiff.
    """
    import optax

    # dispatch quirk: optional array inputs bind positionally in
    # signature order, so a call providing only label_lengths arrives
    # in the data_lengths slot — rebind using the use_* flags
    if (use_label_lengths and label_lengths is None
            and data_lengths is not None and not use_data_lengths):
        label_lengths, data_lengths = data_lengths, None
    if use_data_lengths and data_lengths is None:
        raise ValueError("CTCLoss: use_data_lengths=True but no "
                         "data_lengths array was provided")
    if use_label_lengths and label_lengths is None:
        raise ValueError("CTCLoss: use_label_lengths=True but no "
                         "label_lengths array was provided (when both "
                         "use_* flags are set, both arrays are required)")

    T, B, A = data.shape
    L = label.shape[1]
    logits = jnp.swapaxes(data.astype(jnp.float32), 0, 1)  # (B, T, A)
    lab = label.astype(jnp.int32)
    blank = 0 if blank_label == "first" else A - 1
    pad_mask = (lab == 0) if blank_label == "first" else (lab < 0)

    if use_data_lengths and data_lengths is not None:
        steps = jnp.arange(T)[None, :]
        logit_pad = (steps >= data_lengths.astype(jnp.int32)
                     .reshape(B)[:, None]).astype(jnp.float32)
    else:
        logit_pad = jnp.zeros((B, T), jnp.float32)

    if L == 0:
        # empty label set: P = all-blank path over the unpadded frames
        lp = jax.nn.log_softmax(logits, axis=-1)[:, :, blank]
        return -jnp.sum(lp * (1.0 - logit_pad), axis=1)

    if use_label_lengths and label_lengths is not None:
        steps = jnp.arange(L)[None, :]
        label_pad = (steps >= label_lengths.astype(jnp.int32)
                     .reshape(B)[:, None]).astype(jnp.float32)
    else:
        label_pad = pad_mask.astype(jnp.float32)
    # padded entries must hold a valid non-negative index; they are
    # masked by label_pad, the value itself is irrelevant
    lab = jnp.where(label_pad > 0, 0, lab)
    return optax.ctc_loss(logits, logit_pad, lab, label_pad,
                          blank_id=blank)


@register("_contrib_BilinearResize2D")
def bilinear_resize_2d(data, height=1, width=1, scale_height=None,
                       scale_width=None, mode="size"):
    n, c, h, w = data.shape
    if scale_height is not None:
        height, width = int(h * scale_height), int(w * scale_width)
    return jax.image.resize(data, (n, c, height, width), "bilinear")


@register("_contrib_AdaptiveAvgPooling2D")
def adaptive_avg_pooling(data, output_size=(1, 1)):
    os = output_size if isinstance(output_size, (tuple, list)) else (output_size, output_size)
    n, c, h, w = data.shape
    if h % os[0] == 0 and w % os[1] == 0:
        kh, kw = h // os[0], w // os[1]
        x = data.reshape(n, c, os[0], kh, os[1], kw)
        return jnp.mean(x, axis=(3, 5))

    # non-divisible case: per-window means with floor/ceil boundaries,
    # expressed separably as two small matmuls (static shapes)
    def win_matrix(in_len, out_len):
        m = np.zeros((out_len, in_len), np.float32)
        for o in range(out_len):
            s = (o * in_len) // out_len
            e = -(-((o + 1) * in_len) // out_len)  # ceil div
            m[o, s:e] = 1.0 / (e - s)
        return jnp.asarray(m)

    rw = win_matrix(h, os[0])
    cw = win_matrix(w, os[1])
    return jnp.einsum("oh,nchw,pw->ncop", rw, data, cw)


# ---------------------------------------------------------------------------
# Correlation (ref: src/operator/correlation-inl.h:80-130) — FlowNet-style
# cost volume between two feature maps.
# ---------------------------------------------------------------------------


@register("Correlation")
def correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """Patch correlation of data1 against displaced data2 neighborhoods.

    The reference launches one CUDA block per displacement; here the D*D
    displacement grid is a static Python loop of shifted elementwise
    products, each reduced over the kernel window with reduce_window and
    over channels — every step is an XLA-fusable dense op, and the MXU sees
    the surrounding convs, not this (it is bandwidth-bound by design).
    Normalization matches the reference: sumelems = K*K*C.
    """
    n, c, h, w = data1.shape
    pb_h, pb_w = h + 2 * pad_size, w + 2 * pad_size
    kr = (kernel_size - 1) // 2
    border = max_displacement + kr
    top_h = -(-(pb_h - 2 * border) // stride1)
    top_w = -(-(pb_w - 2 * border) // stride1)
    ngr = max_displacement // stride2     # neighborhood grid radius
    pad = ((0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size))
    p1 = jnp.pad(data1, pad)
    p2 = jnp.pad(data2, pad)
    sumelems = kernel_size * kernel_size * c
    planes = []
    for dy in range(-ngr, ngr + 1):
        for dx in range(-ngr, ngr + 1):
            sy, sx = dy * stride2, dx * stride2
            shifted = jnp.roll(p2, (-sy, -sx), axis=(2, 3))
            prod = p1 * shifted if is_multiply else jnp.abs(p1 - shifted)
            summed = jnp.sum(prod, axis=1)  # over channels -> (n, pbh, pbw)
            if kernel_size > 1:
                summed = lax.reduce_window(
                    summed, 0.0, lax.add, (1, kernel_size, kernel_size),
                    (1, 1, 1), "SAME")
            # top-left output sample sits at the border offset
            win = lax.dynamic_slice(
                summed, (0, border, border),
                (n, pb_h - 2 * border, pb_w - 2 * border))
            planes.append(win[:, ::stride1, ::stride1][:, :top_h, :top_w])
    out = jnp.stack(planes, axis=1) / sumelems
    return out.astype(data1.dtype)


# ---------------------------------------------------------------------------
# SVMOutput (ref: src/operator/svm_output.cc:31-66) — hinge-loss output
# layer: forward is identity, backward replaces the head gradient with the
# L1/L2 SVM subgradient (the same "loss layer defines its own gradient"
# contract as SoftmaxOutput).
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _svm_output_closure(margin, regularization_coefficient, use_linear):
    reg = regularization_coefficient

    @jax.custom_vjp
    def f(data, label):
        return data

    def f_fwd(data, label):
        return data, (data, label)

    def f_bwd(res, g):
        out, label = res
        k = label.astype(jnp.int32)
        onehot = jax.nn.one_hot(k, out.shape[1], dtype=out.dtype)
        if use_linear:  # L1_SVM (svm_output.cc:31-46)
            g_true = -(margin > out).astype(out.dtype) * reg
            g_other = (margin > -out).astype(out.dtype) * reg
        else:           # L2_SVM (svm_output.cc:49-66)
            g_true = -reg * jnp.where(margin > out, 2 * (margin - out), 0.0)
            g_other = -reg * jnp.where(margin > -out, -2 * (margin + out), 0.0)
        grad = jnp.where(onehot > 0, g_true, g_other).astype(out.dtype)
        return grad, jnp.zeros_like(label)

    f.defvjp(f_fwd, f_bwd)
    return f


@register("SVMOutput")
def svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
               use_linear=False):
    if label.ndim == data.ndim and label.shape[-1] == 1:
        label = label.reshape(label.shape[:-1])
    flat = data.reshape(data.shape[0], -1)
    f = _svm_output_closure(float(margin), float(regularization_coefficient),
                            bool(use_linear))
    return f(flat, label).reshape(data.shape)


# ---------------------------------------------------------------------------
# legacy v1/compat ops (ref: src/operator/batch_norm_v1.cc,
# convolution_v1.cc, pooling_v1.cc, crop.cc, swapaxis.cc — deprecated
# spellings the reference still registers; they alias the modern
# implementations, whose math is a superset)
# ---------------------------------------------------------------------------


@register("BatchNorm_v1")
def batch_norm_v1(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                  momentum=0.9, fix_gamma=True, use_global_stats=False,
                  output_mean_var=False, training=False):
    return batch_norm(data, gamma, beta, moving_mean, moving_var, eps=eps,
                      momentum=momentum, fix_gamma=fix_gamma,
                      use_global_stats=use_global_stats,
                      output_mean_var=output_mean_var, training=training)


@register("Convolution_v1")
def convolution_v1(data, weight, bias=None, kernel=(), stride=(),
                   dilate=(), pad=(), num_filter=0, num_group=1,
                   workspace=1024, no_bias=False, layout=None):
    return convolution(data, weight, bias, kernel=kernel, stride=stride,
                       dilate=dilate, pad=pad, num_filter=num_filter,
                       num_group=num_group, no_bias=no_bias)


@register("Pooling_v1")
def pooling_v1(data, kernel=(), pool_type="max", global_pool=False,
               stride=(), pad=(), pooling_convention="valid"):
    return pooling(data, kernel=kernel, pool_type=pool_type,
                   global_pool=global_pool, stride=stride, pad=pad,
                   pooling_convention=pooling_convention)


@register("Crop", optional_arrays=("crop_like",))
def legacy_crop(data, crop_like=None, offset=(0, 0), h_w=(0, 0),
                center_crop=False, num_args=1):
    """Legacy spatial Crop (ref: src/operator/crop-inl.h:47-62): crop
    NCHW `data` to `h_w` (or to `crop_like`'s spatial dims), at `offset`
    or centered."""
    H, W = data.shape[2], data.shape[3]
    if crop_like is not None:
        th, tw = crop_like.shape[2], crop_like.shape[3]
    else:
        th, tw = int(h_w[0]), int(h_w[1])
    if center_crop:
        y0 = max((H - th) // 2, 0)
        x0 = max((W - tw) // 2, 0)
    else:
        y0, x0 = int(offset[0]), int(offset[1])
    if y0 + th > H or x0 + tw > W or y0 < 0 or x0 < 0:
        raise MXNetError(
            f"Crop: window offset ({y0},{x0}) size ({th},{tw}) exceeds "
            f"input ({H},{W}) (the reference CHECKs the same at crop-inl.h)")
    return data[:, :, y0:y0 + th, x0:x0 + tw]


# ---------------------------------------------------------------------------
# Sequence-model layers: RMSNorm, rotary encoding, gated MLP product, short
# causal convolution, grouped-query attention, the gated delta rule, routed
# experts. Statistics, softmax, router scores, the rule's state and decay are
# float32 whatever the storage type.
#
# The elementwise ones compute in float32 and are ``jax.checkpoint``ed:
# differentiated, they keep their 16-bit inputs and recompute the float32
# intermediates in the backward pass, where autodiff would keep every one
# (at 8,192 tokens x 2,048 wide, 67 MB apiece and a dozen a layer).
# ---------------------------------------------------------------------------


def _recomputed(fn):
    """``fn(*arrays, **attrs)`` whose gradient keeps the arrays alone."""
    @functools.wraps(fn)
    def op(*arrays, **attrs):
        return jax.checkpoint(lambda *a: fn(*a, **attrs))(*arrays)
    return op


@register("RMSNorm", aliases=("_contrib_RMSNorm",))
@_recomputed
def rms_norm(data, gamma, eps=1e-5, zero_centered=False):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, the mean in
    float32. ``zero_centered``: the weight is stored about zero and the
    factor is ``1 + gamma``."""
    x = data.astype(jnp.float32)
    inv = lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    w = gamma.astype(jnp.float32)
    return (x * inv * (1.0 + w if zero_centered else w)).astype(data.dtype)


def yarn_bounds(theta, dim, original, beta_fast, beta_slow):
    """YaRN's ramp (Peng et al., arXiv:2309.00071) over the ``dim / 2``
    frequencies of ``dim`` rotated lanes: ``(low, high)``, the first
    frequency index below ``beta_fast`` turns over ``original`` positions
    (rounded down) and the first below ``beta_slow`` (rounded up), with
    ``c(r) = dim ln(original / (2 pi r)) / (2 ln theta)``."""
    edge = lambda r: dim * math.log(original / (r * 2 * math.pi)) / \
        (2 * math.log(theta))
    return (max(math.floor(edge(beta_fast)), 0),
            min(math.ceil(edge(beta_slow)), dim - 1))


@register("RotaryEmbedding", aliases=("_contrib_RotaryEmbedding",))
@_recomputed
def rotary_embedding(data, theta=10000.0, offset=0, rotary_dim=0,
                     yarn_factor=0.0, yarn_original=0, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=1.0, interleaved=False):
    """Rotary position encoding of ``data`` [batch, seq, heads, dim], the
    rotate-half form over the first ``rotary_dim`` lanes (all ``dim`` where
    0), the other lanes untouched: with ``d = rotary_dim``, ``a_t,i =
    (offset + t) * f_i``, ``f_i = theta^(-2i/d)``, ``out = x * cos(a) +
    rotate_half(x) * sin(a)`` and ``rotate_half(x) = concat(-x[d/2:d],
    x[:d/2])``.

    ``yarn_factor`` > 0: YaRN's frequencies, ``f_i (1 - e_i) / yarn_factor
    + f_i e_i`` with ``e_i = 1 - clip((i - low) / (high - low), 0, 1)``
    and ``(low, high) = yarn_bounds(theta, d, yarn_original, beta_fast,
    beta_slow)``. ``attention_factor`` multiplies cos and sin, so the
    rotated lanes alone.

    ``interleaved``: the rotated lanes pair as ``(2i, 2i + 1)``, as
    DeepSeek-V3's published weights have them; they are first
    de-interleaved, ``x <- concat(x[0::2], x[1::2])`` (the modeling code's
    ``x.view(d/2, 2).T``), and the result stays in that order, as the
    modeling code leaves it: q and k alike, so their products are the
    pairs' own."""
    t, d = data.shape[1], rotary_dim or data.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if yarn_factor:
        low, high = yarn_bounds(theta, d, yarn_original, beta_fast,
                                beta_slow)
        ramp = (np.arange(d // 2, dtype=np.float32) - low) / \
            max(high - low, 1e-3)
        kept = jnp.asarray(1.0 - np.clip(ramp, 0.0, 1.0), jnp.float32)
        inv_freq = inv_freq * (1.0 - kept) / yarn_factor + inv_freq * kept
    ang = (offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x = data[..., :d].astype(jnp.float32)
    if interleaved:
        x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if attention_factor != 1.0:
        cos, sin = cos * attention_factor, sin * attention_factor
    out = (x * cos + half * sin).astype(data.dtype)
    if d == data.shape[3]:
        return out
    return jnp.concatenate([out, data[..., d:]], axis=-1)


@register("SwiGLU", aliases=("_contrib_SwiGLU",))
@_recomputed
def swiglu(gate, up, act="silu"):
    """``act(gate) * up``: the product of a gated MLP, taken in float32;
    ``act`` is ``silu`` (SwiGLU) or ``relu`` (ReGLU)."""
    g = gate.astype(jnp.float32)
    if act == "silu":
        g = g * jax.nn.sigmoid(g)
    elif act == "relu":
        g = jnp.maximum(g, 0.0)
    else:
        raise MXNetError(f"SwiGLU act {act!r} unsupported")
    return (g * up.astype(jnp.float32)).astype(up.dtype)


@register("CausalConv1D", aliases=("_contrib_CausalConv1D",))
@_recomputed
def causal_conv1d(data, weight, activation=None):
    """Depthwise causal convolution over the sequence axis: ``data``
    [batch, seq, channels], ``weight`` [channels, width];
    ``out_t = sum_j weight[:, j] * data_{t - (width - 1) + j}`` with zeros
    left of the sequence, summed in float32; ``activation="silu"`` is
    applied to the sum before it is rounded."""
    width = weight.shape[1]
    t = data.shape[1]
    x = jnp.pad(data.astype(jnp.float32), ((0, 0), (width - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(x[:, j:j + t, :] * w[:, j] for j in range(width))
    if activation == "silu":
        out = out * jax.nn.sigmoid(out)
    elif activation is not None:
        raise MXNetError(f"CausalConv1D activation {activation!r} unsupported")
    return out.astype(data.dtype)


@register("GQAttention", aliases=("_contrib_GQAttention",))
def gq_attention(query, key, value, causal=True, scale=None, window=0):
    """Softmax attention with grouped-query heads: ``query`` / ``key``
    [batch, seq, heads / kv_heads, dim], ``value`` [batch, seq, kv_heads,
    value dim] (the output's; latent attention's differs from ``dim``),
    each K/V head serving ``heads // kv_heads`` consecutive query heads;
    softmax in float32, ``scale`` by default ``dim ** -0.5``. ``window`` > 0 (causal only; 0 = none): query t sees keys
    t - window + 1 ... t. Long sequences take the flash kernel
    (``pallas_kernels.flash_attention`` owns the dispatch), which reads
    the shared K/V head through its index map; its gradient is a kernel
    too, which adds a group's ``dk``, ``dv`` onto the head they share
    in VMEM."""
    from .pallas_kernels import flash_attention
    out = flash_attention(jnp.swapaxes(query, 1, 2), jnp.swapaxes(key, 1, 2),
                          jnp.swapaxes(value, 1, 2), causal=causal,
                          scale=scale, window=window)
    return jnp.swapaxes(out, 1, 2)


# chunks of the rule's XLA path kept per checkpoint. It and ``grouped`` below
# serve that path alone: the kernels keep a state a chunk and need no group.
# No benchmark cell runs the XLA path, so the constant is not re-tuned.
RULE_GROUP = 8


@register("GatedDeltaRule", aliases=("_contrib_GatedDeltaRule",))
def gated_delta_rule(query, key, value, g, beta, chunk=64, eps=1e-6):
    """The gated delta rule (Gated DeltaNet's linear attention), causal, in
    chunks of ``chunk`` tokens. ``query`` / ``key`` [batch, seq, key_heads,
    dk], ``value`` [batch, seq, value_heads, dv], ``g`` (log decay, <= 0)
    and ``beta`` (write strength) [batch, seq, value_heads]; each key head
    serves ``value_heads // key_heads`` consecutive value heads. Returns
    ``o`` [batch, seq, value_heads, dv].

    With ``q^ = q / sqrt(sum q^2 + eps) / sqrt(dk)``, ``k^ = k / sqrt(sum
    k^2 + eps)`` and a state ``S`` [dk, dv] per value head, zero before a
    row's first token, for t = 1 ... T::

        S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k^_t);
        S <- S + k^_t d_t^T;  o_t = S^T q^_t

    Computed a chunk of C tokens at a time (``gamma_i = sum_{j <= i} g_j``
    inside the chunk): ``A_ij = beta_i (k^_i . k^_j) exp(gamma_i -
    gamma_j)`` for i > j; ``W = (I + A)^-1 (beta e^gamma * K^)``, ``U = (I +
    A)^-1 (beta * V)`` by forward substitution; then chunk after chunk
    ``V' = U - W S``, ``O = (Q^ * e^gamma) S + tril(Q^ K^T * e^(gamma_i -
    gamma_j)) V'``, ``S <- e^(gamma_C) S + (K^ * e^(gamma_C - gamma))^T V'``.
    The state, the decays, the substitution and every sum are float32; the
    products take their operands in ``value``'s type. A sequence that is
    no multiple of C is padded with tokens that leave the state alone.

    On a TPU, heads of whole 128-lane tiles take two Pallas kernels
    (``pallas_kernels.delta_rule``: ``_gdn_fwd_call`` and ``_gdn_bwd_call``
    in a capture): a program serves a key head's value heads, the state
    stays in VMEM from chunk to chunk, the substitution is made on the VPU
    (16 x 16 blocks) and of products at full float32 precision, and a
    derivative of its own keeps the arguments, the state before each chunk
    and each chunk's inverse, from which the backward kernel rebuilds a
    chunk's arrays.
    Anything else takes the same mathematics in plain XLA, a ``lax.scan``
    over groups of ``RULE_GROUP`` chunks: differentiated, that scan keeps
    each group's incoming state beside the arguments, and a group's own
    arrays are computed again in the backward pass (``jax.checkpoint``):
    one state per group and one group's chunk-local arrays are held,
    never a state per token."""
    return _delta_rule(query, key, value, g, beta, chunk, eps)


def _delta_rule(query, key, value, g, beta, chunk, eps, interpret=None,
                force=False):
    """The rule's one dispatch, from the backend and the shapes alone;
    ``force`` takes the kernels wherever the shapes admit them (the parity
    tests run them in interpret mode)."""
    from . import pallas_kernels as pk
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tiles = pk.delta_rule_tiles(query.shape[3], value.shape[3],
                                min(int(chunk), query.shape[1]))
    if tiles and (force or not interpret):
        return pk.delta_rule(query, key, value, g, beta, chunk=chunk, eps=eps,
                             interpret=bool(interpret))
    return _delta_rule_scan(query, key, value, g, beta, chunk, eps)


def _delta_rule_scan(query, key, value, g, beta, chunk, eps):
    """:func:`gated_delta_rule` in plain XLA: the path of every shape the
    kernels do not take, and the oracle of their tests."""
    b, t, hk, dk = query.shape
    hv, dv = value.shape[2:]
    f32, lo = jnp.float32, value.dtype
    c = min(int(chunk), t)
    n = -(-t // c)
    per = min(RULE_GROUP, n)
    groups = -(-n // per)
    pad = groups * per * c - t

    def grouped(x):
        """[batch, seq, heads, ...] -> [groups, per, batch, heads, c, ...];
        a padded token has q = k = v = 0, beta = 0 and g = 0: it leaves
        the state alone."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, groups, per, c) + x.shape[2:])
        return jnp.transpose(x, (1, 2, 0, 4, 3) + tuple(range(5, x.ndim)))

    def unit(x):
        x = x.astype(f32)
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)

    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(lo), y.astype(lo),
                          preferred_element_type=f32)

    rows = jnp.arange(c)
    lower = rows[:, None] >= rows[None, :]

    def step(state, xs):
        w, u, p, q_in, k_out, keep = xs
        v_new = u - dot("...ck,...kv->...cv", w, state)
        o = dot("...ck,...kv->...cv", q_in, state) + \
            dot("...ij,...jv->...iv", p, v_new)
        state = keep * state + dot("...ck,...cv->...kv", k_out, v_new)
        return state, o.astype(lo)

    def group(state, xs):
        """``per`` chunks: what is local to a chunk for all of them at
        once, then the state through them one after another."""
        q, k, v, g, beta = xs
        # each key head serves hv // hk consecutive value heads
        q, k = (jnp.repeat(x, hv // hk, axis=2)
                for x in (unit(q) * dk ** -0.5, unit(k)))
        gamma = jnp.cumsum(g.astype(f32), axis=-1)        # [per, b, hv, c]
        beta = beta.astype(f32)
        # exp(gamma_i - gamma_j) for i >= j, 0 above the diagonal
        decay = jnp.exp(jnp.where(lower, gamma[..., :, None] -
                                  gamma[..., None, :], -jnp.inf))
        a = jnp.where(rows[:, None] > rows[None, :],
                      beta[..., None] * dot("...id,...jd->...ij", k, k) *
                      decay, 0.0)
        rhs = jnp.concatenate([k * (beta * jnp.exp(gamma))[..., None],
                               v.astype(f32) * beta[..., None]], axis=-1)
        # (I + a)^-1 rhs: forward substitution in float32
        wu = lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True,
                                         unit_diagonal=True).astype(lo)
        p = (dot("...id,...jd->...ij", q, k) * decay).astype(lo)
        q_in = (q * jnp.exp(gamma)[..., None]).astype(lo)
        k_out = (k * jnp.exp(gamma[..., -1:] - gamma)[..., None]).astype(lo)
        keep = jnp.exp(gamma[..., -1])[..., None, None]
        return lax.scan(step, state,
                        (wu[..., :dk], wu[..., dk:], p, q_in, k_out, keep))

    _, o = lax.scan(jax.checkpoint(group), jnp.zeros((b, hv, dk, dv), f32),
                    tuple(grouped(x) for x in (query, key, value, g, beta)))
    # [groups, per, b, hv, c, dv] -> [b, seq, hv, dv]
    o = jnp.transpose(o, (2, 0, 1, 4, 3, 5))
    return o.reshape(b, groups * per * c, hv, dv)[:, :t]


@register("MoERoute", aliases=("_contrib_MoERoute",), num_outputs=3,
          optional_arrays=("expert_bias",))
def moe_route(data, router_weight, expert_bias=None, k=1, norm_topk=True,
              scale=1.0, score="sigmoid"):
    """Top-k routing without drops over all the experts
    (``parallel.moe.route``): ``(selection, gate, counts)``."""
    from ..parallel.moe import route
    return route(data, router_weight, expert_bias, k=k, norm_topk=norm_topk,
                 scale=scale, score=score)


@register("MoEExperts", aliases=("_contrib_MoEExperts",))
def moe_experts(data, selection, gate, w1, w3, w2, first=0, act="silu"):
    """The held experts' part of a routed gated-MLP layer
    (``parallel.moe.experts_held``); ``act`` gates each expert's product:
    ``silu`` or ``relu``."""
    from ..parallel.moe import experts_held
    return experts_held(data, selection, gate, w1, w3, w2, first=first,
                        act=act)
