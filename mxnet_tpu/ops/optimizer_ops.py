"""Fused optimizer-update ops (ref: src/operator/optimizer_op.cc:32-41,
src/operator/contrib/optimizer_op.cc).

The reference exposes each optimizer's update rule as a standalone op so
user code and `update_on_kvstore` servers can apply updates without a
Python Optimizer object. Here each op is one pure jitted XLA program —
elementwise chains the compiler fuses into a single HBM pass (the
reference's hand-written mshadow kernels).

Pure-functional convention: the reference mutates state inputs (mom,
mean/var, z/n...) in place and returns the weight; these ops return
``(out_weight, *updated_states)`` instead. The nd-layer wrappers in
`mxnet_tpu.optimizer.ops` restore the mutate-in-place call surface for
API compatibility.

Clip convention throughout (matching dmlc param docs): clip_gradient
< 0 disables clipping.
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from .registry import register


def _clip(g, c):
    # clip bounds are static op attrs, so this resolves at trace time
    if c is None or c < 0:
        return g
    return jnp.clip(g, -c, c)


def _fused_kernel_enabled():
    """MXTPU_KERNEL_FUSED_OPT: route sgd_mom/adam through the Pallas
    one-pass update kernel (ops/pallas_kernels.fused_*). ``auto``
    (default) = the ``tpu`` backend only — the jnp path below IS the CPU hot
    path and the kernel's numerics oracle, so behavior off-chip is
    unchanged. Resolves at trace time (static env read, no tracer
    impurity)."""
    v = os.environ.get("MXTPU_KERNEL_FUSED_OPT", "auto").lower()
    if v in ("0", "off", "false", "no"):
        return False
    if v in ("1", "on", "true", "yes"):
        return True
    import jax
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# SGD family (ref: optimizer_op-inl.h SGDKernel / SGDMomKernel)
# ---------------------------------------------------------------------------


@register("sgd_update")
def sgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
               clip_gradient=-1.0, lazy_update=True):
    """out = (1 - lr*wd)*w - lr*clip(rescale*g)."""
    g = _clip(rescale_grad * grad, clip_gradient)
    return (1.0 - lr * wd) * weight - lr * g


@register("sgd_mom_update", num_outputs=2)
def sgd_mom_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                   rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True):
    """mom' = mu*mom - lr*wd*w - lr*clip(rescale*g); out = w + mom'."""
    if _fused_kernel_enabled():
        from .pallas_kernels import fused_sgd_mom
        return fused_sgd_mom(weight, grad, mom, lr=lr,
                             momentum=momentum, wd=wd,
                             rescale_grad=rescale_grad,
                             clip_gradient=clip_gradient)
    g = _clip(rescale_grad * grad, clip_gradient)
    mom = momentum * mom - lr * wd * weight - lr * g
    return weight + mom, mom


@register("mp_sgd_update", num_outputs=2)
def mp_sgd_update(weight, grad, weight32, lr=0.01, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, lazy_update=True):
    """Multi-precision SGD: the update runs on the fp32 master copy, the
    low-precision weight output is a cast of it (ref: optimizer_op-inl.h
    MP_SGDKernel)."""
    g = _clip(rescale_grad * grad.astype(jnp.float32), clip_gradient)
    w32 = (1.0 - lr * wd) * weight32 - lr * g
    return w32.astype(weight.dtype), w32


@register("mp_sgd_mom_update", num_outputs=3)
def mp_sgd_mom_update(weight, grad, mom, weight32, lr=0.01, momentum=0.0,
                      wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                      lazy_update=True):
    g = _clip(rescale_grad * grad.astype(jnp.float32), clip_gradient)
    mom = momentum * mom - lr * wd * weight32 - lr * g
    w32 = weight32 + mom
    return w32.astype(weight.dtype), mom, w32


@register("signsgd_update")
def signsgd_update(weight, grad, lr=0.01, wd=0.0, rescale_grad=1.0,
                   clip_gradient=-1.0):
    """out = (1 - lr*wd)*w - lr*sign(g); clip has no effect (ref:
    SignSGDKernel comment)."""
    return (1.0 - lr * wd) * weight - lr * jnp.sign(grad)


@register("signum_update", num_outputs=2)
def signum_update(weight, grad, mom, lr=0.01, momentum=0.0, wd=0.0,
                  rescale_grad=1.0, clip_gradient=-1.0, wd_lh=0.0):
    """mom' = mu*mom - (1-mu)*(wd*w + clip(rescale*g));
    out = (1 - lr*wd_lh)*w + lr*sign(mom') (ref: SignumKernel)."""
    g = _clip(rescale_grad * grad, clip_gradient)
    mom = momentum * mom - (1.0 - momentum) * wd * weight \
        - (1.0 - momentum) * g
    return (1.0 - lr * wd_lh) * weight + lr * jnp.sign(mom), mom


# ---------------------------------------------------------------------------
# Adam / FTML / FTRL (ref: optimizer_op-inl.h AdamUpdate/FTMLKernel/
# FtrlUpdate)
# ---------------------------------------------------------------------------


@register("adam_update", num_outputs=3)
def adam_update(weight, grad, mean, var, lr=0.01, beta1=0.9, beta2=0.999,
                epsilon=1e-8, wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                lazy_update=True):
    """No in-kernel bias correction — the Python optimizer folds it into
    lr, matching the reference kernel exactly."""
    if _fused_kernel_enabled():
        from .pallas_kernels import fused_adam
        return fused_adam(weight, grad, mean, var, lr=lr, beta1=beta1,
                          beta2=beta2, epsilon=epsilon, wd=wd,
                          rescale_grad=rescale_grad,
                          clip_gradient=clip_gradient)
    g = _clip(rescale_grad * grad + wd * weight, clip_gradient)
    mean = beta1 * mean + (1.0 - beta1) * g
    var = beta2 * var + (1.0 - beta2) * jnp.square(g)
    out = weight - lr * mean / (jnp.sqrt(var) + epsilon)
    return out, mean, var


@register("ftml_update", num_outputs=4)
def ftml_update(weight, grad, d, v, z, lr=0.01, beta1=0.6, beta2=0.999,
                epsilon=1e-8, t=1, wd=0.0, rescale_grad=1.0,
                clip_grad=-1.0):
    g = _clip(rescale_grad * grad + wd * weight, clip_grad)
    v_new = beta2 * v + (1.0 - beta2) * jnp.square(g)
    d_t = (1.0 - beta1 ** t) / lr * (
        jnp.sqrt(v_new / (1.0 - beta2 ** t)) + epsilon)
    z_new = beta1 * z + (1.0 - beta1) * g - (d_t - beta1 * d) * weight
    return -z_new / d_t, d_t, v_new, z_new


@register("ftrl_update", num_outputs=3)
def ftrl_update(weight, grad, z, n, lr=0.1, lamda1=0.01, beta=1.0, wd=0.0,
                rescale_grad=1.0, clip_gradient=-1.0):
    g = _clip(rescale_grad * grad, clip_gradient)
    z_new = z + g - (jnp.sqrt(n + jnp.square(g)) - jnp.sqrt(n)) * weight / lr
    n_new = n + jnp.square(g)
    out = (jnp.sign(z_new) * lamda1 - z_new) / (
        (beta + jnp.sqrt(n_new)) / lr + wd) * (jnp.abs(z_new) > lamda1)
    return out, z_new, n_new


# ---------------------------------------------------------------------------
# RMSProp (ref: optimizer_op-inl.h RMSPropUpdate / RMSPropAlexUpdate)
# ---------------------------------------------------------------------------


@register("rmsprop_update", num_outputs=2)
def rmsprop_update(weight, grad, n, lr=0.01, gamma1=0.95, epsilon=1e-8,
                   wd=0.0, rescale_grad=1.0, clip_gradient=-1.0,
                   clip_weights=-1.0):
    """Tieleman & Hinton non-centered RMSProp."""
    g = _clip(rescale_grad * grad + wd * weight, clip_gradient)
    n_new = (1.0 - gamma1) * jnp.square(g) + gamma1 * n
    out = weight - lr * g / jnp.sqrt(n_new + epsilon)
    if clip_weights >= 0:
        out = jnp.clip(out, -clip_weights, clip_weights)
    return out, n_new


@register("rmspropalex_update", num_outputs=4)
def rmspropalex_update(weight, grad, n, g, delta, lr=0.01, gamma1=0.95,
                       gamma2=0.9, epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                       clip_gradient=-1.0, clip_weights=-1.0):
    """Graves 2013 centered RMSProp with momentum."""
    gr = _clip(rescale_grad * grad + wd * weight, clip_gradient)
    n_new = (1.0 - gamma1) * jnp.square(gr) + gamma1 * n
    g_new = (1.0 - gamma1) * gr + gamma1 * g
    delta_new = gamma2 * delta - lr * gr / jnp.sqrt(
        n_new - jnp.square(g_new) + epsilon)
    out = weight + delta_new
    if clip_weights >= 0:
        out = jnp.clip(out, -clip_weights, clip_weights)
    return out, n_new, g_new, delta_new


# ---------------------------------------------------------------------------
# AdaGrad (ref: optimizer_op-inl.h AdagradDnsRspDnsKernel — registered as
# _sparse_adagrad_update; contrib/optimizer_op-inl.h GroupAdagrad)
# ---------------------------------------------------------------------------


@register("_sparse_adagrad_update", num_outputs=2)
def sparse_adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-7,
                          wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """Dense lowering of the rsp kernel: rows absent from a row-sparse
    gradient have g=0 so h and w are unchanged — the dense form computes
    the same fixpoint. RowSparse callers go through
    Optimizer AdaGrad's row-granular path."""
    g = _clip(rescale_grad * grad, clip_gradient)
    h_new = history + jnp.square(g)
    return weight - lr * g / jnp.sqrt(h_new + epsilon), h_new


@register("_contrib_group_adagrad_update", num_outputs=2,
          aliases=("group_adagrad_update",))
def group_adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-5,
                         rescale_grad=1.0, clip_gradient=-1.0):
    """Per-row (group) accumulator: h_row += mean(g_row^2); every element
    of the row divides by the same sqrt(h_row+eps) (ref: contrib
    GroupAdagradKernel state update `grad_ssq / row_length`)."""
    g = _clip(rescale_grad * grad, clip_gradient)
    red_axes = tuple(range(1, g.ndim))
    h_new = history + jnp.mean(jnp.square(g), axis=red_axes).reshape(
        history.shape)
    denom = jnp.sqrt(
        h_new.reshape((-1,) + (1,) * (g.ndim - 1)) + epsilon)
    return weight - lr * g / denom, h_new
