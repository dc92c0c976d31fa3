"""Optimizers (ref: python/mxnet/optimizer/optimizer.py).

Each optimizer is two parts. The *host part* (``_step_scalars``) does the
per-index bookkeeping — update counts, the scheduled and multiplied lr and
wd, the step-count terms — and returns a short tuple of Python floats. The
*kernel* (``_kernel``) is a pure function ``(w, g, state, scalars) ->
(w, state)`` over jax arrays (the reference implements them as fused mshadow
kernels, src/operator/optimizer_op.cc). One jitted program maps the kernel
over however many leaves it is handed — one for ``Optimizer.update``, the
whole parameter tree for ``Updater.update_tree`` — and takes every scalar
that can change between steps in ONE float32 array, so a step is one
transfer and one dispatch whatever the lr schedule or the batch size does.
The Optimizer/Updater API surface (registry, lr/wd multipliers,
multi-precision fp32 master weights, num_update-driven schedules) matches
the reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, registry as _registry
from ..ndarray import NDArray
from ..ndarray.sparse import RowSparseNDArray

_reg = _registry("optimizer")


def register(klass):
    _reg.register(klass)
    return klass


def create(name, **kwargs):
    if isinstance(name, Optimizer):
        return name
    return _reg.get(name)(**kwargs)


def _arrays(state):
    """The jax arrays of an optimizer state, in the state's own nesting."""
    if state is None:
        return None
    if isinstance(state, (tuple, list)):
        return tuple(_arrays(s) for s in state)
    return state._data


def _store(state, new):
    """Write a kernel's new state arrays into the NDArray wrappers."""
    if state is None:
        return
    if isinstance(state, (tuple, list)):
        for s, n in zip(state, new):
            _store(s, n)
    else:
        state._data = new


def _leaf_scalars(w, s):
    # a Python float meets an array in the array's precision; the packed
    # row is float32, so hand a half-precision leaf its scalars in kind
    return s.astype(w.dtype) if jnp.issubdtype(w.dtype, jnp.floating) else s


@functools.lru_cache(maxsize=64)
def _step_program(cls, fields, name, rows=False):
    """The jitted program of one optimizer configuration: ``cls``'s kernel
    with ``fields`` (its ``_kernel_fields`` and their values) as trace-time
    constants, mapped over the leaves it is called with. ``jax.jit``
    re-specialises on the leaves' shapes and the tree's structure itself;
    nothing else about a step reaches the trace, so two optimizers of one
    configuration share the executable. ``rows`` gives the single-leaf
    program of the row-sparse kernel instead."""
    opt = object.__new__(cls)
    vars(opt).update(fields)    # a kernel reading any other field fails loudly

    def leaf(w, g, state, s):
        if opt.multi_precision and isinstance(state, tuple) and state \
                and isinstance(state[0], jax.Array) \
                and state[0].dtype == jnp.float32 \
                and w.dtype != jnp.float32:
            # fp32 master weights (update_multi_precision, in one program)
            master, inner = state
            master, inner = opt._kernel(master, g.astype(jnp.float32),
                                        inner, s)
            return master.astype(w.dtype), (master, inner)
        return opt._kernel(w, g, state, _leaf_scalars(w, s))

    def tree(ws, gs, states, scalars):
        return [leaf(w, g, st, scalars[i])
                for i, (w, g, st) in enumerate(zip(ws, gs, states))]

    def row_leaf(w, g, idx, state, s):
        return opt._kernel_rows(w, g, idx, state, _leaf_scalars(w, s))

    fn = row_leaf if rows else tree
    # the program runs as jit_<name>(<fingerprint>)
    fn.__name__ = name + "_rows" if rows else name
    # the optimizer's state is the Updater's alone and every step rewrites
    # it whole: donated, it is updated in place, where a second copy of a
    # large model's state (12 bytes a parameter under multi-precision Adam)
    # would not fit beside the first. Weights are not donated: whoever
    # holds the old array keeps it.
    return jax.jit(fn, donate_argnums=() if rows else (2,))


class Optimizer:
    # the fields a kernel may read: constants of the optimizer, closed over
    # when its program is traced (and part of the program's cache key)
    _kernel_fields = ("clip_gradient", "multi_precision")
    # row-sparse gradients touch only their rows, where the optimizer has
    # a row kernel; without one (or with lazy_update off) they are
    # densified
    lazy_update = True
    _kernel_rows = None

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None,
                 sym=None, begin_num_update=0, multi_precision=False,
                 param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate
        self.wd = wd
        self.clip_gradient = clip_gradient
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self.multi_precision = multi_precision
        self._index_update_count = {}
        self.idx2name = dict(param_idx2name or {})
        self.param_dict = param_dict or {}
        self.lr_mult = {}
        self.wd_mult = {}

    # -- lr / wd bookkeeping ----------------------------------------------
    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise MXNetError("lr_scheduler is set; cannot set lr directly")
        self.lr = lr

    @property
    def learning_rate(self):
        if self.lr_scheduler is not None:
            return self.lr_scheduler(self.num_update)
        return self.lr

    def set_lr_mult(self, args_lr_mult):
        self.lr_mult = dict(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        self.wd_mult = {}
        for n in self.idx2name.values():
            if not (n.endswith("_weight") or n.endswith("_gamma")):
                self.wd_mult[n] = 0.0
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if index not in self._index_update_count:
            self._index_update_count[index] = self.begin_num_update
        self._index_update_count[index] += 1
        self.num_update = max(self._index_update_count[index], self.num_update)

    def _get_lr(self, index):
        lr = self.lr_scheduler(self.num_update) if self.lr_scheduler else self.lr
        if index in self.param_dict:
            lr *= self.param_dict[index].lr_mult
        elif index in self.lr_mult:
            lr *= self.lr_mult[index]
        elif index in self.idx2name:
            lr *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lr

    def _get_wd(self, index):
        wd = self.wd
        if index in self.param_dict:
            wd *= self.param_dict[index].wd_mult
        elif index in self.wd_mult:
            wd *= self.wd_mult[index]
        elif index in self.idx2name:
            wd *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wd

    # -- state ------------------------------------------------------------
    def create_state(self, index, weight):
        return None

    def create_state_multi_precision(self, index, weight):
        """fp32 master copy for fp16/bf16 weights (ref: optimizer.py:208)."""
        if self.multi_precision and weight.dtype in (np.float16, np.dtype("bfloat16")):
            master = NDArray(weight._data.astype(jnp.float32))
            return (master, self.create_state(index, master))
        return self.create_state(index, weight)

    # -- the step: host part, kernel, program ------------------------------
    def _step_scalars(self, index):
        """Host part of one index's step: count it, then everything the
        kernel needs that can change from step to step, as Python floats
        (computed in double, as the reference's Python does). Subclasses
        extend or replace the tuple; its layout is theirs and their
        kernel's alone."""
        self._update_count(index)
        return (self._get_lr(index), self._get_wd(index), self.rescale_grad)

    def _kernel(self, w, g, state, s):
        """Pure update of one leaf: jax arrays in, ``(w, state)`` out;
        ``s`` is the leaf's row of ``_step_scalars``."""
        raise NotImplementedError

    def _kernel_name(self):
        """What the step's program is called in a trace."""
        return "_%s_step" % type(self).__name__.lower()

    def _grad(self, w, g, rescale, wd):
        g = g * rescale
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        return g + wd * w

    def _program(self, rows=False):
        return _step_program(type(self),
                             tuple((f, getattr(self, f))
                                   for f in self._kernel_fields),
                             self._kernel_name(), rows)

    def _apply(self, leaves, scalars):
        """One dispatch over ``leaves`` — ``(weight, grad, state)`` of
        NDArrays — with ``scalars`` their ``_step_scalars`` rows; the new
        arrays land in the wrappers. The state's old arrays are donated to
        the program; the old weights stay readable for whoever holds
        them."""
        outs = self._program()(
            [w._data for w, _, _ in leaves], [g._data for _, g, _ in leaves],
            [_arrays(st) for _, _, st in leaves],
            np.asarray(scalars, np.float32))
        for (w, _, st), (new_w, new_st) in zip(leaves, outs):
            w._data = new_w
            _store(st, new_st)

    def update(self, index, weight, grad, state):
        s = self._step_scalars(index)
        if isinstance(grad, RowSparseNDArray):
            if self._kernel_rows is not None and self.lazy_update:
                new_w, new_st = self._program(rows=True)(
                    weight._data, grad.data._data, grad.indices._data,
                    _arrays(state), np.asarray(s, np.float32))
                weight._data = new_w
                _store(state, new_st)
                return
            grad = grad.tostype("default")
        self._apply([(weight, grad, state)], [s])

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and isinstance(state, tuple) and \
                isinstance(state[0], NDArray) and \
                state[0]._data.dtype == jnp.float32 and \
                weight._data.dtype != jnp.float32:
            master, inner = state
            grad32 = NDArray(grad._data.astype(jnp.float32))
            self.update(index, master, grad32, inner)
            weight._data = master._data.astype(weight._data.dtype)
        else:
            self.update(index, weight, grad, state)

    def _fuses(self):
        """Whether a step of this optimizer is a pure function of arrays
        and scalars: the built-in ``update`` (host part, kernel) and not a
        subclass's own, which may do anything."""
        cls = type(self)
        return cls.update is Optimizer.update and \
            cls.update_multi_precision is Optimizer.update_multi_precision

    def _preprocess(self, weight, grad, wd):
        return self._grad(weight._data, grad._data, self.rescale_grad, wd)


@register
class SGD(Optimizer):
    """SGD with momentum + optional multi-precision
    (ref: optimizer.py SGD; kernels src/operator/optimizer_op.cc:32)."""

    _kernel_fields = Optimizer._kernel_fields + ("momentum",)

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def _step(self, w, g, s):
        lr, wd, rescale = s
        return w - lr * self._grad(w, g, rescale, wd), None

    def _step_mom(self, w, g, mom, s):
        lr, wd, rescale = s
        mom = self.momentum * mom - lr * self._grad(w, g, rescale, wd)
        return w + mom, mom

    def _kernel(self, w, g, state, s):
        if state is None:
            return self._step(w, g, s)
        return self._step_mom(w, g, state, s)

    def _kernel_name(self):
        return "_step_mom" if self.momentum != 0.0 else "_step"

    def _kernel_rows(self, w, g, rows, state, s):
        """Row-sparse lazy update: touch only the gradient's rows
        (ref: src/operator/optimizer_op.cc:32 sgd_update rsp kernel —
        scatter on HBM instead of a full-matrix write)."""
        lr, wd, rescale = s
        g = self._grad(w[rows], g, rescale, wd)
        if state is None:
            return w.at[rows].add(-lr * g), None
        new_mom_rows = self.momentum * state[rows] - lr * g
        return w.at[rows].add(new_mom_rows), \
            state.at[rows].set(new_mom_rows)


@register
class Signum(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("momentum",)

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def _step_scalars(self, index):
        lr, wd, rescale = super()._step_scalars(index)
        return (lr, wd, rescale, 1 - lr * self.wd_lh)

    def _kernel(self, w, g, state, s):
        lr, wd, rescale, keep = s
        g = self._grad(w, g, rescale, wd)
        if state is None:
            return keep * w - lr * jnp.sign(g), None
        state = self.momentum * state - (1 - self.momentum) * g
        return keep * w + lr * jnp.sign(state), state


@register
class NAG(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("momentum",)

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return None
        return NDArray(jnp.zeros_like(weight._data))

    def _kernel(self, w, g, state, s):
        lr, wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        if state is None:
            return w - lr * g, None
        state = self.momentum * state + g
        return w - lr * (g + self.momentum * state), state


@register
class Adam(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("beta1", "beta2", "epsilon")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def _step_scalars(self, index):
        lr, wd, rescale = super()._step_scalars(index)
        t = self._index_update_count[index]
        lr_t = lr * math.sqrt(1 - self.beta2 ** t) / (1 - self.beta1 ** t)
        return (lr_t, wd, rescale)

    def _kernel(self, w, g, state, s):
        lr_t, wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        m, v = state
        m = self.beta1 * m + (1 - self.beta1) * g
        v = self.beta2 * v + (1 - self.beta2) * g * g
        return w - lr_t * m / (jnp.sqrt(v) + self.epsilon), (m, v)


@register
class AdaGrad(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("float_stable_eps",)

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def _kernel(self, w, g, state, s):
        lr, wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        state = state + g * g
        return w - lr * g / (jnp.sqrt(state) + self.float_stable_eps), state

    def _kernel_rows(self, w, g, rows, state, s):
        # row-sparse AdaGrad: only the touched rows accumulate history
        # (ref: optimizer_op.cc adagrad rsp kernel — the wide_deep path's
        # standard optimizer)
        lr, wd, rescale = s
        g = self._grad(w[rows], g, rescale, wd)
        hist_rows = state[rows] + g * g
        return w.at[rows].add(
            -lr * g / (jnp.sqrt(hist_rows) + self.float_stable_eps)), \
            state.at[rows].set(hist_rows)


@register
class RMSProp(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + (
        "gamma1", "gamma2", "epsilon", "centered", "clip_weights")

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9,
                 epsilon=1e-8, centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.epsilon = epsilon
        self.centered = centered
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (NDArray(jnp.zeros_like(weight._data)),
                    NDArray(jnp.zeros_like(weight._data)),
                    NDArray(jnp.zeros_like(weight._data)))
        return NDArray(jnp.zeros_like(weight._data))

    def _kernel(self, w, g, state, s):
        lr, wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        if self.centered:
            n, gmean, delta = state
            n = (1 - self.gamma1) * g * g + self.gamma1 * n
            gmean = (1 - self.gamma1) * g + self.gamma1 * gmean
            delta = self.gamma2 * delta - lr * g / jnp.sqrt(
                n - gmean * gmean + self.epsilon)
            w, state = w + delta, (n, gmean, delta)
        else:
            state = (1 - self.gamma1) * g * g + self.gamma1 * state
            w = w - lr * g / jnp.sqrt(state + self.epsilon)
        if self.clip_weights:
            w = jnp.clip(w, -self.clip_weights, self.clip_weights)
        return w, state


@register
class AdaDelta(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("rho", "epsilon")

    def __init__(self, rho=0.9, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def _step_scalars(self, index):
        self._update_count(index)
        return (self._get_wd(index), self.rescale_grad)

    def _kernel(self, w, g, state, s):
        wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        acc_g, acc_delta = state
        acc_g = self.rho * acc_g + (1 - self.rho) * g * g
        delta = jnp.sqrt(acc_delta + self.epsilon) / jnp.sqrt(
            acc_g + self.epsilon) * g
        acc_delta = self.rho * acc_delta + (1 - self.rho) * delta * delta
        return w - delta, (acc_g, acc_delta)


@register
class Ftrl(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("lamda1", "beta")

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),  # z
                NDArray(jnp.zeros_like(weight._data)))  # n

    def _kernel(self, w, g, state, s):
        lr, wd, rescale = s
        g = g * rescale
        if self.clip_gradient is not None:
            g = jnp.clip(g, -self.clip_gradient, self.clip_gradient)
        z, n = state
        sigma = (jnp.sqrt(n + g * g) - jnp.sqrt(n)) / lr
        z = z + g - sigma * w
        n = n + g * g
        w = jnp.where(
            jnp.abs(z) > self.lamda1,
            -(z - jnp.sign(z) * self.lamda1)
            / ((self.beta + jnp.sqrt(n)) / lr + wd),
            0.0)
        return w, (z, n)


@register
class Adamax(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("beta1", "beta2")

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def _step_scalars(self, index):
        lr, wd, rescale = super()._step_scalars(index)
        t = self._index_update_count[index]
        return (lr / (1 - self.beta1 ** t), wd, rescale)

    def _kernel(self, w, g, state, s):
        lr_t, wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        m, u = state
        m = self.beta1 * m + (1 - self.beta1) * g
        u = jnp.maximum(self.beta2 * u, jnp.abs(g))
        return w - lr_t * m / (u + 1e-8), (m, u)


@register
class Nadam(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("beta1", "beta2", "epsilon")

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.0

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def _step_scalars(self, index):
        lr, wd, rescale = super()._step_scalars(index)
        t = self._index_update_count[index]
        mu_t = self.beta1 * (1 - 0.5 * 0.96 ** (t * self.schedule_decay))
        mu_tp1 = self.beta1 * (1 - 0.5 * 0.96 ** ((t + 1) * self.schedule_decay))
        self.m_schedule = self.m_schedule * mu_t
        m_sched_next = self.m_schedule * mu_tp1
        return (lr, wd, rescale, 1 - self.m_schedule, 1 - m_sched_next,
                1 - self.beta2 ** t, 1 - mu_t, mu_tp1)

    def _kernel(self, w, g, state, s):
        lr, wd, rescale, g_div, m_div, v_div, g_mix, m_mix = s
        g = self._grad(w, g, rescale, wd)
        m, v = state
        m = self.beta1 * m + (1 - self.beta1) * g
        v = self.beta2 * v + (1 - self.beta2) * g * g
        g_prime = g / g_div
        m_prime = m / m_div
        v_prime = v / v_div
        m_bar = g_mix * g_prime + m_mix * m_prime
        return w - lr * m_bar / (jnp.sqrt(v_prime) + self.epsilon), (m, v)


@register
class SGLD(Optimizer):
    """Stochastic gradient Langevin dynamics. Its noise comes from the
    global key stream, so it keeps an ``update`` of its own: one
    dispatch per index, in the caller's order."""

    def update(self, index, weight, grad, state):
        from .. import random as _random
        self._update_count(index)
        lr, wd = self._get_lr(index), self._get_wd(index)
        g = self._preprocess(weight, grad, wd)
        noise = jax.random.normal(_random.next_key(), weight._data.shape,
                                  weight._data.dtype) * math.sqrt(lr)
        weight._data = weight._data - lr / 2 * g + noise


@register
class FTML(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("beta1", "beta2", "epsilon")

    def __init__(self, learning_rate=0.0025, beta1=0.6, beta2=0.999,
                 epsilon=1e-8, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)),
                NDArray(jnp.zeros_like(weight._data)))

    def _step_scalars(self, index):
        lr, wd, rescale = super()._step_scalars(index)
        t = self._index_update_count[index]
        return (wd, rescale, (1 - self.beta1 ** t) / lr,
                1 - self.beta2 ** t)

    def _kernel(self, w, g, state, s):
        wd, rescale, d_scale, v_div = s
        g = self._grad(w, g, rescale, wd)
        d, v, z = state
        v = self.beta2 * v + (1 - self.beta2) * g * g
        d_t = d_scale * (jnp.sqrt(v / v_div) + self.epsilon)
        sigma = d_t - self.beta1 * d
        z = self.beta1 * z + (1 - self.beta1) * g - sigma * w
        return -z / d_t, (d_t, v, z)


@register
class DCASGD(Optimizer):
    _kernel_fields = Optimizer._kernel_fields + ("momentum", "lamda")

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lamda = lamda

    def create_state(self, index, weight):
        mom = NDArray(jnp.zeros_like(weight._data)) if self.momentum else None
        return (mom, NDArray(jnp.copy(weight._data)))

    def _kernel(self, w, g, state, s):
        lr, wd, rescale = s
        g = self._grad(w, g, rescale, wd)
        mom, prev = state
        comp = g + self.lamda * g * g * (w - prev)
        if mom is not None:
            mom = self.momentum * mom - lr * comp
            delta = mom
        else:
            delta = -lr * comp
        return w + delta, (mom, w)


@register
class LBSGD(SGD):
    """Large-batch SGD with LARS-style layer-wise scaling
    (ref: optimizer.py LBSGD)."""

    _kernel_rows = None

    def __init__(self, momentum=0.0, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32,
                 begin_epoch=0, num_epochs=60, **kwargs):
        super().__init__(momentum=momentum, **kwargs)
        if warmup_strategy not in ("linear", "power2", "sqrt", "lars"):
            raise ValueError(f"unknown warmup_strategy {warmup_strategy!r}")
        self.warmup_strategy = warmup_strategy
        self.warmup_updates = int(warmup_epochs * updates_per_epoch)
        self.batch_scale = batch_scale
        self.init_updates = int(begin_epoch * updates_per_epoch)

    def _get_lr(self, index):
        """Warm the lr up over the first warmup_epochs toward
        batch_scale × base lr (ref: optimizer.py LBSGD._get_lr)."""
        lr = super()._get_lr(index)
        nup = max(self.num_update - self.init_updates, 0)
        target = lr * self.batch_scale
        if nup >= self.warmup_updates or self.warmup_updates == 0:
            return target
        frac = nup / self.warmup_updates
        if self.warmup_strategy == "linear":
            return lr + (target - lr) * frac
        if self.warmup_strategy == "power2":
            return lr + (target - lr) * frac * frac
        if self.warmup_strategy == "sqrt":
            return lr + (target - lr) * (frac ** 0.5)
        return lr  # "lars": constant base lr during warmup

    def _lars_step(self, w, g, mom, s):
        # trust ratio computed on device — no host round-trip per parameter
        lr, wd, rescale = s
        g = g * rescale
        wnorm = jnp.linalg.norm(w)
        gnorm = jnp.linalg.norm(g)
        ratio = jnp.where((wnorm > 0) & (gnorm > 0),
                          wnorm / (gnorm + wd * wnorm + 1e-9), 1.0)
        g = g + wd * w
        mom = self.momentum * mom - (lr * ratio) * g
        return w + mom, mom

    _kernel = _lars_step

    def _kernel_name(self):
        return "_lars_step"

    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))


@register
class Test(Optimizer):
    def create_state(self, index, weight):
        return NDArray(jnp.zeros_like(weight._data))

    def _step_scalars(self, index):
        return (self.rescale_grad,)

    def _kernel(self, w, g, state, s):
        return w - s[0] * g, state


class Updater:
    """Apply an optimizer, holding per-index states
    (ref: optimizer.py get_updater; used by KVStore servers)."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}

    def _state(self, index, weight):
        if index not in self.states:
            self.states[index] = \
                self.optimizer.create_state_multi_precision(index,
                                                            weight)
        return self.states[index]

    def _after(self, triples):
        from ..profiling import health as _health
        from ..profiling import memory as _mem
        if _health.enabled() and not _health.updater_is_covered():
            # optimizer in/out sentry: the incoming gradient and the
            # updated weight in ONE lazy reduce per index — kvstore
            # servers and Module.update get the same coverage as a
            # local Trainer (whose StepProbe covers its whole step in
            # one program and suppresses this per-index check)
            for index, grad, weight in triples:
                name = self.optimizer.idx2name.get(index, str(index))
                _health.check("optimizer/%s" % name, [grad, weight])
        if _mem.census_enabled():
            # updates are functional (fresh jax arrays land in the
            # NDArray wrappers), so the census roles are re-stamped
            # here — one weakref-table write per array, no device work
            for index, grad, weight in triples:
                _mem.tag_tree(self.states[index], "optimizer_state")
                _mem.tag_role(weight, "parameter")
                _mem.tag_role(grad, "gradient")

    def __call__(self, index, grad, weight):
        self.optimizer.update_multi_precision(
            index, weight, grad, self._state(index, weight))
        self._after([(index, grad, weight)])

    def update_tree(self, triples):
        """``__call__`` over many ``(index, grad, weight)`` at once: the
        host parts run in the order given, so update counts and the lr
        schedule read as the per-index loop showed them, and then ONE
        program updates every leaf whose step is a pure function of arrays
        and scalars. A row-sparse gradient, or an optimizer whose class
        brings an ``update`` of its own, goes per index where it stands
        in that order: one dispatch each."""
        opt = self.optimizer
        fuses = opt._fuses()
        leaves, scalars = [], []
        for index, grad, weight in triples:
            state = self._state(index, weight)
            if fuses and not isinstance(grad, RowSparseNDArray):
                scalars.append(opt._step_scalars(index))
                leaves.append((weight, grad, state))
            else:
                opt.update_multi_precision(index, weight, grad, state)
        if leaves:
            opt._apply(leaves, scalars)
        self._after(triples)

    def get_states(self, dump_optimizer=False):
        import pickle
        payload = {"states": {k: _state_to_np(v)
                              for k, v in self.states.items()}}
        if dump_optimizer:
            payload["optimizer"] = self.optimizer
        return pickle.dumps(payload)

    def set_states(self, states):
        import pickle
        loaded = pickle.loads(states)
        if "optimizer" in loaded:
            self.optimizer = loaded["optimizer"]
        self.states = {k: _state_from_np(v)
                       for k, v in loaded["states"].items()}


def _state_to_np(state):
    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_state_to_np(s) for s in state)
    return state.asnumpy()


def _state_from_np(state):
    from ..ndarray import array
    if state is None:
        return None
    if isinstance(state, tuple):
        return tuple(_state_from_np(s) for s in state)
    return array(state)


def get_updater(optimizer):
    return Updater(optimizer)
