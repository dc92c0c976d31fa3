"""Custom Python operators
(ref: python/mxnet/operator.py:426 CustomOp / :472 CustomOpProp,
src/operator/custom/custom.cc).

The reference runs Python callbacks on a dedicated worker thread wired
into the dependency engine. The TPU-native escape hatch is
``jax.pure_callback``: in eager mode the callback runs directly; inside
a jit/hybridize trace XLA inserts a host callback at that point in the
program. Gradients route back through the user's ``backward`` via
``jax.custom_vjp``, so custom ops compose with autograd and hybridize.

The callback stays on NumPy. It runs on a thread of the runtime that is
executing the enclosing program, and device work started from there —
``jnp.zeros`` is a jitted program of its own — waits for that runtime
and never returns. So ``forward``/``backward`` are handed NDArrays
whose buffers are host NumPy memory, and ``assign`` keeps them so: read
with ``.asnumpy()``, compute in NumPy, hand ``assign`` the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError
from .ndarray import NDArray

_REGISTRY = {}


class CustomOp:
    """Base class for user ops (ref: operator.py:426)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        if req in ("null",):
            return
        # host NumPy in, host NumPy kept (see the module docstring); an
        # NDArray src is read back, which is a copy and not a program
        src = np.asarray(src._data if isinstance(src, NDArray) else src)
        if req == "add":
            dst._data = np.asarray(dst._data) + src
        else:  # write / inplace
            dst._data = src


class CustomOpProp:
    """Op metadata + factory (ref: operator.py:472)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def infer_type(self, in_type):
        return in_type, [in_type[0]] * len(self.list_outputs()), \
            [in_type[0]] * len(self.list_auxiliary_states())

    def create_operator(self, ctx, in_shapes, in_dtypes):
        raise NotImplementedError

    def need_top_grad(self):
        return self.need_top_grad_


def register(reg_name):
    """Decorator registering a CustomOpProp subclass under op_type
    (ref: operator.py register)."""

    def deco(prop_cls):
        _REGISTRY[reg_name] = prop_cls
        return prop_cls

    return deco


def get_registered(op_type):
    try:
        return _REGISTRY[op_type]
    except KeyError:
        raise MXNetError(
            f"custom op type {op_type!r} is not registered; decorate its "
            "CustomOpProp with @mx.operator.register(...)") from None


def _host_nd(value):
    """An NDArray over host NumPy memory, for the arrays a CustomOp sees
    inside the host callback (the constructor would make a device
    array, which is device work inside the callback)."""
    arr = NDArray.__new__(NDArray)
    arr._data = np.asarray(value)
    arr.grad = None
    arr._grad_req = "null"
    arr._entry = None
    return arr


def _custom_fn(op_type, kwargs, in_shapes, in_dtypes):
    """Build the jax-facing function for one (op_type, shapes) instance."""
    prop = get_registered(op_type)(**kwargs)
    out_shapes = prop.infer_shape([list(s) for s in in_shapes])[1]
    _, out_types, _ = prop.infer_type(list(in_dtypes))
    op = prop.create_operator(None, in_shapes, in_dtypes)
    n_out = len(prop.list_outputs())
    out_specs = tuple(jax.ShapeDtypeStruct(tuple(s), jnp.dtype(t))
                      for s, t in zip(out_shapes, out_types))
    in_specs = tuple(jax.ShapeDtypeStruct(tuple(s), jnp.dtype(t))
                     for s, t in zip(in_shapes, in_dtypes))

    def host_forward(*in_datas):
        ins = [_host_nd(d) for d in in_datas]
        outs = [_host_nd(np.zeros(tuple(s), jnp.dtype(t)))
                for s, t in zip(out_shapes, out_types)]
        op.forward(True, ["write"] * n_out, ins, outs, [])
        return tuple(np.asarray(o._data, jnp.dtype(t))
                     for o, t in zip(outs, out_types))

    def host_backward(*datas):
        n_in = len(in_shapes)
        ograds = [_host_nd(d) for d in datas[:n_out]]
        ins = [_host_nd(d) for d in datas[n_out:n_out + n_in]]
        outs = [_host_nd(d) for d in datas[n_out + n_in:]]
        igrads = [_host_nd(np.zeros(tuple(s), jnp.dtype(t)))
                  for s, t in zip(in_shapes, in_dtypes)]
        op.backward(["write"] * n_in, ograds, ins, outs, igrads, [])
        return tuple(np.asarray(g._data, jnp.dtype(t))
                     for g, t in zip(igrads, in_dtypes))

    @jax.custom_vjp
    def f(*in_datas):
        return jax.pure_callback(host_forward, out_specs, *in_datas,
                                 vmap_method="sequential")

    def f_fwd(*in_datas):
        outs = jax.pure_callback(host_forward, out_specs, *in_datas,
                                 vmap_method="sequential")
        return outs, (in_datas, outs)

    def f_bwd(res, cotangents):
        in_datas, outs = res
        return jax.pure_callback(host_backward, in_specs, *cotangents,
                                 *in_datas, *outs,
                                 vmap_method="sequential")

    f.defvjp(f_fwd, f_bwd)
    return f, n_out


@functools.lru_cache(maxsize=None)
def _cached_custom_fn(op_type, kwargs_items, shapes, dtypes):
    return _custom_fn(op_type, dict(kwargs_items), shapes, dtypes)


def invoke_custom(inputs, op_type, **kwargs):
    """nd.Custom implementation: run the registered custom op on NDArray
    inputs, recording on the autograd tape."""
    from . import autograd

    nds = [i if isinstance(i, NDArray) else NDArray(i) for i in inputs]
    shapes = tuple(tuple(a.shape) for a in nds)
    dtypes = tuple(str(a._data.dtype) for a in nds)
    f, n_out = _cached_custom_fn(
        op_type, tuple(sorted(kwargs.items())), shapes, dtypes)

    raws = f(*[a._data for a in nds])
    outs = [NDArray(r) for r in raws]
    if autograd.is_recording():
        autograd._record_closure(f"custom_{op_type}", f, nds, outs)
    return outs if n_out > 1 else outs[0]
