"""Parameter / ParameterDict (ref: python/mxnet/gluon/parameter.py).

A Parameter owns one NDArray (single logical copy — replication across a
device mesh is a sharding annotation in this framework, not per-context
copies) plus its gradient, init policy, and deferred-shape state.
"""
from __future__ import annotations

import re
from collections import OrderedDict

import numpy as np

from .. import initializer as init_mod
from ..base import MXNetError
from ..context import Context, current_context
from ..ndarray import NDArray, array, zeros


class DeferredInitializationError(MXNetError):
    """Raised when a parameter's data is requested before shape is known."""


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self.stype = stype
        self.grad_stype = grad_stype
        self._data = None
        self._grad = None
        self._deferred_init = None
        self._ctx = None
        self._placed = False  # initialize() was given one Context
        # sharding annotation for pjit'd steps (jax.sharding.PartitionSpec
        # or None = replicated); consumed by parallel.data_parallel
        self.partition_spec = None

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        self._grad_req = req
        if self._data is not None:
            self._data._grad_req = req
            if req == "null":
                self._grad = None
                self._data.grad = None
            elif self._grad is None:
                self._init_grad()

    def _shape_incomplete(self):
        return self.shape is None or any(d == 0 for d in self.shape)

    # -- initialization ----------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        default_init = default_init or init_mod.Uniform()
        if self._data is not None and not force_reinit:
            return
        self._ctx = ctx or current_context()
        self._placed = isinstance(ctx, Context)
        if self._shape_incomplete():
            if self.allow_deferred_init:
                self._deferred_init = (init, self._ctx, default_init)
                return
            raise MXNetError(
                f"cannot initialize {self.name}: shape {self.shape} incomplete"
                " and deferred init not allowed")
        self._finish_init(init, default_init)

    def _finish_init(self, init, default_init):
        from ..profiling import memory as _mem
        data = zeros(self.shape, dtype=self.dtype)
        initializer = init_mod.create(init or self.init or default_init)
        desc = init_mod.InitDesc(self.name)
        initializer(desc, data)
        if self._placed:
            # an explicit single context places the data there (and so
            # the grad, the optimizer state and every computation that
            # follows it); without one the array stays uncommitted on
            # the default device. Initializers rebind the array, so the
            # move comes after them
            data = data.as_in_context(self._ctx)
        self._data = _mem.tag_role(data, "parameter")
        self._deferred_init = None
        if self._grad_req != "null":
            self._init_grad()

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if self._shape_incomplete():
            raise DeferredInitializationError(
                f"parameter {self.name} shape still unknown")
        init, ctx, default_init = self._deferred_init
        self._finish_init(init, default_init)

    def _init_grad(self):
        self._data.attach_grad(self._grad_req)
        self._grad = self._data.grad

    def shape_inferred(self, shape):
        """Called by layers once the input-dependent dims are known."""
        shape = tuple(shape)
        if self.shape is not None:
            merged = tuple(
                n if o == 0 else o for o, n in zip(self.shape, shape))
            if len(merged) != len(shape) or any(
                    o != 0 and o != n for o, n in zip(self.shape, shape)):
                if merged != shape:
                    raise MXNetError(
                        f"{self.name}: inferred shape {shape} incompatible "
                        f"with declared {self.shape}")
            self.shape = merged
        else:
            self.shape = shape
        if self._deferred_init is not None:
            self._finish_deferred_init()

    # -- access ------------------------------------------------------------
    def data(self, ctx=None):
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    f"parameter {self.name} not fully initialized yet "
                    "(deferred shape)")
            raise MXNetError(
                f"parameter {self.name} has not been initialized; call "
                ".initialize() first")
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None):
        if self._grad is None:
            raise MXNetError(
                f"parameter {self.name} has no gradient (grad_req="
                f"{self._grad_req!r} or not initialized)")
        return self._data.grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        return [self._ctx or current_context()]

    def zero_grad(self):
        if self._data is not None and self._data.grad is not None:
            self._data.grad._data = self._data.grad._data * 0

    def set_data(self, data):
        from ..profiling import memory as _mem
        data = data if isinstance(data, NDArray) else array(data)
        if self.shape is not None and not self._shape_incomplete() and \
                tuple(data.shape) != tuple(self.shape):
            raise MXNetError(
                f"set_data: shape {data.shape} != parameter shape {self.shape}")
        self.shape = tuple(data.shape)
        if self._data is None:
            self._data = data.copy() if isinstance(data, NDArray) else data
            if self._grad_req != "null":
                self._init_grad()
            self._deferred_init = None
        else:
            grad = self._data.grad
            req = self._data._grad_req
            self._data._data = data._data
            self._data.grad = grad
            self._data._grad_req = req
        _mem.tag_role(self._data, "parameter")

    def _load_init(self, data, ctx=None):
        self.set_data(data)

    def cast(self, dtype):
        self.dtype = dtype
        if self._data is not None:
            self._data._data = self._data._data.astype(np.dtype(dtype))

    def var(self):
        from ..symbol import var
        return var(self.name, shape=self.shape, dtype=self.dtype)

    def reset_ctx(self, ctx):
        self._ctx = ctx

    def row_sparse_data(self, row_id):
        from ..ndarray import sparse
        return sparse.row_sparse_array(self.data()).retain(row_id)


class Constant(Parameter):
    """Non-learnable parameter (ref: gluon/parameter.py Constant)."""

    def __init__(self, name, value):
        value = value if isinstance(value, NDArray) else array(value)
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype,
                         init=init_mod.Load({name: value}))


class ParameterDict:
    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __repr__(self):
        items = "\n".join(f"  {v}" for v in self._params.values())
        return f"ParameterDict(prefix={self._prefix!r}\n{items}\n)"

    def __getitem__(self, key):
        return self._params[key]

    def __contains__(self, key):
        return key in self._params

    def __iter__(self):
        return iter(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def get(self, name, **kwargs):
        """Find prefix+name, creating (or sharing) it if absent."""
        name = self._prefix + name
        if name in self._params:
            param = self._params[name]
            # sharing an existing parameter: declared attrs must agree
            want_shape = kwargs.get("shape")
            if want_shape is not None and param.shape is not None:
                if len(want_shape) != len(param.shape) or any(
                        w != 0 and p != 0 and w != p
                        for w, p in zip(want_shape, param.shape)):
                    raise MXNetError(
                        f"cannot share parameter {name}: requested shape "
                        f"{tuple(want_shape)} != existing {param.shape}")
            want_dtype = kwargs.get("dtype")
            if want_dtype is not None and str(want_dtype) != str(param.dtype):
                raise MXNetError(
                    f"cannot share parameter {name}: requested dtype "
                    f"{want_dtype} != existing {param.dtype}")
            return param
        if self._shared is not None and name in self._shared:
            self._params[name] = self._shared[name]
            return self._params[name]
        param = Parameter(name, **kwargs)
        self._params[name] = param
        return param

    def get_constant(self, name, value=None):
        name = self._prefix + name
        if name not in self._params:
            self._params[name] = Constant(name, value)
        return self._params[name]

    def update(self, other):
        for k, v in other.items():
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        for p in self._params.values():
            p.initialize(init=None, ctx=ctx,
                         default_init=init or init_mod.Uniform(),
                         force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def save(self, fname, strip_prefix=""):
        from ..ndarray import save as nd_save
        payload = {}
        for name, p in self._params.items():
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            payload[name] = p.data()
        nd_save(fname, payload)

    def load(self, fname, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        from ..ndarray import load as nd_load
        loaded = nd_load(fname)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self._params.items():
            if name in loaded:
                p._load_init(loaded[name], ctx)
            elif not allow_missing:
                raise MXNetError(f"parameter {name} missing in file {fname}")
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise MXNetError(f"file {fname} has extra parameters {extra}")
