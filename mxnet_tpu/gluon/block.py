"""Block / HybridBlock — the Gluon layer API (ref: python/mxnet/gluon/block.py).

Eager mode runs hybrid_forward op-by-op on the PJRT stream (the reference's
imperative engine path). ``hybridize()`` swaps in a CachedOp: the whole
subtree is traced once into a single jax.jit computation with parameters as
traced arguments — the TPU-native equivalent of the reference's
_build_cache -> ndarray.CachedOp(static_alloc) (block.py:748-785), with XLA
buffer assignment replacing the static memory plan. BatchNorm-style aux-state
updates are collected during the trace and returned as extra outputs
(functional state threading instead of in-place mutation).
"""
from __future__ import annotations

import itertools
import math
import re
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout

from .. import autograd
from .. import ndarray as nd_mod
from .. import random as _random
from .. import telemetry as _telemetry
from .. import tracing as _tracing
from ..base import MXNetError
from ..ndarray import NDArray
from .parameter import (DeferredInitializationError, Parameter, ParameterDict)

_naming = threading.local()


class _BlockScope:
    """Hierarchical name scope (ref: block.py _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                if not hasattr(_naming, "counter"):
                    _naming.counter = {}
                count = _naming.counter.get(hint, 0)
                _naming.counter[hint] = count + 1
                prefix = f"{hint}{count}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, shared=params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, shared=parent._shared)
        else:
            params = ParameterDict(params.prefix, shared=params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *exc):
        if self._block._empty_prefix:
            return False
        _BlockScope._current.value = self._old_scope
        return False


# thread-local collector for functional aux-state updates during jit tracing
_aux_updates = threading.local()


def defer_aux_update(param, new_value):
    """BatchNorm-style running-stat update: collected when tracing (returned
    as jit outputs and written back after execution), applied directly in
    eager mode."""
    stack = getattr(_aux_updates, "stack", None)
    if stack:
        stack[-1].append((param, new_value))
    else:
        if not isinstance(new_value, NDArray):
            # symbolic trace: aux updates are materialized by the
            # executor's BatchNorm training hook, not recorded here
            return
        if param._data is None:
            param.set_data(new_value)
        else:
            param._data._data = new_value._data


def _flatten(args):
    """Flatten nested (lists/tuples of) NDArrays; returns flat list + spec."""
    if isinstance(args, NDArray):
        return [args], "0"
    if isinstance(args, (list, tuple)):
        flat, specs = [], []
        for a in args:
            f, s = _flatten(a)
            flat.extend(f)
            specs.append(s)
        return flat, ("t", type(args).__name__, specs)
    return [args], "raw"


def _regroup(flat, spec):
    if spec == "0":
        return flat.pop(0)
    if spec == "raw":
        return flat.pop(0)
    _, tname, specs = spec
    out = [_regroup(flat, s) for s in specs]
    return tuple(out) if tname == "tuple" else out


class Block:
    """Base building block (ref: gluon/block.py:127)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self):
        return self._params

    def name_scope(self):
        return self._scope

    def __repr__(self):
        s = f"{self.__class__.__name__}(\n"
        for key, child in self._children.items():
            s += f"  ({key}): {child!r}\n"
        return s + ")"

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self._params)
        else:
            pattern = re.compile(select)
            ret.update({k: v for k, v in self._params.items()
                        if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + k: v for k, v in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        payload = {k: v.data() for k, v in params.items()
                   if v._data is not None}
        nd_mod.save(filename, payload)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        loaded = nd_mod.load(filename)
        params = self._collect_params_with_prefix()
        if not any("." in k for k in loaded) and any("." in k for k in params):
            # file saved with flat prefixed names; match by parameter name
            by_name = {p.name: p for p in params.values()}
            for k, v in loaded.items():
                if k in by_name:
                    by_name[k]._load_init(v, ctx)
                elif not ignore_extra:
                    raise MXNetError(f"unknown parameter {k} in {filename}")
            if not allow_missing:
                missing = set(by_name) - set(loaded)
                if missing:
                    raise MXNetError(
                        f"parameters {sorted(missing)} missing in {filename}")
            return
        for k, p in params.items():
            if k in loaded:
                p._load_init(loaded[k], ctx)
            elif not allow_missing:
                raise MXNetError(f"parameter {k} missing in {filename}")
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(f"extra parameters in {filename}: {extra}")

    save_params = save_parameters
    load_params = load_parameters

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._reg_params.values():
            p.cast(dtype)

    def hybridize(self, active=True, **kwargs):
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    def zero_grad(self):
        self.collect_params().zero_grad()

    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        out = self(*inputs)
        nparams = sum(
            int(p.data().size) for p in self.collect_params().values()
            if p._data is not None)
        print(f"{self.__class__.__name__}: {nparams} parameters, "
              f"output {[o.shape for o in (out if isinstance(out, (list, tuple)) else [out])]}")
        return out


class HybridBlock(Block):
    """Block that can be traced into a single compiled computation
    (ref: gluon/block.py:671)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        # what a capture calls this block's programs (_named)
        self._program_alias = prefix.rstrip("_") if prefix else self._alias()
        self._active = False
        self._cached_jit = {}
        self._cached_plist = None
        self._flags = {}

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  inline_limit=2, forward_bulk_size=None,
                  backward_bulk_size=None):
        self._active = active
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape}
        self._cached_jit = {}
        self._cached_plist = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape)

    def infer_shape(self, *args):
        """Resolve deferred parameter shapes from input shapes. Built-in
        layers override; custom blocks with fully-specified shapes never
        need it."""
        raise MXNetError(
            f"{self.__class__.__name__} has deferred-shape parameters but "
            "does not implement infer_shape; give explicit in_units/"
            "in_channels or implement infer_shape")

    def _collect_param_values(self, *args):
        override = getattr(_param_override, "map", None)
        try:
            return {n: (override[id(p)] if override and id(p) in override
                        else p.data())
                    for n, p in self._reg_params.items()}
        except DeferredInitializationError:
            self.infer_shape(*args)
            for p in self._reg_params.values():
                if p._deferred_init is not None:
                    p._finish_deferred_init()
            return {n: p.data() for n, p in self._reg_params.items()}

    def forward(self, x, *args):
        from ..symbol.symbol import Symbol
        if isinstance(x, Symbol):
            # symbolic trace (gluon export / SymbolBlock composition):
            # parameters become graph variables by their full names
            from .. import symbol as sym_mod
            for p in self._reg_params.values():
                if p.shape is None or any(s == 0 for s in p.shape):
                    raise MXNetError(
                        f"{self.name}: cannot trace symbolically while "
                        f"parameter {p.name} has unresolved shape "
                        f"{p.shape}; run the block once on data first")
            params = {n: p.var() for n, p in self._reg_params.items()}
            return self.hybrid_forward(sym_mod, x, *args, **params)
        if self._active and not getattr(_in_trace, "value", False):
            # one span per outermost hybridized call (children are
            # reached only while tracing, through hybrid_forward):
            # parameter walk, signature, cache lookup, dispatch, tape
            # record, aux write-back
            with _tracing.span("block.call"):
                return self._call_cached_op(x, *args)
        params = self._collect_param_values(x, *args)
        return self.hybrid_forward(nd_mod, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp path -----------------------------------------------------
    def _ensure_initialized(self, *args):
        try:
            for p in self.collect_params().values():
                if p._data is None:
                    p.data()  # raises with a helpful message
            return True
        except DeferredInitializationError:
            return False

    def _call_cached_op(self, *args):
        if self._cached_plist is None:
            if not self._ensure_initialized(*args):
                # first call resolves deferred shapes imperatively (the
                # reference's deferred-init first pass); later calls compile
                prev = _in_trace_flag()
                _set_in_trace(True)
                try:
                    return self.forward(*args)
                finally:
                    _set_in_trace(prev)
            # parameter tree is static once shapes are resolved — walk once
            self._cached_plist = sorted(self.collect_params().items())
        plist = self._cached_plist
        pvals = [p.data()._data for _, p in plist]
        flat_in, in_spec = _flatten(list(args))
        in_datas = [a._data for a in flat_in]
        training = autograd.is_training()
        sig = (tuple((tuple(d.shape), str(d.dtype)) for d in in_datas),
               tuple((tuple(v.shape), str(v.dtype)) for v in pvals),
               training, in_spec if isinstance(in_spec, str) else str(in_spec))

        entry = self._cached_jit.get(sig)
        if entry is None:
            entry = (*self._build_cached(plist, in_spec, training), {})
            self._cached_jit[sig] = entry
        jfn, out_spec_box, aux_params_box, recorded = entry

        key = _random.next_key()
        pvals = tuple(pvals)
        pullback = None
        if autograd.is_recording():
            nds = [p.data() for _, p in plist] + flat_in
            diff = tuple(autograd._differentiated(a) for a in nds)
        if autograd.is_recording() and any(diff):
            # one program gives the outputs and writes the residuals; the
            # pullback it returns waits on the tape for backward
            if diff not in recorded:
                recorded[diff] = self._build_recorded(
                    jfn, diff, training, (pvals, key, *in_datas))
            flat_out_data, aux_data, pullback = recorded[diff][0](
                pvals, key, in_datas)
        else:
            flat_out_data, aux_data = jfn(pvals, key, *in_datas)
        outs = [NDArray(d) for d in flat_out_data]

        if autograd.is_recording():
            # the plain program stays on the node for what the pullback
            # cannot serve (autograd._compute_gradients); never called,
            # it is never compiled
            n_params = len(pvals)
            node = autograd._record_closure(
                f"cachedop_{self.name}",
                lambda *datas: jfn(tuple(datas[:n_params]), key,
                                   *datas[n_params:])[0],
                nds, outs)
            if pullback is not None:
                node.pullback = (flat_out_data, diff, pullback)

        # write back functional aux updates (running stats)
        for p, d in zip(aux_params_box[0], aux_data):
            p._data._data = d

        flat = list(outs)
        return _regroup(flat, out_spec_box[0])

    def optimize_for(self, x, *args, backend="XLA"):
        """Partition the inference graph with a registered subgraph
        backend and keep using it from the hybridized call path (ref:
        gluon/block.py optimize_for; parity with CachedOp running the
        same graph passes as bind, src/imperative/cached_op.cc:685).

        With the default ``backend="XLA"`` conv+BN(+add)+relu chains
        collapse into ``_sg_xla_conv`` with the BN affine folded into
        the convolution weights (subgraph/xla_fuse.py). Training-mode
        calls (autograd.is_training()) bypass the partitioned graph —
        folding moving stats would silently freeze BN statistics."""
        out = self(x, *args)  # resolves deferred shapes imperatively
        self._optimized_backend = backend
        self._cached_jit = {}
        self._cached_plist = None
        self._active = True
        return out

    @staticmethod
    def _spec_nleaves(spec):
        if spec in ("0", "raw"):
            return 1
        return sum(HybridBlock._spec_nleaves(s) for s in spec[2])

    def _build_cached_partitioned(self, plist, in_spec, backend):
        """Symbolically trace, run the subgraph partitioner, and lower
        the optimized graph to a jitted fn with the same signature as
        `_build_cached`'s direct trace."""
        from ..symbol import Group
        from ..symbol import var as sym_var

        n_in = self._spec_nleaves(in_spec)
        placeholders = [sym_var(f"__cached_in{i}") for i in range(n_in)]
        flat = list(placeholders)
        args = _regroup(flat, in_spec)
        if not isinstance(args, list):
            args = [args]
        prev = _in_trace_flag()
        _set_in_trace(True)
        try:
            out = self.forward(*args)
        finally:
            _set_in_trace(prev)
        flat_out, out_spec = _flatten(out)
        sym = Group(list(flat_out)) if len(flat_out) > 1 else flat_out[0]
        opt = sym.get_backend_symbol(backend)
        needed = set(opt.list_inputs())

        def pure_fn(param_vals, key, *in_datas):
            bindings = {}
            for (n, _p), v in zip(plist, param_vals):
                if n in needed:
                    bindings[n] = NDArray(v)
            for i, d in enumerate(in_datas):
                bindings[f"__cached_in{i}"] = NDArray(d)
            prev_trace = _in_trace_flag()
            _set_in_trace(True)
            try:
                with _random.key_context(key):
                    res = opt.eval_dict(bindings)
            finally:
                _set_in_trace(prev_trace)
            res_list = res if isinstance(res, list) else [res]
            return [r._data for r in res_list], []

        return jax.jit(self._named(pure_fn, False)), [out_spec], [[]]

    def _named(self, pure_fn, training, part=""):
        """Name the traced program after the block and the mode, so a
        capture's ``XLA Modules`` line reads ``jit_mx_<block>_train`` /
        ``_eval`` and not ``jit_pure_fn``; a recorded call's two programs
        add ``part``: ``_fwd`` and ``_bwd``. ``<block>`` is the prefix the
        user gave this block, else its class: never gluon's numbered
        name, which counts the blocks built before it in the process.
        The persistent compilation cache holds the module's name in its
        key, so a name that moved with the count would compile cold."""
        pure_fn.__name__ = pure_fn.__qualname__ = "mx_%s_%s%s" % (
            re.sub(r"\W", "_", self._program_alias),
            "train" if training else "eval", part)
        return pure_fn

    def _build_recorded(self, jfn, diff, training, args):
        """``(call, (fwd, bwd), (forward, backward))`` for a recorded call
        that differentiates the arguments ``diff`` marks (parameters, then
        inputs), built for arguments like ``args`` (``(param_vals, key,
        *in_datas)``: arrays or shapes). ``fwd`` and ``bwd`` are its two
        programs: the forward that also writes the residuals, and the
        pullback over them; ``forward`` and ``backward`` the same compiled
        for ``args``. ``call(param_vals, key, in_datas)`` runs ``fwd`` and
        gives the outputs, the aux updates and ``pullback(cts)``, which
        runs ``bwd`` on the cotangents of the inexact outputs.

        ``fwd`` returns only those leaves of ``jax.vjp``'s pullback that
        it computed. A leaf that is one of its arguments or outputs (a
        weight kept for the input's gradient, an output its own
        derivative needs) would be copied to be returned a second time:
        the trace notes where each leaf comes from, and ``call`` puts
        the tree together from the arrays it already holds.

        A program returns its results in the default layout for their
        shapes, so a residual made in another one (a convolution's
        activation, made with its channels minor) is copied into it. The
        copies that feed the compiled forward's result say which, and in
        what layout each was made. Nothing but the pullback reads a
        residual, so such a one may cross transposed instead: as the array
        whose default layout holds the bytes as they were made, which the
        transpose writes without a copy; the pullback transposes it back.
        The pair with the transposed residuals is kept unless its forward,
        compiled for ``args``, plans more bytes (outputs and temporaries)
        than the plain one; only the kept pair's backward is compiled
        (comparing the two backwards too would cost every process one more
        compile), and the call compiles nothing more. A non-default layout
        itself never crosses: jax reads an uncommitted array in one as if
        it were in the default layout, and labels the outputs of a program
        read back from the persistent compilation cache in the default
        one."""
        pure_fn = jfn.__wrapped__
        vjp_tree = sources = None

        def forward(param_vals, key, *in_datas):
            nonlocal vjp_tree, sources
            args = (*param_vals, *in_datas)

            def g(*diff_args):
                it = iter(diff_args)
                full = [next(it) if d else a for d, a in zip(diff, args)]
                outs, aux = pure_fn(tuple(full[:len(param_vals)]), key,
                                    *full[len(param_vals):])
                return (tuple(o for o in outs if autograd._inexact(o)),
                        (outs, aux))

            _, vjp_fn, (outs, aux) = jax.vjp(
                g, *[a for d, a in zip(diff, args) if d], has_aux=True)
            leaves, vjp_tree = jax.tree_util.tree_flatten(vjp_fn)
            passed = {id(v): i for i, v in enumerate((*args, *outs))}
            sources = [passed.get(id(leaf)) for leaf in leaves]
            return outs, aux, [leaf for leaf, i in zip(leaves, sources)
                               if i is None]

        def backward(vjp_fn, cts):
            return vjp_fn(cts)

        plain = jax.jit(self._named(forward, training, "_fwd"))
        plain_back = jax.jit(self._named(backward, training, "_bwd"))
        outs, aux, computed = jax.eval_shape(plain, *args)
        shape = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        passed = (*args[0], *args[2:], *map(shape, outs))
        cts = tuple(shape(o) for o in outs if autograd._inexact(o))

        def transposed_pair(orders):
            def transposed(param_vals, key, *in_datas):
                outs, aux, computed = plain(param_vals, key, *in_datas)
                return outs, aux, [c if o is None else jnp.transpose(c, o)
                                   for c, o in zip(computed, orders)]

            def transposed_back(vjp_fn, cts):
                leaves, it = jax.tree_util.tree_leaves(vjp_fn), iter(orders)
                for k, i in enumerate(sources):
                    o = next(it) if i is None else None
                    if o is not None:
                        leaves[k] = jnp.transpose(leaves[k], _inverse(o))
                return plain_back(vjp_tree.unflatten(leaves), cts)

            return (jax.jit(self._named(transposed, training, "_fwd")),
                    jax.jit(self._named(transposed_back, training, "_bwd")))

        def compiled(fwd):
            """``fwd`` compiled for ``args``, and the bytes it plans:
            outputs and temporaries."""
            c = fwd.lower(*args).compile()
            m = c.memory_analysis()
            return c, m.output_size_in_bytes + m.temp_size_in_bytes

        devices = jax.tree_util.tree_leaves(args)[0].sharding.device_set
        device = next(iter(devices))
        fwd, bwd = plain, plain_back
        forward_c, planned = compiled(plain)
        orders = [None] * len(computed)
        if len(devices) == 1:           # a sharded program's text is a shard's
            orders = _axis_orders(forward_c.as_text(),
                                  len(outs) + len(aux), computed, device)
        if any(orders):
            moved_fwd, moved_bwd = transposed_pair(orders)
            moved_c, moved_planned = compiled(moved_fwd)
            if moved_planned <= planned:
                fwd, bwd, forward_c = moved_fwd, moved_bwd, moved_c
            else:
                orders = [None] * len(computed)
        rs = iter(map(shape, jax.eval_shape(fwd, *args)[2]))
        pullback = vjp_tree.unflatten(
            next(rs) if i is None else passed[i] for i in sources)
        # ``autograd.backward`` calls the pullback inside jax's
        # transposition, which traces under the empty abstract mesh: traced
        # in that context here, it is found there compiled
        with jax.sharding.use_abstract_mesh(_NO_MESH):
            pair = forward_c, bwd.lower(pullback, cts).compile()
        moved = [c for c, o in zip(computed, orders) if o is not None]
        _set_relaid(plain.__name__, len(moved), sum(
            math.prod(c.shape) * c.dtype.itemsize for c in moved))

        def call(param_vals, key, in_datas):
            outs, aux, computed = fwd(param_vals, key, *in_datas)
            passed, computed = (*param_vals, *in_datas, *outs), iter(computed)
            vjp_fn = vjp_tree.unflatten(
                next(computed) if i is None else passed[i] for i in sources)
            return outs, aux, lambda cts: bwd(vjp_fn, cts)

        return call, (fwd, bwd), pair

    def _build_cached(self, plist, in_spec, training):
        """Trace the whole subtree once into a jitted pure function."""
        backend = getattr(self, "_optimized_backend", None)
        if backend and not training:
            try:
                return self._build_cached_partitioned(
                    plist, in_spec, backend)
            except Exception as e:  # noqa: BLE001 — un-traceable blocks
                import warnings
                warnings.warn(
                    f"optimize_for({backend!r}): symbolic partition "
                    f"failed ({e!r}); falling back to the direct trace")
        out_spec_box = [None]
        aux_params_box = [[]]
        params = [p for _, p in plist]

        def pure_fn(param_vals, key, *in_datas):
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(training)
            prev_trace = _in_trace_flag()
            _set_in_trace(True)
            override = {id(p): NDArray(v) for p, v in zip(params, param_vals)}
            old_map = getattr(_param_override, "map", None)
            _param_override.map = override
            if not hasattr(_aux_updates, "stack"):
                _aux_updates.stack = []
            _aux_updates.stack.append([])
            try:
                with _random.key_context(key):
                    flat_in = [NDArray(d) for d in in_datas]
                    args = _regroup(list(flat_in), in_spec)
                    if not isinstance(args, list):
                        args = [args]
                    out = self.forward(*args)
                aux = _aux_updates.stack[-1]
            finally:
                _aux_updates.stack.pop()
                _param_override.map = old_map
                _set_in_trace(prev_trace)
                autograd.set_training(prev_train)
                autograd.set_recording(prev_rec)
            flat_out, out_spec = _flatten(out)
            out_spec_box[0] = out_spec
            aux_params_box[0] = [p for p, _ in aux]
            return ([o._data for o in flat_out],
                    [v._data for _, v in aux])

        return (jax.jit(self._named(pure_fn, training)), out_spec_box,
                aux_params_box)

    def export(self, path, epoch=0):
        """Export to symbol JSON + params (ref: block.py export).

        Requires the network to have run at least once so shapes are known.
        Traces hybrid_forward with Symbol placeholders.
        """
        from .. import symbol as sym_mod
        from ..symbol.trace import trace_block
        out, params = trace_block(self)
        out.save(f"{path}-symbol.json")
        aux_names = set(out.list_auxiliary_states())
        payload = {}
        for name, p in params.items():
            prefix = "aux" if name in aux_names else "arg"
            payload[f"{prefix}:{name}"] = p.data()
        nd_mod.save(f"{path}-{epoch:04d}.params", payload)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"


_in_trace = threading.local()
_param_override = threading.local()


_NO_MESH = jax.sharding.AbstractMesh((), ())

_relaid_metrics = _telemetry.metrics.lazy_metrics(lambda reg: (
    reg.gauge("mx_residuals_relaid",
              "residuals a recorded call's forward returns transposed, "
              "made in another layout than the default for their shape",
              labelnames=("program",)),
    reg.gauge("mx_residual_bytes_relaid",
              "bytes of those residuals", labelnames=("program",))))

_HLO_LAYOUT = re.compile(r"\{([\d,]*)(?::T((?:\(\d+(?:,\d+)*\))+))?")


def _axis_orders(hlo, first, residuals, device):
    """For each of the ``residuals`` (shapes), the results from ``first``
    on of the program compiled for ``device`` (``hlo``, its text): ``None``
    where the program returns it as it made it, else the order of axes in
    which it crosses without a copy (``jnp.transpose(r, order)``'s default
    layout holds the bytes as they were made), or ``None`` where no order
    does."""
    orders = [None] * len(residuals)
    lines = hlo[hlo.index("\nENTRY "):].splitlines()[1:]
    made, root = {}, ""
    for line in lines[:next(i for i, l in enumerate(lines)
                            if l.startswith("}"))]:
        name, _, rest = line.strip().partition(" = ")
        if name.startswith("ROOT "):
            root = rest
        made[name.split()[-1]] = rest
    if " tuple(" not in root:
        return orders
    results = re.sub(r"/\*.*?\*/", "", root.split(" tuple(", 1)[1]).split(
        ")")[0].split(", ")
    for k, (r, name) in enumerate(zip(residuals, results[first:])):
        op = made.get(name, "").partition(" ")[2]
        if not op.startswith("copy(") or jax.dtypes.issubdtype(
                r.dtype, jax.dtypes.extended):
            continue
        source = made.get(op[5:].split(")")[0].split(",")[0], "")
        layout = _HLO_LAYOUT.search(source.partition(" ")[0])
        if layout is None or not layout.group(1):
            continue
        order = tuple(int(a) for a in layout.group(1).split(","))[::-1]
        tiles = tuple(tuple(int(n) for n in t.split(","))
                      for t in re.findall(r"\(([\d,]+)\)",
                                          layout.group(2) or ""))
        if len(order) != len(r.shape):
            continue
        for q in (order, *itertools.permutations(range(len(order)))):
            if q == tuple(range(len(q))):
                continue
            default = Layout.from_pjrt_layout(device.client.get_default_layout(
                r.dtype, tuple(r.shape[a] for a in q), device))
            if (tuple(q[a] for a in default.major_to_minor) == order
                    and tuple(default.tiling) == tiles):
                orders[k] = q
                break
    return orders


def _inverse(order):
    return tuple(sorted(range(len(order)), key=order.__getitem__))


def _set_relaid(program, count, nbytes):
    """The forward ``program``'s gauges, once a compiled pair: how many of
    its residuals cross transposed, and their bytes."""
    if _telemetry.enabled():
        gauges = _relaid_metrics()
        gauges[0].labels(program=program).set(count)
        gauges[1].labels(program=program).set(nbytes)


def _in_trace_flag():
    return getattr(_in_trace, "value", False)


def _set_in_trace(v):
    _in_trace.value = v


def infer_shapes(block, *input_shapes, dtype=None):
    """Resolve a block's deferred parameter shapes with ONE abstract
    forward pass — no op is compiled or executed on the device.

    ``jax.eval_shape`` runs the eager path on shape tracers, so each
    layer's shape inference fires and deferred initializers materialize
    real (concrete — see ndarray._materialize) parameter arrays. This is
    the shared warm-up used by __graft_entry__.entry() and
    contrib.quantization.quantize_net; the reference's analogue is the
    deferred-init first pass of HybridBlock (gluon/block.py:860
    infer_shape)."""
    import jax.numpy as jnp
    dtype = dtype or jnp.float32

    def _warm(*datas):
        prev = _in_trace_flag()
        _set_in_trace(True)
        try:
            out = block.forward(*[NDArray(d) for d in datas])
            flat, _spec = _flatten(out)
            return [o._data for o in flat]
        finally:
            _set_in_trace(prev)

    jax.eval_shape(_warm, *[jax.ShapeDtypeStruct(tuple(s), dtype)
                            for s in input_shapes])


class SymbolBlock(HybridBlock):
    """Construct a block from a Symbol (ref: gluon/block.py:952)."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        self._outputs = outputs
        self._inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        from ..symbol.symbol import Symbol
        self._out_sym = outputs if isinstance(outputs, Symbol) else outputs[0]
        input_names = {s.name for s in self._inputs}
        for name in self._out_sym.list_inputs():
            if name not in input_names:
                self._reg_params[name] = self.params.get(
                    name, allow_deferred_init=True)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as sym_mod
        out = sym_mod.load(symbol_file)
        inputs = [sym_mod.var(n) for n in (
            input_names if isinstance(input_names, (list, tuple))
            else [input_names])]
        blk = SymbolBlock(out, inputs)
        if param_file:
            loaded = nd_mod.load(param_file)
            cleaned = {}
            for k, v in loaded.items():
                k = k.split(":", 1)[-1]
                cleaned[k] = v
            for name, p in blk._reg_params.items():
                if name in cleaned:
                    p.set_data(cleaned[name])
            if ctx:
                blk.collect_params().reset_ctx(ctx)
        return blk

    def forward(self, *args):
        bindings = {s.name: a for s, a in zip(self._inputs, args)}
        for name, p in self._reg_params.items():
            if p._data is not None:
                bindings[name] = p.data()
        return self._out_sym.eval_dict(bindings)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError
