"""Deployment-only inference API
(ref: include/mxnet/c_predict_api.h — 12 MXPred* functions the
reference's amalgamation builds for mobile/embedded; here the analogue
is a minimal class over a checkpoint that forwards with zero training
machinery and an optionally AOT-compiled executable).

    pred = mx.predictor.Predictor.from_checkpoint("model", 3,
                                                  {"data": (1, 3, 224, 224)})
    out = pred.forward(data=batch)          # numpy in, numpy out
"""
from __future__ import annotations

import threading

import numpy as np

from .base import MXNetError


def compile_symbol_forward(symbol, bindings, device=None, cast=None):
    """The one symbol→executable lowering both deployment layers use
    (Predictor._build and the serving VariantSet — a fix here reaches
    both): commit ``bindings`` (params/aux, NDArray or array-like) to
    ``device`` as a sorted tuple and return ``(jitted, param_vals)``
    where ``jitted(param_vals, inputs_dict)`` evaluates the symbol and
    returns a tuple of jax arrays.

    ``cast`` (e.g. ``"bfloat16"``) builds a reduced-precision variant:
    float params are cast offline, float inputs at the graph edge, and
    float outputs cast back to fp32 (replies stay fp32-typed).
    """
    import jax
    import jax.numpy as jnp

    from .ndarray.ndarray import NDArray

    names = sorted(bindings)
    cast_dt = jnp.dtype(cast) if cast is not None else None

    def _cast(a):
        if cast_dt is not None and jnp.issubdtype(a.dtype,
                                                  jnp.floating):
            return a.astype(cast_dt)
        return a

    vals = tuple(
        _cast(bindings[n]._data if isinstance(bindings[n], NDArray)
              else jnp.asarray(np.asarray(bindings[n])))
        for n in names)

    def fwd(param_vals, inputs):
        b = {n: NDArray(v) for n, v in zip(names, param_vals)}
        for k, v in inputs.items():
            b[k] = NDArray(_cast(jnp.asarray(v)))
        out = symbol.eval_dict(b)
        outs = out if isinstance(out, (list, tuple)) else [out]
        res = []
        for o in outs:
            a = o._data
            if cast_dt is not None and \
                    jnp.issubdtype(a.dtype, jnp.floating):
                a = a.astype(jnp.float32)
            res.append(a)
        return tuple(res)

    pvals = jax.device_put(vals, device) if device is not None \
        else jax.device_put(vals)
    return jax.jit(fwd), pvals


class Predictor:
    """MXPredCreate/SetInput/Forward/GetOutput rolled into one object."""

    def __init__(self, symbol, arg_params, aux_params, input_shapes,
                 dev_type=None, dev_id=0, device=None):
        import jax

        from .ndarray.ndarray import NDArray

        # MXPredCreate's dev_type/dev_id select the device; None = the
        # backend default. ``device`` takes a jax
        # device object directly — the serving gateway pins one
        # replica's executables per device this way (serving/gateway.py)
        self._device = device
        if device is None and dev_type is not None:
            try:
                matching = jax.devices(dev_type)
            except RuntimeError:
                matching = []
            if not matching or dev_id >= len(matching):
                raise MXNetError(
                    f"Predictor: no device {dev_type}:{dev_id}; available "
                    f"platforms: {sorted({d.platform for d in jax.devices()})}")
            self._device = matching[dev_id]
        self._symbol = symbol
        self._input_names = list(input_shapes)
        self._shapes = dict(input_shapes)
        known = set(symbol.list_inputs())
        missing = [n for n in self._input_names if n not in known]
        if missing:
            raise MXNetError(f"Predictor: inputs {missing} not in graph")
        self._bindings = {}
        for k, v in list(arg_params.items()) + list(aux_params.items()):
            self._bindings[k] = v if isinstance(v, NDArray) else NDArray(v)
        self._jitted = None
        # guards the lazy _build: the serving gateway's worker threads
        # race the first forward(); without this, two threads half-
        # initialize (_jitted set, _param_vals missing) and one crashes
        self._lock = threading.Lock()

    @classmethod
    def from_checkpoint(cls, prefix, epoch, input_shapes, **kwargs):
        """Load `prefix-symbol.json` + `prefix-{epoch}.params`
        (MXPredCreate's file contract, c_predict_api.h)."""
        from .model import load_checkpoint

        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return cls(symbol, arg_params, aux_params, input_shapes, **kwargs)

    def _build(self):
        jitted, pvals = compile_symbol_forward(
            self._symbol, self._bindings, self._device)
        # committed params pin the computation to the selected device.
        # _param_vals is published BEFORE _jitted: forward()'s unlocked
        # fast path reads _jitted first, so it must never observe a
        # jitted fn without the params it closes over
        self._param_vals = pvals
        self._jitted = jitted

    def forward(self, **inputs):
        """Run one forward; numpy (or NDArray) in, list of numpy out
        (MXPredSetInput + MXPredForward + MXPredGetOutput)."""
        import jax.numpy as jnp

        from .ndarray.ndarray import NDArray

        jitted = self._jitted
        if jitted is None:
            with self._lock:          # double-checked: concurrent first
                if self._jitted is None:   # calls build exactly once
                    self._build()
                jitted = self._jitted
        # local snapshots: a concurrent reshape() nulls _jitted under
        # the lock — this call then runs the pre-reshape executable
        # (jit retraces per input shape, so even a racing new shape
        # computes correctly) instead of crashing on a None read
        pvals = self._param_vals
        feed = {}
        for k, v in inputs.items():
            if k not in self._shapes:
                raise MXNetError(f"Predictor: unknown input {k!r}")
            # preserve the caller's dtype (int token indices etc.), as
            # MXPredSetInput does
            arr = v._data if isinstance(v, NDArray) \
                else jnp.asarray(np.asarray(v))
            if tuple(arr.shape) != tuple(self._shapes[k]):
                raise MXNetError(
                    f"Predictor: input {k} shape {tuple(arr.shape)} != "
                    f"declared {tuple(self._shapes[k])} (reshape with a "
                    "new Predictor, as MXPredReshape does)")
            feed[k] = arr
        outs = jitted(pvals, feed)
        return [np.asarray(o) for o in outs]

    def reshape(self, new_input_shapes):
        """New shapes -> new compiled executable (MXPredReshape)."""
        with self._lock:
            self._shapes.update(new_input_shapes)
            self._jitted = None
        return self

    def output_shapes(self, dtypes=None):
        """Output shapes for the declared input shapes, WITHOUT running
        or compiling a forward (MXPredGetOutputShape is legal right
        after MXPredCreate in the reference ABI) — jax.eval_shape
        traces abstractly. Inputs default to float32 (the C ABI is
        float-only by signature); Python callers with integer inputs
        (token ids) pass ``dtypes={"data": "int32"}``."""
        import jax
        import jax.numpy as jnp

        from .ndarray.ndarray import NDArray

        dtypes = dtypes or {}
        bindings = {
            k: jax.ShapeDtypeStruct(
                tuple(v), jnp.dtype(dtypes.get(k, jnp.float32)))
            for k, v in self._shapes.items()}

        def absfwd(inputs):
            b = dict(self._bindings)
            for k, v in inputs.items():
                b[k] = NDArray(v)
            out = self._symbol.eval_dict(b)
            outs = out if isinstance(out, (list, tuple)) else [out]
            return tuple(o._data for o in outs)

        shaped = jax.eval_shape(absfwd, bindings)
        return [tuple(s.shape) for s in shaped]


class _CPredictor:
    """Bridge object behind the MXPred* C ABI (_native/predict.cc):
    one instance per PredictorHandle; the C side calls these methods
    under the GIL. Mirrors c_predict_api.h semantics: declared input
    shapes, set_input copies, forward compiles-and-runs, outputs are
    fetched as flat fp32."""

    # reference dev_type codes (c_predict_api.h: 1 cpu, 2 gpu) — the
    # accelerator code maps to this framework's chip backend
    _DEV = {1: "cpu", 2: "tpu"}

    def __init__(self, symbol_json, param_bytes, dev_type, dev_id,
                 input_names, input_shapes, output_names=()):
        from . import symbol as sym_mod
        from .ndarray.utils import load_frombuffer
        from .symbol.symbol import is_aux_name

        sym = sym_mod.load_json(symbol_json)
        if output_names:
            internals = sym.get_internals()
            names = internals.list_outputs()
            outs = []
            for name in output_names:
                cand = name if name in names else name + "_output"
                if cand not in names:
                    raise MXNetError(
                        f"MXPredCreatePartialOut: {name} not in graph")
                outs.append(internals[cand])
            sym = sym_mod.Group(outs)
        loaded = load_frombuffer(param_bytes)
        arg_params, aux_params = {}, {}
        for k, v in loaded.items():
            if k.startswith("aux:"):
                aux_params[k[4:]] = v
            elif k.startswith("arg:"):
                arg_params[k[4:]] = v
            else:
                (aux_params if is_aux_name(k) else arg_params)[k] = v
        dev = self._DEV.get(int(dev_type)) if dev_type else None
        try:
            self._pred = Predictor(sym, arg_params, aux_params,
                                   dict(zip(input_names, input_shapes)),
                                   dev_type=dev, dev_id=int(dev_id))
        except MXNetError:
            if dev != "cpu":
                raise
            # cpu requested but jax only exposes the chip backend: the
            # default device is the deployment target anyway
            self._pred = Predictor(sym, arg_params, aux_params,
                                   dict(zip(input_names, input_shapes)))
        self._inputs = {}
        self._outputs = None
        self._abstract_shapes = None

    def set_input(self, key, flat):
        if key not in self._pred._shapes:
            raise MXNetError(f"MXPredSetInput: unknown input {key!r}")
        shape = tuple(self._pred._shapes[key])
        # copy: the C caller's buffer is only valid during the call
        arr = np.array(flat, np.float32, copy=True)
        if arr.size != int(np.prod(shape)):
            raise MXNetError(
                f"MXPredSetInput: {key} got {arr.size} elements, "
                f"shape {shape} needs {int(np.prod(shape))}")
        self._inputs[key] = arr.reshape(shape)
        self._outputs = None

    def forward(self):
        missing = [k for k in self._pred._shapes if k not in self._inputs]
        if missing:
            raise MXNetError(f"MXPredForward: inputs not set: {missing}")
        self._outputs = [np.asarray(o, np.float32)
                         for o in self._pred.forward(**self._inputs)]

    def reshaped(self, input_names, input_shapes):
        """A NEW bridge at the new shapes; this handle keeps serving its
        original shapes (reference MXPredReshape returns a fresh handle
        sharing weights, c_predict_api.h). Inputs not named keep their
        previous shapes, as the reference does."""
        unknown = [n for n in input_names
                   if n not in self._pred._shapes]
        if unknown:
            raise MXNetError(
                f"MXPredReshape: {unknown} are not inputs of this "
                f"predictor (declared: {sorted(self._pred._shapes)})")
        shapes = dict(self._pred._shapes)
        shapes.update(dict(zip(input_names, input_shapes)))
        clone = object.__new__(_CPredictor)
        p = Predictor.__new__(Predictor)
        p._device = self._pred._device
        p._symbol = self._pred._symbol
        p._input_names = list(shapes)
        p._shapes = shapes
        p._bindings = self._pred._bindings  # weights shared, not copied
        p._jitted = None
        p._lock = threading.Lock()
        clone._pred = p
        clone._inputs = {}
        clone._outputs = None
        clone._abstract_shapes = None
        return clone

    def _inferred_shapes(self):
        # one abstract trace per handle: shapes are fixed for its life
        if self._abstract_shapes is None:
            self._abstract_shapes = self._pred.output_shapes()
        return self._abstract_shapes

    def num_outputs(self):
        if self._outputs is None:
            return len(self._inferred_shapes())
        return len(self._outputs)

    def output_shape(self, index):
        if self._outputs is None:
            # legal straight after create: infer abstractly
            return self._inferred_shapes()[index]
        return tuple(self._outputs[index].shape)

    def output(self, index):
        self._ensure()
        return np.ascontiguousarray(self._outputs[index], np.float32)

    def _ensure(self):
        if self._outputs is None:
            raise MXNetError("MXPredGetOutput: call MXPredForward first")
