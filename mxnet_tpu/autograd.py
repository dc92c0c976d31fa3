"""Tape-based autograd (ref: python/mxnet/autograd.py + src/imperative/imperative.cc).

The reference records nnvm nodes per op and builds a gradient graph with the
nnvm Gradient pass (imperative.cc:278). Here recording builds a lightweight
tape of (op, attrs, input-slots, outputs); ``backward`` replays the reachable
subgraph as one pure JAX function and differentiates it with jax.vjp — the
FGradient attribute table is replaced by JAX AD, and XLA compiles/fuses the
whole backward. RNG keys drawn during forward are recorded as constants so the
replay is bit-identical (dropout masks match between forward and backward).

A recorded call of a hybridized block is not run again: its forward program
already wrote the residuals, and its node keeps the pullback (``_Node.
pullback``). The replay sees such a node as a ``jax.custom_vjp`` whose forward
is the outputs it gave and whose backward is that pullback, so the rest of the
tape chains through it. The node's plain closure is replayed instead where the
pullback cannot serve: ``create_graph=True`` (it is closed over concrete
residuals, so it has no derivative of its own), or a leaf asked for now that
the forward did not differentiate.
"""
from __future__ import annotations

import threading
import weakref

import jax
import jax.numpy as jnp

from . import tracing as _tracing
from .base import MXNetError

_state = threading.local()


def _st():
    if not hasattr(_state, "recording"):
        _state.recording = False
        _state.train_mode = False
        _state.tape = []
    return _state


class _Entry:
    """One array value in the recorded graph (nnvm NodeEntry analogue)."""

    __slots__ = ("node", "index", "nd_ref")

    def __init__(self, node, index, nd=None):
        self.node = node  # None for leaves (marked variables)
        self.index = index
        self.nd_ref = weakref.ref(nd) if nd is not None else None


class _Node:
    """One recorded op application (nnvm Node + AGInfo analogue)."""

    __slots__ = ("op", "attrs", "slots", "out_entries", "n_out", "pullback")

    def __init__(self, op, attrs, slots, n_out):
        self.op = op
        self.attrs = attrs
        self.slots = slots  # list of ("e", entry, snapshot) | ("c", value)
        self.out_entries = []
        self.n_out = n_out
        # (outs, diff, run) where the forward kept its pullback: the raw
        # outputs, which slots it differentiated, and run(cts of the
        # inexact outputs) -> the cotangents of those slots
        self.pullback = None


class _ClosureOp:
    """Minimal OpDef protocol for ops captured as closures (getitem, custom
    Function, grad-of-grad nodes)."""

    needs_rng = False
    _kwarg_names = ()

    def __init__(self, name, fn):
        self.name = name
        self.fn = fn

    def __call__(self, *a, **k):
        return self.fn(*a, **k)


# -- recording state ---------------------------------------------------------


def is_recording():
    return _st().recording


def is_training():
    return _st().train_mode


def set_recording(is_record):
    st = _st()
    prev = st.recording
    st.recording = bool(is_record)
    return prev


def set_training(train_mode):
    st = _st()
    prev = st.train_mode
    st.train_mode = bool(train_mode)
    return prev


class _RecordingStateScope:
    def __init__(self, is_record, train_mode):
        self._is_record = is_record
        self._train_mode = train_mode
        self._prev = None
        self._prev_train = None

    def __enter__(self):
        if self._is_record is not None:
            self._prev = set_recording(self._is_record)
        if self._train_mode is not None:
            self._prev_train = set_training(self._train_mode)
        return self

    def __exit__(self, *exc):
        if self._prev is not None or self._is_record is not None:
            set_recording(self._prev)
        if self._prev_train is not None or self._train_mode is not None:
            set_training(self._prev_train)
        return False


def record(train_mode=True):
    """Scope: operations are recorded for differentiation."""
    return _RecordingStateScope(True, train_mode)


def pause(train_mode=False):
    return _RecordingStateScope(False, train_mode)


def train_mode():
    return _RecordingStateScope(None, True)


def predict_mode():
    return _RecordingStateScope(None, False)


# -- tape construction -------------------------------------------------------


def _mark_variable(nd):
    nd._entry = _Entry(None, 0, nd)


def mark_variables(variables, gradients=None, grad_reqs="write"):
    """(ref: autograd.py mark_variables / MXAutogradMarkVariables)"""
    if gradients is None:
        gradients = [None] * len(variables)
    if isinstance(grad_reqs, str):
        grad_reqs = [grad_reqs] * len(variables)
    for v, g, req in zip(variables, gradients, grad_reqs):
        v.grad = g
        v._grad_req = req
        _mark_variable(v)


def _inexact(data):
    return jnp.issubdtype(data.dtype, jnp.inexact)


def _differentiated(nd):
    """Whether a forward that keeps its pullback has to differentiate
    ``nd``: it is on the tape, as a variable that wants a gradient or as
    a recorded node's output (the rule of ``_collect``'s ``grad_leaves``,
    applied when the forward runs)."""
    e = nd._entry
    return (e is not None and (e.node is not None or nd._grad_req != "null")
            and _inexact(nd._data))


def _slot_for(nd):
    if nd._entry is not None:
        return ("e", nd._entry, nd._data)
    return ("c", nd._data)


def _record_op(op, attrs, nd_inputs, nd_outputs, rng_consts=()):
    st = _st()
    slots = [("c", k) for k in rng_consts]
    slots += [_slot_for(i) for i in nd_inputs]
    node = _Node(op, attrs, slots, len(nd_outputs))
    for idx, o in enumerate(nd_outputs):
        e = _Entry(node, idx, o)
        node.out_entries.append(e)
        o._entry = e
    st.tape.append(node)
    return node


def _record_getitem(nd, key):
    from .ndarray.ndarray import NDArray

    op = _ClosureOp("getitem", lambda x: x[key])
    out_data = op.fn(nd._data)
    out = NDArray(out_data)
    _record_op(op, {}, [nd], [out])
    return out


def _record_closure(name, fn, nd_inputs, nd_outputs):
    return _record_op(_ClosureOp(name, fn), {}, nd_inputs, nd_outputs)


# -- backward ----------------------------------------------------------------


def _collect(head_entries):
    """Reachable subgraph in recorded (topological) order + ordered leaves."""
    st = _st()
    needed = set()
    leaves = []
    leaf_seen = set()
    stack = [e for e in head_entries if e is not None]
    while stack:
        e = stack.pop()
        if e.node is None:
            if id(e) not in leaf_seen:
                leaf_seen.add(id(e))
                leaves.append(e)
            continue
        if id(e.node) in needed:
            continue
        needed.add(id(e.node))
        for s in e.node.slots:
            if s[0] == "e":
                stack.append(s[1])
    nodes = [n for n in st.tape if id(n) in needed]
    # only leaves attached to live NDArrays that want grad
    grad_leaves = [
        e for e in leaves
        if e.nd_ref is not None and e.nd_ref() is not None
        and e.nd_ref()._grad_req != "null"
    ]
    return nodes, grad_leaves


def _held_fn(node):
    """A node's forward as the replay runs it when the stored pullback
    serves: the outputs the forward program gave, whatever the inputs,
    and for their derivative the pullback that program kept."""
    outs, diff, run = node.pullback
    live = [i for i, o in enumerate(outs) if _inexact(o)]
    live_outs = tuple(outs[i] for i in live)
    held = jax.custom_vjp(lambda *diff_ins: live_outs)
    held.defvjp(lambda *diff_ins: (live_outs, None), lambda _, cts: run(cts))

    def fn(*ins):
        vals = list(outs)
        for i, v in zip(live, held(*[x for x, d in zip(ins, diff) if d])):
            vals[i] = v
        return vals

    return fn


def _pullback_serves(node, wanted):
    """False where a leaf asked for now (``wanted``: ids of the grad
    leaves' entries) feeds a slot the forward did not differentiate."""
    return all(d or s[0] != "e" or id(s[1]) not in wanted
               for s, d in zip(node.slots, node.pullback[1]))


def _build_replay(nodes, grad_leaves, head_entries, held):
    """Pure function leaf_values -> head_values replaying the tape;
    ``held`` maps id(node) to the function that stands in for its op."""

    def f(*leaf_vals):
        env = {id(e): v for e, v in zip(grad_leaves, leaf_vals)}
        for node in nodes:
            ins = []
            for s in node.slots:
                if s[0] == "e":
                    ins.append(env.get(id(s[1]), s[2]))
                else:
                    ins.append(s[1])
            raw = held.get(id(node), node.op.fn)(*ins, **node.attrs)
            raws = list(raw) if isinstance(raw, (tuple, list)) else [raw]
            for e, v in zip(node.out_entries, raws):
                env[id(e)] = v
        outs = []
        for e in head_entries:
            if id(e) in env:
                outs.append(env[id(e)])
            else:
                nd = e.nd_ref() if e.nd_ref else None
                outs.append(nd._data if nd is not None else None)
        return tuple(outs)

    return f


def _release_written(grad_leaves):
    """Let go of the gradient that a ``write`` leaf's backward replaces,
    before that backward runs: the device can then give its memory to the
    new one (at 16 bytes of state a parameter, the 2 of a bfloat16
    gradient held twice is what a step would not fit in). The NDArray
    itself stays and takes the new value; until then it holds nothing."""
    released = []
    for e in grad_leaves:
        nd = e.nd_ref()
        if nd._grad_req == "write" and nd.grad is not None:
            old = nd.grad._data
            released.append((nd.grad, old.shape, old.dtype))
            nd.grad._data = None
    return released


def _compute_gradients(heads, head_grads, create_graph=False,
                       release=False):
    from .ndarray.ndarray import NDArray

    head_entries = []
    tape_ids = {id(n) for n in _st().tape}
    for h in heads:
        if h._entry is None:
            raise MXNetError(
                "cannot differentiate: output is not part of a recorded "
                "computational graph (did you forget autograd.record()?)")
        if h._entry.node is not None and id(h._entry.node) not in tape_ids:
            raise MXNetError(
                "cannot differentiate: the computational graph has already "
                "been freed (backward was called before); pass "
                "retain_graph=True to keep it")
        head_entries.append(h._entry)

    nodes, grad_leaves = _collect(head_entries)
    if not grad_leaves:
        raise MXNetError("no variables with grad attached found in the graph")
    released = _release_written(grad_leaves) if release else ()

    held = {}
    if not create_graph:
        wanted = {id(e) for e in grad_leaves}
        held = {id(n): _held_fn(n) for n in nodes
                if n.pullback is not None and _pullback_serves(n, wanted)}
    f = _build_replay(nodes, grad_leaves, head_entries, held)
    leaf_vals = [e.nd_ref()._data for e in grad_leaves]

    if head_grads is None:
        hg = [jnp.ones(h.shape, h._data.dtype) for h in heads]
    else:
        hg = [
            g._data if g is not None else jnp.ones(h.shape, h._data.dtype)
            for h, g in zip(heads, head_grads)
        ]

    def gradfn(*lv):
        # the replay is traced anew by every call: the span holds the
        # linearisation of what the tape holds besides the nodes that
        # kept their pullback, and the next span runs the pullbacks
        with _tracing.span("autograd.vjp"):
            _, vjp_fn = jax.vjp(f, *lv)
        with _tracing.span("autograd.pullback"):
            return vjp_fn(tuple(hg))

    try:
        grads = gradfn(*leaf_vals)
    except BaseException:
        for g, shape, dtype in released:      # what was let go reads zero
            g._data = jnp.zeros(shape, dtype)
        raise
    grad_nds = [NDArray(g) for g in grads]

    if create_graph:
        # record the grad computation itself so second-order grads work
        leaf_nds = [e.nd_ref() for e in grad_leaves]
        _record_closure("grad", gradfn, leaf_nds, grad_nds)

    return grad_leaves, grad_nds


def _free_graph():
    """Drop the recorded graph and everything it holds. A node and its
    output entries refer to each other, so left to themselves the arrays
    snapshotted in a node's slots (every parameter a cached op read, its
    inputs and its outputs) and the residuals its pullback is closed over
    stay on the device until the cyclic collector
    happens to run: at a large model's size, several steps' worth."""
    tape = _st().tape
    for node in tape:
        node.slots = node.out_entries = ()
        node.pullback = None
    tape.clear()


def backward(heads, head_grads=None, retain_graph=False, train_mode=True):
    """Compute gradients of heads wrt all marked variables, accumulating into
    their .grad per grad_req (ref: MXAutogradBackwardEx)."""
    from .ndarray.ndarray import NDArray

    with _tracing.span("autograd.backward"):
        grad_leaves, grads = _compute_gradients(heads, head_grads,
                                                release=True)
        for e, g in zip(grad_leaves, grads):
            nd = e.nd_ref()
            if nd._grad_req == "add" and nd.grad is not None:
                nd.grad._data = nd.grad._data + g._data
            else:
                if nd.grad is None:
                    nd.grad = NDArray(g._data)
                else:
                    nd.grad._data = g._data
        if not retain_graph:
            _free_graph()


def grad(heads, variables, head_grads=None, retain_graph=None,
         create_graph=False, train_mode=True):
    """Functional gradient API (ref: autograd.py grad())."""
    if retain_graph is None:
        retain_graph = create_graph
    prev_reqs = [(v, v._grad_req) for v in variables]
    for v in variables:
        if v._entry is None:
            _mark_variable(v)
        if v._grad_req == "null":
            v._grad_req = "write"
    try:
        with _tracing.span("autograd.backward"):
            grad_leaves, grads = _compute_gradients(
                heads, head_grads, create_graph=create_graph)
    finally:
        for v, req in prev_reqs:
            v._grad_req = req
    by_id = {id(e.nd_ref()): g for e, g in zip(grad_leaves, grads)}
    out = []
    for v in variables:
        if id(v) not in by_id:
            raise MXNetError("one of the requested variables does not "
                             "contribute to the heads")
        out.append(by_id[id(v)])
    if not retain_graph:
        _free_graph()
    return out


def get_symbol(x):
    raise MXNetError("get_symbol is not supported; use HybridBlock.export")


class Function:
    """Custom differentiable function (ref: autograd.py Function).

    Subclass and implement forward(self, *inputs) / backward(self, *out_grads),
    both operating on NDArrays. The pair is wrapped in a jax.custom_vjp over
    the replay trace, so it composes with the rest of the tape.
    """

    def __init__(self):
        self._saved = ()

    def save_for_backward(self, *arrays):
        self._saved = arrays

    @property
    def saved_tensors(self):
        return self._saved

    def __call__(self, *inputs):
        from .ndarray.ndarray import NDArray

        func = self

        def fwd_raw(*datas):
            nds = [NDArray(d) for d in datas]
            with pause():
                outs = func.forward(*nds)
            multi = isinstance(outs, (tuple, list))
            outs = list(outs) if multi else [outs]
            return tuple(o._data for o in outs)

        @jax.custom_vjp
        def wrapped(*datas):
            return fwd_raw(*datas)

        def wrapped_fwd(*datas):
            out = fwd_raw(*datas)
            return out, datas

        def wrapped_bwd(datas, gs):
            nds = [NDArray(d) for d in datas]
            with pause():
                func.forward(*nds)  # rebuild saved tensors for this trace
                grads = func.backward(*[NDArray(g) for g in gs])
            multi = isinstance(grads, (tuple, list))
            grads = list(grads) if multi else [grads]
            return tuple(g._data for g in grads)

        wrapped.defvjp(wrapped_fwd, wrapped_bwd)

        raw = wrapped(*[i._data for i in inputs])
        from .ndarray.ndarray import NDArray as _ND

        outs = [_ND(r) for r in raw]
        if is_recording():
            _record_closure("custom_function", wrapped, list(inputs), outs)
        return outs if len(outs) > 1 else outs[0]
