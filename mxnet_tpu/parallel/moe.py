"""Expert parallelism: a top-k routed expert layer that drops no token and
is told which experts it holds.

Every chip of an expert-parallel job routes its tokens over ALL the
experts (``route``: the router keeps its published width) and computes
the part of the result that the experts it holds give
(``experts_held``). What the absent experts would add arrives over the
exchange in a job that spans chips; on one chip there is no exchange and
the partial result is what goes on. The parts of all the shares add up
to the whole layer (tests/test_moe.py).

The held experts' products are grouped: the (token, slot) visits are
sorted by expert, visits to absent experts last, and one
``lax.ragged_dot`` per weight multiplies each group by its own expert.
On a TPU ``ragged_dot`` is the compiler's own grouped kernel
(``ragged-dot`` in a capture), forward and in both gradients.

The buffer the rows are gathered into, multiplied in and added back from
is a rung of a ladder long (:func:`ladder`: a quarter, a half and the
whole of tokens x k), and the rung is chosen on the device, each call,
from the live count (:func:`rung_rows`: the shortest rung that holds
every visit to a held expert). Every rung is in the one compiled
program, so a routing that changes from step to step compiles nothing,
and the longest rung is every visit there could be, so no visit is ever
dropped. Only the plan (one sort of tokens x k integers) is always full
length.

Why three rungs a factor of two apart (my chip runs, PR 33; PERF.md §6):
on a TPU v5e a layer costs some 0.5 us a buffer row whatever is live
(2,048 wide, forward and backward: 8.3 / 12.1 / 22.0 / 45.1 ms on 10,240
/ 20,480 / 40,960 / 81,920 rows), so a rung twice its live rows wastes
at most the rung's half; a chip of an ep-way job sees about 1/ep of the
visits and its busiest layer up to twice that, which a quarter holds
from ep = 8 on. Every rung is a branch in both gluon programs of a model
and costs each process some 0.65 s of set-up (found again in the compile
cache or not), which is what an eighth's rung did not repay.

New capability vs. the reference (SURVEY.md §2.3 item 7). The closest
reference analogue is the sparse row_sparse parameter-server path
(ref: src/kvstore/kvstore_dist.h:470 PullRowSparse) — sending only the
needed rows; here the routing moves activations instead.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.nn import swiglu


def route(x, router_w, expert_bias=None, k=1, norm_topk=True, scale=1.0,
          score="sigmoid"):
    """Top-k routing of ``x`` [tokens, d] over the ``router_w.shape[0]``
    experts; ``router_w`` is [experts, d].

    Scores are float32: the ``sigmoid`` of the router's product, or its
    ``softmax`` over all the experts, as ``score`` says.
    ``expert_bias`` [experts] is added for the SELECTION only;
    the weights come from the unbiased scores, divided by their sum over
    the k selected where ``norm_topk`` (+ 1e-6 under ``sigmoid``, whose
    scores may all be near zero), times ``scale``.

    Returns ``(sel, gate, counts)``: int32 [tokens, k] expert ids,
    float32 [tokens, k] weights (differentiable towards ``x`` and
    ``router_w``), and int32 [experts] visits to each expert.
    """
    logits = lax.dot_general(x, router_w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if score not in ("sigmoid", "softmax"):
        raise ValueError(f"route: score {score!r}")
    sigmoid = score == "sigmoid"
    scores = jax.nn.sigmoid(logits) if sigmoid else \
        jax.nn.softmax(logits, axis=-1)
    biased = scores if expert_bias is None else \
        scores + expert_bias.astype(jnp.float32)
    _, sel = lax.top_k(lax.stop_gradient(biased), k)
    gate = jnp.take_along_axis(scores, sel, axis=1)
    if norm_topk:
        gate = gate / (jnp.sum(gate, axis=1, keepdims=True) +
                       (1e-6 if sigmoid else 0.0))
    gate = gate * scale
    n_experts = router_w.shape[0]
    counts = jnp.sum(jax.nn.one_hot(sel.reshape(-1), n_experts,
                                    dtype=jnp.int32), axis=0)
    return sel.astype(jnp.int32), gate, counts


def ladder(rows):
    """The static lengths a visits' buffer of ``rows`` = tokens x k may
    take: a quarter, a half and the whole of it (each rounded up; equal
    lengths once). Derived from ``rows`` alone."""
    return tuple(sorted({-(-rows // part) for part in (4, 2, 1)}))


def _rung_index(live, steps):
    # how many of the shorter rungs the live rows do not fit: plain
    # comparisons, so a count on the host and a traced one read alike
    return sum(live > c for c in steps[:-1])


def rung_rows(live, rows):
    """The rule: the length of the buffer a call with ``live`` visits to
    held experts works on, of ``rows`` = tokens x k there could be — the
    shortest rung of :func:`ladder` that holds every live row, so ``rows``
    itself when more than half of them are live. ``live`` is a count on
    the host, or an array of counts (a row of ``net.expert_tokens`` summed
    over the held experts, layer by layer)."""
    steps = ladder(rows)
    return np.asarray(steps)[_rung_index(np.asarray(live), steps)]


def _on_rung(body, x, sel, gate, w1, w3, w2, first, *more, act="silu"):
    """Plan (integers only, at the full length: the visits sorted by held
    expert, visits to absent experts last), then ``body`` on the rung
    that holds the live rows."""
    held = w1.shape[0]
    local = sel.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    steps = ladder(key.shape[0])
    return lax.switch(_rung_index(jnp.sum(sizes), steps),
                      _branches(body, steps, act),
                      x, gate, w1, w3, w2, order, sizes, *more)


@lru_cache(maxsize=None)
def _branches(body, steps, act):
    # the same callables every call: JAX keeps a branch's traced body by
    # the callable and its shapes, so a model's second expert layer and
    # its second program find the first's (fresh closures cost a model of
    # four layers three seconds of set-up)
    return tuple(partial(body, rows, act) for rows in steps)


def _rung(rows, act, x, gate, w1, w3, w2, order, sizes):
    """The layer on the first ``rows`` sorted visits (every live one is
    among them): gather, the three grouped products, the weighted rows
    added to their tokens. Nothing here is longer than ``rows``."""
    with jax.named_scope(f"rows.{rows}"):
        order = order[:rows]
        token = order // gate.shape[1]
        # rows past the last group are never multiplied; what a kernel
        # leaves there is masked on the way in (so no gradient comes back
        # through them) and on the way out
        live = (jnp.arange(rows) < jnp.sum(sizes))[:, None]
        xs = jnp.where(live, jnp.take(x, token, axis=0), 0)
        h = swiglu(lax.ragged_dot(xs, w1, sizes),
                   lax.ragged_dot(xs, w3, sizes), act=act)
        y = jnp.where(live, lax.ragged_dot(h, w2, sizes), 0)
        y = y.astype(jnp.float32) * jnp.take(gate.reshape(-1), order)[:, None]
        out = jnp.zeros(x.shape, jnp.float32).at[token].add(y)
        return out.astype(x.dtype)


def _rung_grads(rows, act, x, gate, w1, w3, w2, order, sizes, ct):
    _, pull = jax.vjp(
        lambda *args: _rung(rows, act, *args, order, sizes),
        x, gate, w1, w3, w2)
    return pull(ct)


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _experts_held(x, sel, gate, w1, w3, w2, first, act):
    return _on_rung(_rung, x, sel, gate, w1, w3, w2, first, act=act)


def _experts_held_fwd(*args):
    return _experts_held(*args), args[:-1]


def _experts_held_bwd(act, args, ct):
    dx, dgate, dw1, dw3, dw2 = _on_rung(_rung_grads, *args, ct, act=act)
    return dx, None, dgate, dw1, dw3, dw2, None


_experts_held.defvjp(_experts_held_fwd, _experts_held_bwd)


def experts_held(x, sel, gate, w1, w3, w2, first=0, act="silu"):
    """This share's part of a gated-MLP expert layer.

    ``x`` [tokens, d]; ``sel`` / ``gate`` [tokens, k] from :func:`route`
    over all the experts; ``w1``, ``w3`` [held, d, f] and ``w2``
    [held, f, d] are the experts ``first`` … ``first + held - 1``.
    Returns ``sum_e gate_e * w2_e(act(w1_e x) * w3_e x)`` over the
    selected experts that are held: [tokens, d]; ``act`` is ``silu`` or
    ``relu``. No visit is dropped. ``sel`` and ``gate`` may come from
    another input than ``x`` (a router that reads the layer's input
    before attention): the layer only multiplies what it is told.

    The rows gathered, multiplied and added back are a rung of
    :func:`ladder` long, the rung :func:`rung_rows` names for this call's
    live count; under a model each rung's operations carry the scope
    ``rows.<length>``, forward and backward.

    Differentiated, the layer keeps its arguments alone and runs the
    rung's forward again in the backward pass. The derivative is the
    function's own (``jax.custom_vjp``) because JAX's rule for a
    conditional makes every branch return every other branch's
    residuals, zeros at their full shapes: the shortest rung would write
    the longest one's buffers after all. Here only the arguments, the
    cotangent and the five gradients cross either ``lax.switch``.
    """
    if act not in ("silu", "relu"):
        raise ValueError(f"experts_held: act {act!r}")
    return _experts_held(x, sel, gate, w1, w3, w2,
                         jnp.asarray(first, jnp.int32), act)


def moe_ffn(x, router_w, w1, w3, w2, expert_bias=None, k=1, first=0,
            norm_topk=True, scale=1.0, score="sigmoid", act="silu",
            route_on=None):
    """Router and held experts in one call: ``(out, counts)``. The router
    reads ``route_on`` [tokens, d] where given, else ``x``."""
    sel, gate, counts = route(x if route_on is None else route_on, router_w,
                              expert_bias, k=k, norm_topk=norm_topk,
                              scale=scale, score=score)
    return experts_held(x, sel, gate, w1, w3, w2, first=first,
                        act=act), counts


def moe_ffn_ep(x, router_w, w1, w3, w2, axis_name="ep", **route_args):
    """The layer across an ``ep`` mesh axis (call inside ``shard_map``;
    ``x`` and the router replicated, the experts split over the axis):
    every device computes its own experts' part for all the tokens and
    one ``psum`` adds the parts. Correct for any routing and drops
    nothing; a job whose tokens are split over the same axis wants the
    all-to-all exchange instead (ROADMAP B4)."""
    held = w1.shape[0]
    first = lax.axis_index(axis_name) * held
    out, counts = moe_ffn(x, router_w, w1, w3, w2, first=first, **route_args)
    return lax.psum(out, axis_name), counts
