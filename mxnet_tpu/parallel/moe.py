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
The buffer holds every visit there could be, tokens x k rows, so its
shape does not depend on the routing and a routing that changes from
step to step runs the same compiled program; the rows past the last
group are never multiplied. On a TPU ``ragged_dot`` is the compiler's
own grouped kernel (``ragged-dot`` in a capture), forward and in both
gradients.

New capability vs. the reference (SURVEY.md §2.3 item 7). The closest
reference analogue is the sparse row_sparse parameter-server path
(ref: src/kvstore/kvstore_dist.h:470 PullRowSparse) — sending only the
needed rows; here the routing moves activations instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
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


def experts_held(x, sel, gate, w1, w3, w2, first=0):
    """This share's part of a gated-MLP expert layer.

    Differentiated, the layer keeps its arguments alone and recomputes the
    grouped products in the backward pass (``jax.checkpoint``): the
    visits' buffer is as long as every visit there could be, eight times
    the live rows where a chip holds an eighth of the experts, and its
    intermediates would be kept at that length.

    ``x`` [tokens, d]; ``sel`` / ``gate`` [tokens, k] from :func:`route`
    over all the experts; ``w1``, ``w3`` [held, d, f] and ``w2``
    [held, f, d] are the experts ``first`` … ``first + held - 1``.
    Returns ``sum_e gate_e * w2_e(silu(w1_e x) * w3_e x)`` over the
    selected experts that are held: [tokens, d]. No visit is dropped.
    """
    return jax.checkpoint(_experts_held)(x, sel, gate, w1, w3, w2, first)


def _experts_held(x, sel, gate, w1, w3, w2, first):
    n, k = sel.shape
    held = w1.shape[0]
    local = sel.reshape(-1) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)          # absent experts sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    token = order // k
    # rows past the last group are never multiplied; what a kernel leaves
    # there is masked on the way in (so no gradient comes back through
    # them) and on the way out
    live = (jnp.arange(n * k) < jnp.sum(sizes))[:, None]
    xs = jnp.where(live, jnp.take(x, token, axis=0), 0)   # [tokens * k, d]
    h = swiglu(lax.ragged_dot(xs, w1, sizes), lax.ragged_dot(xs, w3, sizes))
    y = jnp.where(live, lax.ragged_dot(h, w2, sizes), 0)
    y = y.astype(jnp.float32) * jnp.take(gate.reshape(-1), order)[:, None]
    back = jnp.argsort(order)                   # visit -> its sorted row
    out = jnp.take(y, back, axis=0).reshape(n, k, -1).sum(axis=1)
    return out.astype(x.dtype)


def moe_ffn(x, router_w, w1, w3, w2, expert_bias=None, k=1, first=0,
            norm_topk=True, scale=1.0, score="sigmoid"):
    """Router and held experts in one call: ``(out, counts)``."""
    sel, gate, counts = route(x, router_w, expert_bias, k=k,
                              norm_topk=norm_topk, scale=scale, score=score)
    return experts_held(x, sel, gate, w1, w3, w2, first=first), counts


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
