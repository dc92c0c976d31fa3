"""Laguna decoder (``model_type: laguna``; poolside/Laguna-S-2.1's
``config.json``) as a gluon HybridBlock, built from the configuration dict.
Every product is without bias.

- ``N(x; w) = w * x / sqrt(mean(x^2) + rms_norm_eps)``, statistics in
  float32, ``w`` born one.
- Layer ``i`` on ``x``: ``u = N(x; w_in)``, ``h = x + Attn_i(u)``, ``n =
  N(h; w_post)``, ``y = h + FF_i(n)``. ``logits = W_head . N(x_L; w_f)``,
  float32; ``W_head`` is untied.
- ``Attn_i`` (``H_i = num_attention_heads_per_layer[i]`` over
  ``num_key_value_heads``, ``head_dim`` lanes): ``q = W_q u``, ``k = W_k
  u``, ``v = W_v u``; rotary encoding, rotate-half form, by
  ``rope_parameters[layer_types[i]]``: over ``partial_rotary_factor *
  head_dim`` lanes of q and k, ``rope_theta``, and where ``rope_type`` is
  ``yarn`` YaRN's frequencies and ``attention_factor`` on cos and sin
  (``ops/nn.py`` ``rotary_embedding``). A ``sliding_attention`` layer's
  query ``t`` sees keys ``t - sliding_window + 1 ... t``; a
  ``full_attention`` layer's every earlier key. ``o = softmax(q k^T /
  sqrt(head_dim)) v`` in float32, each K/V head serving ``H_i /
  num_key_value_heads`` consecutive query heads; the per-head gate ``o_h
  <- sigmoid((W_g u)_h) o_h`` (``gating: per-head``); ``Attn = W_o
  concat_h(o_h)``.
- ``FF_i`` where ``mlp_layer_types[i]`` is ``dense``: ``W_2(silu(W_1 n) *
  W_3 n)`` at ``intermediate_size``. Where ``sparse``: ``p = softmax(W_r
  n)`` over all the published experts, float32; ``sel = top_k(p)``; ``w_e =
  p_e / sum_sel p`` (``norm_topk_prob``); ``FF = S(n) +
  moe_routed_scaling_factor * sum over the selected experts that are HELD
  of w_e E_e(n)``, ``E_e`` and the shared expert ``S`` SwiGLU blocks at
  ``moe_intermediate_size`` and ``shared_expert_intermediate_size``. No
  token is dropped, no selection bias, no soft-capping.

``held = (first, count)`` is this chip's share of an expert-parallel
job, as in ``lfm2_moe``: the layer routes over all the experts and
computes the selected experts ``first`` ... ``first + count - 1`` only.
The shared expert, which every chip computes alike, is whole here.

Read from the configuration: ``vocab_size``, ``hidden_size``,
``intermediate_size``, ``layer_types``, ``mlp_layer_types``,
``num_attention_heads_per_layer`` (else ``num_attention_heads``),
``num_key_value_heads``, ``head_dim``, ``sliding_window``,
``rope_parameters``, ``num_experts``, ``published_num_experts`` (the
router's width where ``num_experts`` counts the experts held here),
``num_experts_per_tok``, ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``norm_topk_prob``,
``moe_routed_scaling_factor``, ``rms_norm_eps``. Refused:
``tie_word_embeddings``, ``attention_bias``,
``moe_apply_router_weight_on_input``, a non-zero
``moe_router_logit_softcapping``, a gating other than per head.
Assumed, the published file having no key for it: softmax router
scores; the shared expert ungated; silu in every gated product; the gate
reads ``u``; no norm on q or k (``docs/laguna.md``).

Training memory is ``lfm2_moe``'s: attention, the dense MLP and the
shared expert are rematerialised (``gluon.utils.recompute``), the
elementwise ops and the routed experts recompute their own
intermediates.

Device-side named scopes: ``laguna.attn.window``, ``laguna.attn.full``,
``laguna.dense_mlp``, ``laguna.shared_expert``, ``laguna.head``; the
routed part keeps ``lfm2.moe.route`` / ``lfm2.moe.experts`` (one code,
one reader). The counter ``net.expert_tokens`` (int32 [sparse layers,
published experts]) is ``lfm2_moe``'s.
"""
from __future__ import annotations

import math

import jax

from ....base import MXNetError
from ...block import HybridBlock, defer_aux_update
from ...nn import RMSNorm
from ...utils import recompute
from .lfm2_moe import DenseMLP, SparseExperts, _dense


def rotary_attrs(rope, head_dim):
    """``RotaryEmbedding``'s attributes for one entry of
    ``rope_parameters``."""
    attrs = {"theta": float(rope["rope_theta"]),
             "rotary_dim": int(head_dim * rope.get("partial_rotary_factor",
                                                   1.0))}
    kind = rope.get("rope_type", "default")
    if kind == "yarn":
        factor = float(rope["factor"])
        attrs.update(
            yarn_factor=factor,
            yarn_original=int(rope["original_max_position_embeddings"]),
            beta_fast=float(rope.get("beta_fast", 32)),
            beta_slow=float(rope.get("beta_slow", 1)),
            attention_factor=float(rope.get("attention_factor") or
                                   0.1 * math.log(factor) + 1.0))
    elif kind != "default":
        raise MXNetError(f"laguna: rope_type {kind!r} is not built")
    return attrs


class GatedAttention(HybridBlock):
    """Causal grouped-query attention, rotated by ``rope`` (the
    attributes of ``RotaryEmbedding``), ``window`` > 0 bounding the keys
    a query sees, each head's output scaled by a sigmoid gate of its
    own read from the layer's input."""

    def __init__(self, hidden, heads, kv_heads, head_dim, rope, window,
                 dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._head_dim, self._rope, self._window = head_dim, rope, window
        with self.name_scope():
            get = self.params.get
            self.q_proj = get("q_proj_weight", dtype=dtype,
                              shape=(heads * head_dim, hidden))
            self.k_proj = get("k_proj_weight", dtype=dtype,
                              shape=(kv_heads * head_dim, hidden))
            self.v_proj = get("v_proj_weight", dtype=dtype,
                              shape=(kv_heads * head_dim, hidden))
            self.g_proj = get("g_proj_weight", dtype=dtype,
                              shape=(heads, hidden))
            self.o_proj = get("o_proj_weight", dtype=dtype,
                              shape=(hidden, heads * head_dim))

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, g_proj, o_proj):
        heads = (0, 0, -1, self._head_dim)
        q = F.reshape(_dense(F, u, q_proj), shape=heads)
        k = F.reshape(_dense(F, u, k_proj), shape=heads)
        v = F.reshape(_dense(F, u, v_proj), shape=heads)
        o = F.GQAttention(F.RotaryEmbedding(q, **self._rope),
                          F.RotaryEmbedding(k, **self._rope), v,
                          causal=True, window=self._window)
        gate = F.expand_dims(F.sigmoid(_dense(F, u, g_proj)), axis=-1)
        return _dense(F, F.reshape(F.broadcast_mul(o, gate),
                                   shape=(0, 0, -1)), o_proj)


class SharedSparseExperts(HybridBlock):
    """``routed + shared``: ``lfm2_moe.SparseExperts`` over this share's
    experts (the router's ``scale`` multiplies their weights) beside the
    ungated shared expert, which is whole on every chip; its operations
    carry the named scope ``scope``."""

    def __init__(self, hidden, width, shared_width, held, router, dtype,
                 scope="laguna.shared_expert", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._shared_scope = scope
        with self.name_scope():
            self.routed = SparseExperts(hidden, width, held, router, dtype,
                                        prefix="routed_")
            self.shared = DenseMLP(hidden, shared_width, dtype,
                                   prefix="shared_")
        self.router = self.routed.router

    def hybrid_forward(self, F, n):
        out, counts = self.routed(n)
        with jax.named_scope(self._shared_scope):
            shared = recompute(self.shared, n)
        return out + shared, counts


class DecoderLayer(HybridBlock):
    """``h = x + Attn(N(x))``, ``y = h + FF(N(h))``; attention runs under the
    named scope ``attn_scope`` (by default ``laguna.attn.<kind>``), a dense
    ``FF`` under ``mlp_scope``."""

    def __init__(self, attn, ff, sparse, hidden, eps, dtype, attn_scope=None,
                 mlp_scope="laguna.dense_mlp", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        norm = lambda name: RMSNorm(eps, in_channels=hidden, dtype=dtype,
                                    prefix=name)
        with self.name_scope():
            self.input_norm = norm("input_norm_")
            self.attn = attn()
            self.post_norm = norm("post_norm_")
            self.ff = ff()
        self.sparse = sparse
        self._scope = attn_scope or "laguna.attn." + (
            "window" if self.attn._window else "full")
        self._mlp_scope = mlp_scope

    def hybrid_forward(self, F, x):
        # attention and the dense MLP keep their input alone and run again
        # in the backward pass; the routed experts recompute themselves
        # (MoEExperts)
        with jax.named_scope(self._scope):
            h = x + recompute(lambda v: self.attn(self.input_norm(v)), x)
        if self.sparse:
            out, counts = self.ff(self.post_norm(h))
            return h + out, counts
        with jax.named_scope(self._mlp_scope):
            return h + recompute(lambda v: self.ff(self.post_norm(v)), h)


class Decoder(HybridBlock):
    """``net(ids)`` -> float32 logits [batch, seq, vocab]: the embedding,
    the layers, the final norm and the untied head (under the named scope
    ``HEAD_SCOPE``); the sparse layers' visits are summed into the counter
    ``net.expert_tokens``. A model's ``__init__`` reads its configuration
    and hands :meth:`_build` one ``DecoderLayer`` maker a layer."""

    HEAD_SCOPE = "laguna.head"

    def _build(self, vocab, hidden, eps, experts, layers, dtype):
        self._vocab, self._hidden, self._eps = vocab, hidden, eps
        with self.name_scope():
            self.embed = self.params.get("embed_weight", dtype=dtype,
                                         shape=(vocab, hidden))
            self.norm = self.params.get("norm_gamma", shape=(hidden,),
                                        dtype=dtype, init="ones")
            self.head = self.params.get("head_weight", dtype=dtype,
                                        shape=(vocab, hidden))
            self.layers = []
            for i, make in enumerate(layers):
                layer = make(prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            # visits to each expert of each sparse layer, summed on the
            # device over the forward passes so far (lfm2_moe's counter)
            n_sparse = sum(layer.sparse for layer in self.layers)
            self.expert_tokens = self.params.get(
                "expert_tokens", shape=(n_sparse, experts), dtype="int32",
                init="zeros", differentiable=False) if n_sparse else None

    def hybrid_forward(self, F, ids, embed, norm, head, expert_tokens=None):
        x = F.Embedding(ids, embed, input_dim=self._vocab,
                        output_dim=self._hidden)
        visits = []
        for layer in self.layers:
            if layer.sparse:
                x, counts = layer(x)
                visits.append(counts)
            else:
                x = layer(x)
        if visits:
            defer_aux_update(self.expert_tokens,
                             expert_tokens + F.stack(*visits, axis=0))
        with jax.named_scope(self.HEAD_SCOPE):
            return _dense(F, F.RMSNorm(x, norm, eps=self._eps), head,
                          out_dtype="float32")


class Laguna(Decoder):
    """``net(ids)`` -> float32 logits [batch, seq, vocab_size]."""

    def __init__(self, config, held=None, dtype="bfloat16", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        cfg = config
        for key in ("tie_word_embeddings", "attention_bias",
                    "moe_apply_router_weight_on_input",
                    "moe_router_logit_softcapping"):
            if cfg.get(key):
                raise MXNetError(f"laguna: {key} is not built")
        if cfg.get("gating", "per-head") not in ("per-head", True):
            raise MXNetError(f"laguna: gating {cfg['gating']!r} is not built")
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        kinds, mlps = cfg["layer_types"], cfg["mlp_layer_types"]
        depth = cfg["num_hidden_layers"]
        heads = cfg.get("num_attention_heads_per_layer") or \
            [cfg["num_attention_heads"]] * depth
        if not len(kinds) == len(mlps) == len(heads) == depth:
            raise MXNetError(
                f"laguna: layer_types / mlp_layer_types / heads of "
                f"{len(kinds)} / {len(mlps)} / {len(heads)} entries for "
                f"{depth} layers")
        experts = cfg.get("published_num_experts", cfg["num_experts"])
        held = tuple(held) if held else (0, cfg["num_experts"])
        if held[0] < 0 or held[0] + held[1] > experts:
            raise MXNetError(f"laguna: held {held} of {experts} experts")
        head_dim = cfg["head_dim"]
        rope = {kind: rotary_attrs(p, head_dim)
                for kind, p in cfg["rope_parameters"].items()
                if isinstance(p, dict)}
        windows = {"full_attention": 0,
                   "sliding_attention": cfg["sliding_window"]}
        router = {"experts": experts, "k": cfg["num_experts_per_tok"],
                  "norm_topk": bool(cfg.get("norm_topk_prob", True)),
                  "scale": float(cfg.get("moe_routed_scaling_factor", 1.0)),
                  "use_bias": False, "score": "softmax"}
        attn = lambda i: lambda: GatedAttention(
            hidden, heads[i], cfg["num_key_value_heads"], head_dim,
            rope[kinds[i]], windows[kinds[i]], dtype, prefix="attn_")
        ffs = {
            "dense": lambda: DenseMLP(hidden, cfg["intermediate_size"],
                                      dtype, prefix="mlp_"),
            "sparse": lambda: SharedSparseExperts(
                hidden, cfg["moe_intermediate_size"],
                cfg["shared_expert_intermediate_size"], held, router, dtype,
                prefix="moe_")}
        for i in range(depth):
            if kinds[i] not in windows or mlps[i] not in ffs:
                raise MXNetError(f"laguna: layer {i} of kind "
                                 f"{kinds[i]!r} / {mlps[i]!r}")
        self.held = held
        self._build(cfg["vocab_size"], hidden, eps, experts, [
            lambda prefix, i=i: DecoderLayer(
                attn(i), ffs[mlps[i]], mlps[i] == "sparse", hidden, eps,
                dtype, prefix=prefix)
            for i in range(depth)], dtype)


def laguna(config, held=None, dtype="bfloat16", **kwargs):
    """The decoder of ``config`` (a dict, or the path of a JSON file with
    the published keys); ``held = (first, count)`` is this chip's share of
    the experts, by default ``(0, num_experts)``."""
    if isinstance(config, str):
        import json
        with open(config) as f:
            config = json.load(f)
    return Laguna(config, held=held, dtype=dtype, **kwargs)
