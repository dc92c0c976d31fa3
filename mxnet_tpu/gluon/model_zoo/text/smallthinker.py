"""SmallThinker decoder (PowerInfer/SmallThinker-21BA3B-Instruct's
``config.json``, ``model_name: smallthinker_21b_instruct``) as a gluon
HybridBlock, built from the configuration dict. Every product is without
bias.

- ``N(x; w) = w * x / sqrt(mean(x^2) + rms_norm_eps)``, statistics in
  float32, ``w`` born one.
- Layer ``i`` (0-based) on ``x``: ``u = N(x; w_in)``; ``r = u``, the
  ROUTER'S input; ``h = x + Attn_i(u)``; ``n = N(h; w_post)``; ``y = h +
  MoE(r, n)``. ``logits = W_head . N(x_L; w_f)``, float32; ``W_head`` is
  untied.
- ``Attn_i`` (``num_attention_heads`` over ``num_key_value_heads``,
  ``head_dim`` lanes): ``q = W_q u``, ``k = W_k u``, ``v = W_v u``. Where
  ``rope_layout[i] == 1``: rotary encoding, rotate-half form,
  ``rope_theta``, over all the lanes of q and k; where 0: none (such a
  layer carries no positional encoding). Query ``t`` sees key ``s`` where
  ``s <= t`` and, where ``sliding_window_layout[i] == 1``, ``s > t -
  sliding_window_size`` (``sliding_window_size`` keys, itself among
  them). ``softmax(q k^T / sqrt(head_dim)) v`` in float32, each K/V head
  serving heads / kv_heads consecutive query heads; ``Attn = W_o
  concat(o)``.
- ``MoE(r, n)``: ``l = W_r r`` over all the published experts, float32;
  ``sel = top_k(l)``, ``k = moe_num_active_primary_experts``; ``w =
  softmax(l[sel])`` (``moe_primary_router_apply_softmax`` with
  ``norm_topk_prob``: the softmax over all the experts renormalised over
  the selected is the same number; without the first key's truth the
  scores are sigmoids); ``MoE = sum over the selected experts that are
  HELD of w_e W_down,e(relu(W_gate,e n) * W_up,e n)`` at
  ``moe_ffn_hidden_size``. No shared expert, no selection bias, no token
  dropped.

``held = (first, count)`` is this chip's share of an expert-parallel
job, as in ``lfm2_moe``: the layer routes over all the experts and
computes the selected experts ``first`` ... ``first + count - 1`` only.

Read from the configuration: ``vocab_size``, ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``rope_layout``, ``rope_theta``, ``sliding_window_layout``,
``sliding_window_size``, ``moe_ffn_hidden_size``,
``moe_num_primary_experts``, ``published_num_experts`` (the router's width
where ``moe_num_primary_experts`` counts the experts held here),
``moe_num_active_primary_experts``, ``moe_primary_router_apply_softmax``,
``norm_topk_prob``, ``rms_norm_eps``. ``tie_word_embeddings`` must be
false and ``rope_scaling`` null. Assumed, the published file having no key
for it: the router reads ``u`` (the family's description: "router placed
before attention"); ReLU gates the experts ("sparse ReGLU"); no
projection bias, no q/k norm; no secondary experts, no auxiliary loss.

Training memory is ``lfm2_moe``'s: attention is rematerialised
(``gluon.utils.recompute``), the elementwise ops and the routed experts
recompute their own intermediates.

Device-side named scopes: ``smallthinker.attn.window`` (a layer whose
queries see ``sliding_window_size`` keys), ``smallthinker.attn.full``,
``smallthinker.head``; the routed part keeps ``lfm2.moe.route`` /
``lfm2.moe.experts`` (one code, one reader). The counter
``net.expert_tokens`` (int32 [layers, published experts]) is
``lfm2_moe``'s.
"""
from __future__ import annotations

import jax

from ....base import MXNetError
from ...block import HybridBlock, defer_aux_update
from ...nn import RMSNorm
from ...utils import recompute
from .lfm2_moe import SparseExperts, _dense


class Attention(HybridBlock):
    """Causal grouped-query attention; ``rotary`` rotates q and k over
    all their lanes, ``window`` > 0 bounds the keys a query sees."""

    def __init__(self, hidden, heads, kv_heads, head_dim, theta, rotary,
                 window, dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._head_dim, self._theta = head_dim, theta
        self._rotary, self._window = rotary, window
        with self.name_scope():
            get = self.params.get
            self.q_proj = get("q_proj_weight", dtype=dtype,
                              shape=(heads * head_dim, hidden))
            self.k_proj = get("k_proj_weight", dtype=dtype,
                              shape=(kv_heads * head_dim, hidden))
            self.v_proj = get("v_proj_weight", dtype=dtype,
                              shape=(kv_heads * head_dim, hidden))
            self.o_proj = get("o_proj_weight", dtype=dtype,
                              shape=(hidden, heads * head_dim))

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, o_proj):
        heads = (0, 0, -1, self._head_dim)
        q = F.reshape(_dense(F, u, q_proj), shape=heads)
        k = F.reshape(_dense(F, u, k_proj), shape=heads)
        v = F.reshape(_dense(F, u, v_proj), shape=heads)
        if self._rotary:
            q = F.RotaryEmbedding(q, theta=self._theta)
            k = F.RotaryEmbedding(k, theta=self._theta)
        o = F.GQAttention(q, k, v, causal=True, window=self._window)
        return _dense(F, F.reshape(o, shape=(0, 0, -1)), o_proj)


class DecoderLayer(HybridBlock):
    sparse = True           # every layer routes

    def __init__(self, attn, ff, hidden, eps, dtype, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        norm = lambda name: RMSNorm(eps, in_channels=hidden, dtype=dtype,
                                    prefix=name)
        with self.name_scope():
            self.input_norm = norm("input_norm_")
            self.attn = attn()
            self.post_norm = norm("post_norm_")
            self.ff = ff()
        self._scope = "smallthinker.attn." + (
            "window" if self.attn._window else "full")

    def hybrid_forward(self, F, x):
        # attention keeps its input alone and runs again in the backward
        # pass; the routed experts recompute themselves (MoEExperts)
        with jax.named_scope(self._scope):
            h = x + recompute(lambda v: self.attn(self.input_norm(v)), x)
        # the router reads the layer's normalised input, before attention
        out, counts = self.ff(self.post_norm(h), self.input_norm(x))
        return h + out, counts


class SmallThinker(HybridBlock):
    """``net(ids)`` -> float32 logits [batch, seq, vocab_size]."""

    def __init__(self, config, held=None, dtype="bfloat16", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        cfg = config
        if cfg.get("tie_word_embeddings") or cfg.get("rope_scaling"):
            raise MXNetError("smallthinker: tied embeddings and rope "
                             "scaling are not built")
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        depth = cfg["num_hidden_layers"]
        rope, windowed = cfg["rope_layout"], cfg["sliding_window_layout"]
        if len(rope) != depth or len(windowed) != depth:
            raise MXNetError(
                f"smallthinker: rope_layout / sliding_window_layout of "
                f"{len(rope)} / {len(windowed)} entries for {depth} layers")
        count = cfg["moe_num_primary_experts"]
        experts = cfg.get("published_num_experts", count)
        held = tuple(held) if held else (0, count)
        if held[0] < 0 or held[0] + held[1] > experts:
            raise MXNetError(f"smallthinker: held {held} of {experts} "
                             "experts")
        router = {"experts": experts,
                  "k": cfg["moe_num_active_primary_experts"],
                  "norm_topk": bool(cfg.get("norm_topk_prob", True)),
                  "scale": 1.0, "use_bias": False,
                  "score": "softmax" if cfg.get(
                      "moe_primary_router_apply_softmax", True)
                  else "sigmoid"}
        attn = lambda i: lambda: Attention(
            hidden, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rope_theta"]), bool(rope[i]),
            cfg["sliding_window_size"] if windowed[i] else 0, dtype,
            prefix="attn_")
        ff = lambda: SparseExperts(hidden, cfg["moe_ffn_hidden_size"], held,
                                   router, dtype, act="relu", prefix="moe_")
        self._vocab, self._hidden, self._eps = cfg["vocab_size"], hidden, eps
        self.held = held
        with self.name_scope():
            self.embed = self.params.get("embed_weight", dtype=dtype,
                                         shape=(self._vocab, hidden))
            self.norm = self.params.get("norm_gamma", shape=(hidden,),
                                        dtype=dtype, init="ones")
            self.head = self.params.get("head_weight", dtype=dtype,
                                        shape=(self._vocab, hidden))
            self.layers = []
            for i in range(depth):
                layer = DecoderLayer(attn(i), ff, hidden, eps, dtype,
                                     prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            # visits to each expert of each layer, summed on the device
            # over the forward passes so far (lfm2_moe's counter)
            self.expert_tokens = self.params.get(
                "expert_tokens", shape=(depth, experts), dtype="int32",
                init="zeros", differentiable=False)

    def hybrid_forward(self, F, ids, embed, norm, head, expert_tokens):
        x = F.Embedding(ids, embed, input_dim=self._vocab,
                        output_dim=self._hidden)
        visits = []
        for layer in self.layers:
            x, counts = layer(x)
            visits.append(counts)
        defer_aux_update(self.expert_tokens,
                         expert_tokens + F.stack(*visits, axis=0))
        with jax.named_scope("smallthinker.head"):
            return _dense(F, F.RMSNorm(x, norm, eps=self._eps), head,
                          out_dtype="float32")


def smallthinker(config, held=None, dtype="bfloat16", **kwargs):
    """The decoder of ``config`` (a dict, or the path of a JSON file with
    the published keys); ``held = (first, count)`` is this chip's share of
    the experts, by default ``(0, moe_num_primary_experts)``."""
    if isinstance(config, str):
        import json
        with open(config) as f:
            config = json.load(f)
    return SmallThinker(config, held=held, dtype=dtype, **kwargs)
