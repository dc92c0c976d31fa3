"""LFM2-MoE decoder (``model_type: lfm2_moe``; LiquidAI/LFM2-24B-A2B's
``config.json``) as a gluon HybridBlock, built from the configuration dict.

A layer is ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))``. ``Op``
is a gated short convolution (``conv``) or grouped-query attention with
per-head RMSNorm on q and k and rotary encoding (``full_attention``), as
``layer_types`` says. ``FF`` is a SwiGLU MLP in the first
``num_dense_layers`` layers and a routed expert layer after them: sigmoid
scores over ``published_num_experts`` (else ``num_experts``), the top
``num_experts_per_tok`` selected with ``expert_bias`` added for the
selection only, weights normalised over the selected, no token dropped.
The logits are ``E . RMSNorm(x)`` with the embedding ``E``, tied.

``held = (first, count)`` is this chip's share of an expert-parallel
job: the layer routes over all the experts and computes the selected
experts ``first`` … ``first + count - 1`` only; the rest of the sum lies
on other chips. Without ``held`` every expert is held.

Read from the configuration: ``vocab_size``, ``hidden_size``,
``layer_types``, ``num_dense_layers``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts``, ``published_num_experts``,
``num_experts_per_tok``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` (else hidden / heads), ``conv_L_cache``, ``norm_eps``,
``norm_topk_prob``, ``use_expert_bias``, ``routed_scaling_factor``,
``rope_parameters.rope_theta`` (else ``rope_theta``). ``conv_bias`` must
be false.

Training memory: every operator and the dense MLP is rematerialised
(``gluon.utils.recompute``), the elementwise ops and the expert layer
recompute their own intermediates, so a step keeps three hidden-wide
arrays a layer and not forty.

The device-side named scopes (``lfm2.conv``, ``lfm2.attn``,
``lfm2.moe.route``, ``lfm2.moe.experts``, ``lfm2.dense_mlp``,
``lfm2.head``) name each part's operations in a profiler capture. The
counter ``net.expert_tokens`` (a buffer, int32 [expert layers, experts])
is the visits to each expert of each expert layer, summed over the
forward passes so far on the device: a step reads nothing back for it;
``net.expert_tokens.data().asnumpy()`` does, when asked.
"""
from __future__ import annotations

import jax

from ....base import MXNetError
from ...block import HybridBlock, defer_aux_update
from ...nn import RMSNorm
from ...utils import recompute


def _dense(F, x, weight, **kw):
    return F.FullyConnected(x, weight, no_bias=True, flatten=False, **kw)


class ShortConv(HybridBlock):
    """``W_out(C * conv(B * X))`` with ``[B, C, X] = split3(W_in u)`` and a
    depthwise causal convolution of width ``conv_L_cache``."""

    def __init__(self, hidden, width, dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.in_proj = self.params.get(
                "in_proj_weight", shape=(3 * hidden, hidden), dtype=dtype)
            self.conv = self.params.get(
                "conv_weight", shape=(hidden, width), dtype=dtype)
            self.out_proj = self.params.get(
                "out_proj_weight", shape=(hidden, hidden), dtype=dtype)

    def hybrid_forward(self, F, u, in_proj, conv, out_proj):
        b, c, x = F.split(_dense(F, u, in_proj), num_outputs=3, axis=-1)
        return _dense(F, c * F.CausalConv1D(b * x, conv), out_proj)


class Attention(HybridBlock):
    """Causal grouped-query attention; q and k are normalised per head
    and rotated before the product."""

    def __init__(self, hidden, heads, kv_heads, head_dim, theta, eps, dtype,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._head_dim, self._theta, self._eps = head_dim, theta, eps
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
            self.q_norm = get("q_norm_gamma", shape=(head_dim,), dtype=dtype,
                              init="ones")
            self.k_norm = get("k_norm_gamma", shape=(head_dim,), dtype=dtype,
                              init="ones")

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, o_proj, q_norm,
                       k_norm):
        heads = (0, 0, -1, self._head_dim)
        q = F.reshape(_dense(F, u, q_proj), shape=heads)
        k = F.reshape(_dense(F, u, k_proj), shape=heads)
        v = F.reshape(_dense(F, u, v_proj), shape=heads)
        q = F.RotaryEmbedding(F.RMSNorm(q, q_norm, eps=self._eps),
                              theta=self._theta)
        k = F.RotaryEmbedding(F.RMSNorm(k, k_norm, eps=self._eps),
                              theta=self._theta)
        o = F.GQAttention(q, k, v, causal=True)
        return _dense(F, F.reshape(o, shape=(0, 0, -1)), o_proj)


class DenseMLP(HybridBlock):
    """``W2(silu(W1 n) * W3 n)``."""

    def __init__(self, hidden, width, dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.w1 = self.params.get("w1_weight", shape=(width, hidden),
                                      dtype=dtype)
            self.w3 = self.params.get("w3_weight", shape=(width, hidden),
                                      dtype=dtype)
            self.w2 = self.params.get("w2_weight", shape=(hidden, width),
                                      dtype=dtype)

    def hybrid_forward(self, F, n, w1, w3, w2):
        return _dense(F, F.SwiGLU(_dense(F, n, w1), _dense(F, n, w3)), w2)


class Router(HybridBlock):
    """Scores over all the experts and the top-k selection:
    ``(selection, gate, counts)`` of ``F.MoERoute``."""

    def __init__(self, hidden, experts, k, norm_topk, scale, use_bias, dtype,
                 score="sigmoid", prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._attrs = {"k": k, "norm_topk": norm_topk, "scale": scale,
                       "score": score}
        with self.name_scope():
            self.weight = self.params.get("weight", shape=(experts, hidden),
                                          dtype=dtype)
            # a buffer: selects, takes no gradient and no optimizer step
            self.expert_bias = self.params.get(
                "expert_bias", shape=(experts,), dtype="float32",
                init="zeros", differentiable=False) if use_bias else None

    def hybrid_forward(self, F, n, weight, expert_bias=None):
        return F.MoERoute(n, weight, expert_bias, **self._attrs)


class SparseExperts(HybridBlock):
    """The routed expert layer, this share's part of it:
    ``layer(n)`` routes and multiplies on ``n``; ``layer(n, r)`` routes on
    ``r`` (a router that reads another input than the experts') and
    multiplies on ``n``. ``act`` gates each expert's product."""

    def __init__(self, hidden, width, held, router, dtype, act="silu",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._first, count = held
        self._act = act
        with self.name_scope():
            self.router = Router(hidden, dtype=dtype, prefix="router_",
                                 **router)
            get = self.params.get
            self.w1 = get("w1_weight", shape=(count, hidden, width),
                          dtype=dtype)
            self.w3 = get("w3_weight", shape=(count, hidden, width),
                          dtype=dtype)
            self.w2 = get("w2_weight", shape=(count, width, hidden),
                          dtype=dtype)

    def hybrid_forward(self, F, n, r=None, *, w1, w3, w2):
        flat = F.reshape(n, shape=(-3, 0))
        with jax.named_scope("lfm2.moe.route"):
            sel, gate, counts = self.router(
                flat if r is None else F.reshape(r, shape=(-3, 0)))
        with jax.named_scope("lfm2.moe.experts"):
            out = F.MoEExperts(flat, sel, gate, w1, w3, w2,
                               first=self._first, act=self._act)
        return F.reshape_like(out, n), counts


class DecoderLayer(HybridBlock):
    def __init__(self, op, ff, hidden, eps, dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.operator_norm = RMSNorm(eps, in_channels=hidden, dtype=dtype,
                                         prefix="operator_norm_")
            self.op = op()
            self.ffn_norm = RMSNorm(eps, in_channels=hidden, dtype=dtype,
                                    prefix="ffn_norm_")
            self.ff = ff()
        self._op_scope = "lfm2.attn" if isinstance(self.op, Attention) \
            else "lfm2.conv"
        self.sparse = isinstance(self.ff, SparseExperts)

    def hybrid_forward(self, F, x):
        # the operator and the dense MLP keep their input alone and run
        # again in the backward pass; the expert layer's router is kept
        # (it is small) and its experts recompute themselves (MoEExperts)
        with jax.named_scope(self._op_scope):
            h = x + recompute(
                lambda v: self.op(self.operator_norm(v)), x)
        if self.sparse:
            out, counts = self.ff(self.ffn_norm(h))
            return h + out, counts
        with jax.named_scope("lfm2.dense_mlp"):
            return h + recompute(lambda v: self.ff(self.ffn_norm(v)), h)


class LFM2MoE(HybridBlock):
    """``net(ids)`` -> float32 logits [batch, seq, vocab_size]."""

    def __init__(self, config, held=None, dtype="bfloat16", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        cfg = config
        if cfg.get("conv_bias"):
            raise MXNetError("lfm2_moe: conv_bias is not built")
        hidden, eps = cfg["hidden_size"], cfg["norm_eps"]
        heads = cfg["num_attention_heads"]
        experts = cfg.get("published_num_experts", cfg["num_experts"])
        held = tuple(held) if held else (0, cfg["num_experts"])
        if held[0] < 0 or held[0] + held[1] > experts:
            raise MXNetError(f"lfm2_moe: held {held} of {experts} experts")
        rope = cfg.get("rope_parameters") or cfg
        router = {"experts": experts, "k": cfg["num_experts_per_tok"],
                  "norm_topk": bool(cfg.get("norm_topk_prob", True)),
                  "scale": float(cfg.get("routed_scaling_factor", 1.0)),
                  "use_bias": bool(cfg.get("use_expert_bias", False))}
        kinds = {
            "conv": lambda: ShortConv(hidden, cfg["conv_L_cache"], dtype,
                                      prefix="conv_"),
            "full_attention": lambda: Attention(
                hidden, heads, cfg["num_key_value_heads"],
                cfg.get("head_dim") or hidden // heads,
                float(rope["rope_theta"]), eps, dtype, prefix="attn_"),
        }
        self._vocab, self._hidden, self._eps = cfg["vocab_size"], hidden, eps
        self.held = held
        with self.name_scope():
            self.embed = self.params.get("embed_weight", dtype=dtype,
                                         shape=(self._vocab, hidden))
            self.norm = self.params.get("norm_gamma", shape=(hidden,),
                                        dtype=dtype, init="ones")
            self.layers = []
            for i, kind in enumerate(cfg["layer_types"]):
                if i < cfg["num_dense_layers"]:
                    ff = lambda: DenseMLP(hidden, cfg["intermediate_size"],
                                          dtype, prefix="mlp_")
                else:
                    ff = lambda: SparseExperts(
                        hidden, cfg["moe_intermediate_size"], held, router,
                        dtype, prefix="moe_")
                layer = DecoderLayer(kinds[kind], ff, hidden, eps, dtype,
                                     prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            n_sparse = sum(layer.sparse for layer in self.layers)
            # visits to each expert of each expert layer, summed over the
            # forward passes so far: written on the device by every call,
            # read back only by whoever asks for its data
            self.expert_tokens = self.params.get(
                "expert_tokens", shape=(n_sparse, experts), dtype="int32",
                init="zeros", differentiable=False) if n_sparse else None

    def hybrid_forward(self, F, ids, embed, norm, expert_tokens=None):
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
        with jax.named_scope("lfm2.head"):
            return _dense(F, F.RMSNorm(x, norm, eps=self._eps), embed,
                          out_dtype="float32")


def lfm2_moe(config, held=None, dtype="bfloat16", **kwargs):
    """The decoder of ``config`` (a dict, or the path of a JSON file with
    the published keys); ``held = (first, count)`` is this chip's share of
    the experts, by default ``(0, num_experts)``."""
    if isinstance(config, str):
        import json
        with open(config) as f:
            config = json.load(f)
    return LFM2MoE(config, held=held, dtype=dtype, **kwargs)
