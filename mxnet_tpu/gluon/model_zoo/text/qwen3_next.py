"""Qwen3-Next decoder (``model_type: qwen3_next``;
Qwen/Qwen3-Next-80B-A3B-Instruct's ``config.json``) as a gluon HybridBlock,
built from the configuration dict. Every product is without bias.

- Norms. ``N0(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` in float32,
  ``w`` born zero: the two layer norms, the final norm and the per-head
  norms of q and k. ``Ng(o, z; w) = w * o / sqrt(mean(o^2) + eps) *
  silu(z)`` over ``linear_value_head_dim``, ``w`` born one. ``eps`` is
  ``rms_norm_eps``.
- Layer ``i``: ``h = x + Mix_i(N0(x))``, ``y = h + MoE(N0(h))``; ``Mix_i``
  is full attention where ``layer_types[i]`` says ``full_attention``
  (without the key: where ``(i + 1) % full_attention_interval == 0``),
  else the gated delta rule. ``logits = W_head . N0(x_last)``, float32;
  ``W_head`` is untied.
- Full attention (``num_attention_heads`` over ``num_key_value_heads``,
  ``head_dim`` lanes): ``[q | gate] = split per head(W_q u)``, ``k = W_k
  u``, ``v = W_v u``; ``q = N0(q; w_q)``, ``k = N0(k; w_k)`` over the
  head; rotary encoding, rotate-half form, ``rope_theta``, on the first
  ``partial_rotary_factor * head_dim`` lanes of q and k, the others
  untouched; causal ``softmax(q k^T / sqrt(head_dim)) v``, each K/V head
  serving heads / kv_heads query heads; ``Mix = W_o (concat(o) *
  sigmoid(gate))``.
- Gated delta rule (``linear_num_key_heads`` x ``linear_key_head_dim``;
  ``linear_num_value_heads`` x ``linear_value_head_dim``): ``[q, k, v, z]
  = W_qkvz u`` (in that order, each its heads side by side), ``[b, a] =
  W_ba u``. ``[q, k, v] <- silu(causal depthwise conv of width
  linear_conv_kernel_dim, no bias, over the channels of concat(q, k,
  v))``. Per value head: ``beta = sigmoid(b)``, ``g = -exp(A_log) *
  softplus(a + dt_bias)`` (float32). Then ``o = GatedDeltaRule(q, k, v,
  g, beta)`` (``ops/nn.py``: q and k L2-normalised, the recurrence ``S <-
  exp(g_t) S; d_t = beta_t (v_t - S^T k_t); S <- S + k_t d_t^T; o_t = S^T
  q_t`` in chunks of 64), and ``Mix = W_out . concat_h Ng(o_h, z_h; w)``.
- Expert layer: ``p = softmax(W_r n)`` over all the published experts in
  float32; ``sel = top_k(p)``; ``w_e = p_e / sum_{e in sel} p_e``
  (``norm_topk_prob``); ``routed = sum over the selected experts that are
  HELD of w_e W2_e(silu(W1_e n) * W3_e n)``; ``shared = sigmoid(w_sg . n)
  * W2_s(silu(W1_s n) * W3_s n)`` at ``shared_expert_intermediate_size``;
  ``MoE = routed + shared``. No token is dropped; no selection bias.

``held = (first, count)`` is this chip's share of an expert-parallel
job, as in ``lfm2_moe``: the layer routes over all the experts and
computes the selected experts ``first`` ... ``first + count - 1`` only.
The shared expert, which every chip computes alike, is whole here.

Read from the configuration: ``vocab_size``, ``hidden_size``,
``num_hidden_layers``, ``layer_types`` or ``full_attention_interval``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``partial_rotary_factor``, ``rope_theta``, ``linear_num_key_heads``,
``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``, ``linear_conv_kernel_dim``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts``, ``published_num_experts``, ``num_experts_per_tok``,
``norm_topk_prob``, ``rms_norm_eps``. ``mlp_only_layers`` must be empty
and ``decoder_sparse_step`` 1 (every layer routes). Not built: the
multi-token-prediction module and the auxiliary balancing loss.

Training memory is ``lfm2_moe``'s: every mixer and the shared expert is
rematerialised (``gluon.utils.recompute``), the elementwise ops, the rule
and the routed experts recompute their own intermediates.

Device-side named scopes: ``qwen3next.gdn.proj`` (the two input products,
the gates), ``qwen3next.gdn.conv``, ``qwen3next.gdn.rule``,
``qwen3next.gdn.out`` (gated norm and output product), ``qwen3next.attn``,
``qwen3next.shared_expert``, ``qwen3next.head``; the routed part keeps
``lfm2.moe.route`` / ``lfm2.moe.experts``. The counter
``net.expert_tokens`` (int32 [layers, published experts]) is
``lfm2_moe``'s.
"""
from __future__ import annotations

import jax

from ....base import MXNetError
from ...block import HybridBlock, defer_aux_update
from ...nn import RMSNorm
from ...utils import recompute
from .lfm2_moe import DenseMLP, SparseExperts, _dense


class GatedDeltaNet(HybridBlock):
    """The gated-delta-rule mixer of a ``linear_attention`` layer."""

    def __init__(self, hidden, key_heads, value_heads, key_dim, value_dim,
                 width, eps, dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._sizes = (key_heads * key_dim, value_heads * value_dim)
        self._dims, self._eps = (key_dim, value_dim), eps
        keys, values = self._sizes
        with self.name_scope():
            get = self.params.get
            self.in_proj_qkvz = get("in_proj_qkvz_weight", dtype=dtype,
                                    shape=(2 * keys + 2 * values, hidden))
            self.in_proj_ba = get("in_proj_ba_weight", dtype=dtype,
                                  shape=(2 * value_heads, hidden))
            self.conv = get("conv_weight", dtype=dtype,
                            shape=(2 * keys + values, width))
            self.A_log = get("A_log", shape=(value_heads,), dtype=dtype)
            self.dt_bias = get("dt_bias", shape=(value_heads,), dtype=dtype,
                               init="ones")
            self.norm = get("norm_gamma", shape=(value_dim,), dtype=dtype,
                            init="ones")
            self.out_proj = get("out_proj_weight", dtype=dtype,
                                shape=(hidden, values))

    def hybrid_forward(self, F, u, in_proj_qkvz, in_proj_ba, conv, A_log,
                       dt_bias, norm, out_proj):
        keys, values = self._sizes
        key_dim, value_dim = self._dims
        f32 = lambda x: F.cast(x, dtype="float32")
        with jax.named_scope("qwen3next.gdn.proj"):
            qkvz = _dense(F, u, in_proj_qkvz)
            qkv = F.slice_axis(qkvz, axis=-1, begin=0, end=2 * keys + values)
            z = F.slice_axis(qkvz, axis=-1, begin=2 * keys + values, end=None)
            b, a = F.split(_dense(F, u, in_proj_ba), num_outputs=2, axis=-1)
            beta = F.sigmoid(f32(b))
            g = F.broadcast_mul(
                -F.exp(f32(A_log)),
                F.Activation(F.broadcast_add(f32(a), f32(dt_bias)),
                             act_type="softrelu"))
        with jax.named_scope("qwen3next.gdn.conv"):
            qkv = F.CausalConv1D(qkv, conv, activation="silu")
        q = F.slice_axis(qkv, axis=-1, begin=0, end=keys)
        k = F.slice_axis(qkv, axis=-1, begin=keys, end=2 * keys)
        v = F.slice_axis(qkv, axis=-1, begin=2 * keys, end=None)
        with jax.named_scope("qwen3next.gdn.rule"):
            o = F.GatedDeltaRule(
                F.reshape(q, shape=(0, 0, -1, key_dim)),
                F.reshape(k, shape=(0, 0, -1, key_dim)),
                F.reshape(v, shape=(0, 0, -1, value_dim)), g, beta)
        with jax.named_scope("qwen3next.gdn.out"):
            z = F.reshape(z, shape=(0, 0, -1, value_dim))
            o = F.SwiGLU(z, F.RMSNorm(o, norm, eps=self._eps))
            return _dense(F, F.reshape(o, shape=(0, 0, -1)), out_proj)


class GatedAttention(HybridBlock):
    """Causal grouped-query attention with zero-centred per-head norms on
    q and k, rotary encoding over the first ``rotary_dim`` lanes, and a
    sigmoid gate on the output taken from the query's product."""

    def __init__(self, hidden, heads, kv_heads, head_dim, rotary_dim, theta,
                 eps, dtype, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._head_dim, self._rotary = head_dim, rotary_dim
        self._theta, self._eps = theta, eps
        with self.name_scope():
            get = self.params.get
            self.q_proj = get("q_proj_weight", dtype=dtype,
                              shape=(2 * heads * head_dim, hidden))
            self.k_proj = get("k_proj_weight", dtype=dtype,
                              shape=(kv_heads * head_dim, hidden))
            self.v_proj = get("v_proj_weight", dtype=dtype,
                              shape=(kv_heads * head_dim, hidden))
            self.o_proj = get("o_proj_weight", dtype=dtype,
                              shape=(hidden, heads * head_dim))
            self.q_norm = get("q_norm_gamma", shape=(head_dim,), dtype=dtype,
                              init="zeros")
            self.k_norm = get("k_norm_gamma", shape=(head_dim,), dtype=dtype,
                              init="zeros")

    def hybrid_forward(self, F, u, q_proj, k_proj, v_proj, o_proj, q_norm,
                       k_norm):
        d = self._head_dim
        rotate = lambda x, w: F.RotaryEmbedding(
            F.RMSNorm(x, w, eps=self._eps, zero_centered=True),
            theta=self._theta, rotary_dim=self._rotary)
        with jax.named_scope("qwen3next.attn"):
            q, gate = F.split(F.reshape(_dense(F, u, q_proj),
                                        shape=(0, 0, -1, 2 * d)),
                              num_outputs=2, axis=-1)
            k = F.reshape(_dense(F, u, k_proj), shape=(0, 0, -1, d))
            v = F.reshape(_dense(F, u, v_proj), shape=(0, 0, -1, d))
            o = F.GQAttention(rotate(q, q_norm), rotate(k, k_norm), v,
                              causal=True)
            return _dense(F, F.reshape(o * F.sigmoid(gate),
                                       shape=(0, 0, -1)), o_proj)


class SharedSparseExperts(HybridBlock):
    """``routed + shared``: ``lfm2_moe.SparseExperts`` over this share's
    experts beside the shared expert, which is whole on every chip."""

    def __init__(self, hidden, width, shared_width, held, router, dtype,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.shared_gate = self.params.get(
                "shared_gate_weight", shape=(1, hidden), dtype=dtype)
            self.routed = SparseExperts(hidden, width, held, router, dtype,
                                        prefix="routed_")
            self.shared = DenseMLP(hidden, shared_width, dtype,
                                   prefix="shared_")
        self.router = self.routed.router

    def hybrid_forward(self, F, n, shared_gate):
        out, counts = self.routed(n)
        with jax.named_scope("qwen3next.shared_expert"):
            shared = recompute(
                lambda v: F.broadcast_mul(
                    F.sigmoid(_dense(F, v, shared_gate)), self.shared(v)), n)
        return out + shared, counts


class DecoderLayer(HybridBlock):
    sparse = True           # every layer routes

    def __init__(self, mixer, ff, hidden, eps, dtype, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        norm = lambda name: RMSNorm(eps, gamma_initializer="zeros",
                                    in_channels=hidden, dtype=dtype,
                                    zero_centered=True, prefix=name)
        with self.name_scope():
            self.input_norm = norm("input_norm_")
            self.mixer = mixer()
            self.post_norm = norm("post_norm_")
            self.ff = ff()

    def hybrid_forward(self, F, x):
        # the mixer keeps its input alone and runs again in the backward
        # pass; the routed experts recompute themselves (MoEExperts)
        h = x + recompute(lambda v: self.mixer(self.input_norm(v)), x)
        out, counts = self.ff(self.post_norm(h))
        return h + out, counts


class Qwen3Next(HybridBlock):
    """``net(ids)`` -> float32 logits [batch, seq, vocab_size]."""

    def __init__(self, config, held=None, dtype="bfloat16", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        cfg = config
        if cfg.get("mlp_only_layers") or cfg.get("decoder_sparse_step", 1) != 1:
            raise MXNetError("qwen3_next: dense MLP layers are not built")
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        experts = cfg.get("published_num_experts", cfg["num_experts"])
        held = tuple(held) if held else (0, cfg["num_experts"])
        if held[0] < 0 or held[0] + held[1] > experts:
            raise MXNetError(f"qwen3_next: held {held} of {experts} experts")
        interval = cfg.get("full_attention_interval", 4)
        kinds = cfg.get("layer_types") or [
            "full_attention" if (i + 1) % interval == 0
            else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]
        head_dim = cfg["head_dim"]
        router = {"experts": experts, "k": cfg["num_experts_per_tok"],
                  "norm_topk": bool(cfg.get("norm_topk_prob", True)),
                  "scale": 1.0, "use_bias": False, "score": "softmax"}
        mixers = {
            "linear_attention": lambda: GatedDeltaNet(
                hidden, cfg["linear_num_key_heads"],
                cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                cfg["linear_value_head_dim"], cfg["linear_conv_kernel_dim"],
                eps, dtype, prefix="gdn_"),
            "full_attention": lambda: GatedAttention(
                hidden, cfg["num_attention_heads"],
                cfg["num_key_value_heads"], head_dim,
                int(head_dim * cfg.get("partial_rotary_factor", 1.0)),
                float(cfg["rope_theta"]), eps, dtype, prefix="attn_"),
        }
        ff = lambda: SharedSparseExperts(
            hidden, cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], held, router, dtype,
            prefix="moe_")
        self._vocab, self._hidden, self._eps = cfg["vocab_size"], hidden, eps
        self.held = held
        with self.name_scope():
            self.embed = self.params.get("embed_weight", dtype=dtype,
                                         shape=(self._vocab, hidden))
            self.norm = self.params.get("norm_gamma", shape=(hidden,),
                                        dtype=dtype, init="zeros")
            self.head = self.params.get("head_weight", dtype=dtype,
                                        shape=(self._vocab, hidden))
            self.layers = []
            for i, kind in enumerate(kinds):
                if kind not in mixers:
                    raise MXNetError(f"qwen3_next: layer type {kind!r}")
                layer = DecoderLayer(mixers[kind], ff, hidden, eps, dtype,
                                     prefix="layer%d_" % i)
                self.register_child(layer)
                self.layers.append(layer)
            # visits to each expert of each layer, summed on the device
            # over the forward passes so far (lfm2_moe's counter)
            self.expert_tokens = self.params.get(
                "expert_tokens", shape=(len(kinds), experts), dtype="int32",
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
        with jax.named_scope("qwen3next.head"):
            x = F.RMSNorm(x, norm, eps=self._eps, zero_centered=True)
            return _dense(F, x, head, out_dtype="float32")


def qwen3_next(config, held=None, dtype="bfloat16", **kwargs):
    """The decoder of ``config`` (a dict, or the path of a JSON file with
    the published keys); ``held = (first, count)`` is this chip's share of
    the experts, by default ``(0, num_experts)``."""
    if isinstance(config, str):
        import json
        with open(config) as f:
            config = json.load(f)
    return Qwen3Next(config, held=held, dtype=dtype, **kwargs)
