"""DeepSeek-V3 decoder (``model_type: deepseek_v3``; moonshotai's
Moonlight-16B-A3B ``config.json``) as a gluon HybridBlock, built from the
configuration dict. Every product is without bias.

- ``N(x; w) = w * x / sqrt(mean(x^2) + eps)``, statistics in float32, ``w``
  born one; ``eps`` is ``rms_norm_eps`` but in the latent norm, whose eps
  is the modeling code's default 1e-6.
- Layer ``i`` on ``x``: ``u = N(x; w_in)``, ``h = x + Attn(u)``, ``n =
  N(h; w_post)``, ``y = h + FF_i(n)``. ``logits = W_head . N(x_L; w_f)``,
  float32; ``W_head`` is untied.
- ``Attn`` is latent attention (``H = num_attention_heads``, ``d_n =
  qk_nope_head_dim``, ``d_r = qk_rope_head_dim``, ``d_v = v_head_dim``,
  ``r = kv_lora_rank``): ``[q_n; q_r] = W_q u`` per head (``d_n + d_r``
  lanes); ``[c; k_r] = W_kva u`` (``r + d_r``), ``c <- N(c; w_c)``;
  ``[k_n; v] = W_kvb c`` per head (``d_n + d_v``). ``q_r`` (each head) and
  ``k_r`` (ONE head, shared by all H) are rotated at ``rope_theta``, their
  lanes paired ``(2i, 2i + 1)`` as published (``RotaryEmbedding``'s
  ``interleaved``); ``q = [q_n; q_r]``, ``k_h = [k_n,h; k_r]``; ``o =
  softmax(q k^T / sqrt(d_n + d_r)) v``, causal, float32, through the flash
  kernels at 192-lane scores over 128-lane values; ``Attn = W_o
  concat_h(o_h)``.
- ``FF_i`` for ``i < first_k_dense_replace`` (and where ``i %
  moe_layer_freq``): ``W_2(silu(W_1 n) * W_3 n)`` at ``intermediate_size``.
  Else ``s = sigmoid(W_r n)`` over all the published experts, float32;
  ``sel = top_k(s + b)`` with ``b = e_score_correction_bias`` (the
  router's ``expert_bias`` buffer) for the selection only; ``w_e = s_e /
  sum_sel s`` (``norm_topk_prob``); ``FF = S(n) + routed_scaling_factor *
  sum over the selected experts that are HELD of w_e E_e(n)``: ``E_e``
  SwiGLU blocks at ``moe_intermediate_size``, ``S`` the
  ``n_shared_experts`` shared experts as one ungated SwiGLU at
  ``n_shared_experts * moe_intermediate_size``. With ``n_group`` 1 the
  group-limited selection is the identity.

``held = (first, count)`` is this chip's share of an expert-parallel
job, as in ``laguna``: the layer routes over all the experts and computes
the selected experts ``first`` ... ``first + count - 1`` only; the shared
experts are whole here.

Read from the configuration: ``vocab_size``, ``hidden_size``,
``intermediate_size``, ``num_hidden_layers``, ``num_attention_heads``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``kv_lora_rank``, ``rope_theta``, ``rms_norm_eps``,
``first_k_dense_replace``, ``moe_layer_freq``, ``n_routed_experts``,
``published_num_experts`` (the router's width where ``n_routed_experts``
counts the experts held here), ``num_experts_per_tok``,
``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``,
``routed_scaling_factor``. Refused: a ``q_lora_rank``, ``n_group`` > 1,
``rope_scaling``, ``attention_bias``, ``tie_word_embeddings``, a
``topk_method`` other than ``noaux_tc``, a ``scoring_func`` other than
``sigmoid``, a ``hidden_act`` other than ``silu``. Not built: the
sequence-wise balance loss (``seq_aux``) and the multi-token prediction
layers (``docs/deepseek_v3.md``).

Training memory is ``laguna``'s: attention, the dense MLP and the shared
experts keep their inputs alone and run again in the backward pass
(``gluon.utils.recompute``); the routed experts recompute their own.

Device-side named scopes: ``moonlight.attn.mla`` (the norm, the three
projections, the latent norm, the rotations, both flash kernels and
``o_proj``), ``moonlight.dense_mlp``, ``moonlight.shared_expert``,
``moonlight.head``; the routed part keeps ``lfm2.moe.route`` /
``lfm2.moe.experts`` and the counter ``net.expert_tokens`` (one code, one
reader).
"""
from __future__ import annotations

from ....base import MXNetError
from ...block import HybridBlock
from .laguna import Decoder, DecoderLayer, SharedSparseExperts
from .lfm2_moe import DenseMLP, _dense

LATENT_EPS = 1e-6       # DeepseekV3RMSNorm's default, the kv_a_layernorm's


class MLAttention(HybridBlock):
    """Causal latent attention: the queries from ``q_proj``; keys and values
    from one compressed vector ``c`` a token (``kv_a_proj_with_mqa``, then
    ``kv_a_layernorm``) through ``kv_b_proj``, beside one rotary key that
    every head shares."""

    def __init__(self, hidden, heads, nope, rope, value, rank, theta, dtype,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._dims = nope, rope, value, rank
        self._heads, self._theta = heads, theta
        with self.name_scope():
            get = self.params.get
            self.q_proj = get("q_proj_weight", dtype=dtype,
                              shape=(heads * (nope + rope), hidden))
            self.kv_a_proj_with_mqa = get(
                "kv_a_proj_with_mqa_weight", dtype=dtype,
                shape=(rank + rope, hidden))
            self.kv_a_layernorm = get("kv_a_layernorm_gamma", dtype=dtype,
                                      shape=(rank,), init="ones")
            self.kv_b_proj = get("kv_b_proj_weight", dtype=dtype,
                                 shape=(heads * (nope + value), rank))
            self.o_proj = get("o_proj_weight", dtype=dtype,
                              shape=(hidden, heads * value))

    def hybrid_forward(self, F, u, q_proj, kv_a_proj_with_mqa, kv_a_layernorm,
                       kv_b_proj, o_proj):
        nope, rope, value, rank = self._dims
        rotate = lambda x: F.RotaryEmbedding(x, theta=self._theta,
                                             interleaved=True)
        part = lambda x, lo, hi=None: F.slice_axis(x, axis=-1, begin=lo,
                                                   end=hi)
        q = F.reshape(_dense(F, u, q_proj), shape=(0, 0, -1, nope + rope))
        ckr = _dense(F, u, kv_a_proj_with_mqa)
        c = F.RMSNorm(part(ckr, 0, rank), kv_a_layernorm, eps=LATENT_EPS)
        # the one rotary key: rotated once, then read by every head
        k_r = F.broadcast_axis(
            rotate(F.reshape(part(ckr, rank), shape=(0, 0, 1, rope))),
            axis=2, size=self._heads)
        kv = F.reshape(_dense(F, c, kv_b_proj),
                       shape=(0, 0, -1, nope + value))
        q = F.concat(part(q, 0, nope), rotate(part(q, nope)), dim=-1)
        k = F.concat(part(kv, 0, nope), k_r, dim=-1)
        o = F.GQAttention(q, k, part(kv, nope), causal=True)
        return _dense(F, F.reshape(o, shape=(0, 0, -1)), o_proj)


class DeepseekV3(Decoder):
    """``net(ids)`` -> float32 logits [batch, seq, vocab_size]."""

    HEAD_SCOPE = "moonlight.head"

    def __init__(self, config, held=None, dtype="bfloat16", prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        cfg = config
        refused = {
            "q_lora_rank": cfg.get("q_lora_rank") is not None,
            "n_group": cfg.get("n_group", 1) > 1,
            "rope_scaling": bool(cfg.get("rope_scaling")),
            "attention_bias": bool(cfg.get("attention_bias")),
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings")),
            "topk_method": cfg.get("topk_method", "noaux_tc") != "noaux_tc",
            "scoring_func": cfg.get("scoring_func", "sigmoid") != "sigmoid",
            "hidden_act": cfg.get("hidden_act", "silu") != "silu"}
        for key, bad in refused.items():
            if bad:
                raise MXNetError(
                    f"deepseek_v3: {key} {cfg.get(key)!r} is not built")
        hidden, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
        experts = cfg.get("published_num_experts", cfg["n_routed_experts"])
        held = tuple(held) if held else (0, cfg["n_routed_experts"])
        if held[0] < 0 or held[0] + held[1] > experts:
            raise MXNetError(f"deepseek_v3: held {held} of {experts} experts")
        width = cfg["moe_intermediate_size"]
        router = {"experts": experts, "k": cfg["num_experts_per_tok"],
                  "norm_topk": bool(cfg.get("norm_topk_prob", True)),
                  "scale": float(cfg.get("routed_scaling_factor", 1.0)),
                  "use_bias": True, "score": "sigmoid"}
        attn = lambda: MLAttention(
            hidden, cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            float(cfg["rope_theta"]), dtype, prefix="attn_")
        dense = lambda: DenseMLP(hidden, cfg["intermediate_size"], dtype,
                                 prefix="mlp_")
        sparse = lambda: SharedSparseExperts(
            hidden, width, cfg.get("n_shared_experts", 1) * width, held,
            router, dtype, scope="moonlight.shared_expert", prefix="moe_")
        self.held = held
        self._build(cfg["vocab_size"], hidden, eps, experts, [
            lambda prefix, s=self.routes(cfg, i): DecoderLayer(
                attn, sparse if s else dense, s, hidden, eps, dtype,
                attn_scope="moonlight.attn.mla",
                mlp_scope="moonlight.dense_mlp", prefix=prefix)
            for i in range(cfg["num_hidden_layers"])], dtype)

    @staticmethod
    def routes(cfg, i):
        """Does layer ``i`` route (the modeling code's rule)?"""
        return i >= cfg.get("first_k_dense_replace", 0) and \
            i % cfg.get("moe_layer_freq", 1) == 0


def deepseek_v3(config, held=None, dtype="bfloat16", **kwargs):
    """The decoder of ``config`` (a dict, or the path of a JSON file with
    the published keys); ``held = (first, count)`` is this chip's share of
    the experts, by default ``(0, n_routed_experts)``."""
    if isinstance(config, str):
        import json
        with open(config) as f:
            config = json.load(f)
    return DeepseekV3(config, held=held, dtype=dtype, **kwargs)
