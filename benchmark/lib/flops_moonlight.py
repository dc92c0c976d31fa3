"""Operations of the DeepSeek-V3 decoder's training step (Moonlight-16B-A3B),
from the configuration's own shapes, by the rules of the other
language-model counts: 2 FLOPs per multiply-accumulate, forward x 3 (the
backward pass costs twice the forward; recomputation does not count).
Products only: norms, rotations, the gated products, the softmaxes are not
counted. Per layer: latent attention's four projections (``q_proj``,
``kv_a_proj_with_mqa``, ``kv_b_proj`` from the latent, ``o_proj``); the
dense MLP, or the router over all the published experts, the EXPECTED
visits to the experts held here (``num_experts_per_tok * held /
published``) and the shared experts. Attention's score and value products
are counted pair by pair over every causal pair, at their true widths: the
scores over ``qk_nope_head_dim + qk_rope_head_dim`` lanes, the values over
``v_head_dim``, whatever a kernel pads them to."""
from benchmark.lib.flops_smallthinker import seen_pairs
from benchmark.reference.deepseek_v3 import routes


def widths(cfg):
    """(score lanes, value lanes) of a head."""
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]


def attention_macs(cfg, seq):
    """Score and value products of ONE layer over one sequence."""
    qk, v = widths(cfg)
    return cfg["num_attention_heads"] * (qk + v) * seen_pairs(seq)


def attention_fwd_flops(cfg, batch, seq):
    """The forward kernel of one layer over a batch: ``q k^T`` and ``p
    v``."""
    return 2 * batch * attention_macs(cfg, seq)


def attention_bwd_flops(cfg, batch, seq):
    """The backward kernel of one layer over a batch: the scores again,
    ``dp = do v^T``, ``dv = p^T do``, ``dk = ds^T q`` and ``dq = ds k``:
    three products at the score width and two at the value width."""
    qk, v = widths(cfg)
    return 2 * batch * cfg["num_attention_heads"] * (3 * qk + 2 * v) * \
        seen_pairs(seq)


def projection_macs(cfg):
    """Latent attention's four projections per token."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qk, v = widths(cfg)
    r, dr = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return d * h * qk + d * (r + dr) + \
        r * h * (cfg["qk_nope_head_dim"] + v) + h * v * d


def ff_macs(cfg, i):
    """Layer ``i``'s feed-forward part per token."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    if not routes(cfg, i):
        return 3 * d * cfg["intermediate_size"]
    routed = cfg.get("published_num_experts", cfg["n_routed_experts"])
    held = (cfg.get("held") or (0, cfg["n_routed_experts"]))[1]
    visits = cfg["num_experts_per_tok"] * held / routed
    return d * routed + visits * 3 * d * f + \
        3 * d * cfg.get("n_shared_experts", 1) * f


def token_macs(cfg):
    """Multiply-accumulates per token of one forward pass, attention's
    score and value products aside."""
    layers = sum(projection_macs(cfg) + ff_macs(cfg, i)
                 for i in range(cfg["num_hidden_layers"]))
    return cfg["hidden_size"] * cfg["vocab_size"] + layers


def train_flops(cfg, batch, seq):
    """FLOPs of one training step over ``batch`` sequences of ``seq``."""
    macs = batch * (seq * token_macs(cfg) +
                    cfg["num_hidden_layers"] * attention_macs(cfg, seq))
    return 3 * 2 * macs


def parameters(cfg):
    """Trainable parameters of the cut (the routers' selection bias and
    the visit counter are buffers)."""
    d = cfg["hidden_size"]
    held = (cfg.get("held") or (0, cfg["n_routed_experts"]))[1]
    f, r = cfg["moe_intermediate_size"], cfg["kv_lora_rank"]
    routed = cfg.get("published_num_experts", cfg["n_routed_experts"])
    attn = projection_macs(cfg) + r + 2 * d       # the three norms
    total = 2 * cfg["vocab_size"] * d + d
    for i in range(cfg["num_hidden_layers"]):
        total += attn + ff_macs(cfg, i) if not routes(cfg, i) else \
            attn + held * 3 * d * f + d * routed + \
            3 * d * cfg.get("n_shared_experts", 1) * f
    return total
