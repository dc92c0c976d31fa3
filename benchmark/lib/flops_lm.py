"""Operations of a decoder-only language model's training step, from the
configuration's own shapes: 2 FLOPs per multiply-accumulate, forward x 3
(the backward pass costs twice the forward; recomputation does not count).
Products only: norms, rotations, activations, the short convolution's
elementwise part and the softmax are not counted."""


def head_dim(cfg):
    return cfg.get("head_dim") or \
        cfg["hidden_size"] // cfg["num_attention_heads"]


def expert_macs(cfg):
    """Multiply-accumulates of one visit to one expert."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_macs(cfg):
    """Multiply-accumulates per token of one forward pass, attention's
    score and value products aside. An expert layer counts its router
    over all the published experts and the EXPECTED visits to the experts
    held here: ``num_experts_per_tok * held / published``."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    routed = cfg.get("published_num_experts", cfg["num_experts"])
    held = (cfg.get("held") or (0, cfg["num_experts"]))[1]
    total = d * cfg["vocab_size"]                       # the tied head
    for i, kind in enumerate(cfg["layer_types"]):
        if kind == "conv":
            total += d * 3 * d + d * d + cfg["conv_L_cache"] * d
        else:
            total += 2 * d * h * hd + 2 * d * kv * hd
        if i < cfg["num_dense_layers"]:
            total += 3 * d * cfg["intermediate_size"]
        else:
            visits = cfg["num_experts_per_tok"] * held / routed
            total += d * routed + visits * expert_macs(cfg)
    return total


def attention_macs(cfg, seq):
    """Score and value products of ONE causal attention layer over one
    sequence: token i sees i positions."""
    return 2 * cfg["num_attention_heads"] * head_dim(cfg) * \
        seq * (seq + 1) // 2


def attention_fwd_flops(cfg, batch, seq):
    """Forward FLOPs of one attention layer's score and value products."""
    return 2 * batch * attention_macs(cfg, seq)


def train_flops(cfg, batch, seq):
    """FLOPs of one training step over ``batch`` sequences of ``seq``."""
    n_attn = sum(k == "full_attention" for k in cfg["layer_types"])
    macs = batch * (seq * token_macs(cfg) +
                    n_attn * attention_macs(cfg, seq))
    return 3 * 2 * macs
