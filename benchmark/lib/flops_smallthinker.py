"""Operations of the SmallThinker decoder's training step, from the
configuration's own shapes: 2 FLOPs per multiply-accumulate, forward x 3
(the backward pass costs twice the forward; recomputation does not count).
Products only: norms, rotations, the experts' gate, the softmaxes are not
counted. Attention's score and value products are counted pair by pair:
every causal pair in a full layer, the banded ones (a query and the last
``sliding_window_size`` keys up to itself) in a window layer."""


def windows(cfg):
    """Per layer: the keys a query sees at most, 0 for every earlier
    one."""
    return [cfg["sliding_window_size"] if on else 0
            for on in cfg["sliding_window_layout"]]


def seen_pairs(seq, window=0):
    """(query, key) pairs of one head over one sequence: token i (0-based)
    sees ``min(i + 1, window)`` keys, ``i + 1`` where ``window`` is 0."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def attention_macs(cfg, seq, window=0):
    """Score and value products of ONE attention layer over one
    sequence."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        seen_pairs(seq, window)


def attention_fwd_flops(cfg, batch, seq, window=0):
    """One forward pass of one attention layer's kernel over a batch."""
    return 2 * batch * attention_macs(cfg, seq, window)


def expert_layer_macs(cfg):
    """One expert layer per token: the router over all the published
    experts and the EXPECTED visits to the experts held here
    (``moe_num_active_primary_experts * held / published``)."""
    d = cfg["hidden_size"]
    routed = cfg.get("published_num_experts", cfg["moe_num_primary_experts"])
    held = (cfg.get("held") or (0, cfg["moe_num_primary_experts"]))[1]
    visits = cfg["moe_num_active_primary_experts"] * held / routed
    return d * routed + visits * 3 * d * cfg["moe_ffn_hidden_size"]


def token_macs(cfg):
    """Multiply-accumulates per token of one forward pass, attention's
    score and value products aside."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = d * h * hd + 2 * d * kv * hd + h * hd * d + expert_layer_macs(cfg)
    return d * cfg["vocab_size"] + cfg["num_hidden_layers"] * layer


def train_flops(cfg, batch, seq):
    """FLOPs of one training step over ``batch`` sequences of ``seq``."""
    macs = batch * (seq * token_macs(cfg) +
                    sum(attention_macs(cfg, seq, w) for w in windows(cfg)))
    return 3 * 2 * macs
