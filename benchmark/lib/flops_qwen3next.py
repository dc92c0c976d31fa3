"""Operations and bytes of the Qwen3-Next decoder's training step, from the
configuration's own shapes: 2 FLOPs per multiply-accumulate, forward x 3
(the backward pass costs twice the forward; recomputation does not count).
Products only: norms, rotations, activations, gates, the short
convolution's elementwise part and the softmaxes are not counted."""


def kinds(cfg):
    interval = cfg.get("full_attention_interval", 4)
    return cfg.get("layer_types") or [
        "full_attention" if (i + 1) % interval == 0 else "linear_attention"
        for i in range(cfg["num_hidden_layers"])]


def rule_layers(cfg):
    return sum(k == "linear_attention" for k in kinds(cfg))


def rule_macs(cfg):
    """The recurrence's multiply-accumulates per token of one layer: per
    value head the read ``S^T k``, the write ``k d^T`` and the output
    ``S^T q``, each dk x dv."""
    return 3 * cfg["linear_num_value_heads"] * \
        cfg["linear_key_head_dim"] * cfg["linear_value_head_dim"]


def rule_bytes(cfg, itemsize=2):
    """Bytes per token of one layer's recurrence run once: q, k, v read
    and o written in the storage type, g and beta read in float32."""
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return itemsize * (2 * keys + 2 * values) + \
        2 * 4 * cfg["linear_num_value_heads"]


def expert_layer_macs(cfg):
    """One expert layer per token: the router over all the published
    experts, the EXPECTED visits to the experts held here
    (``num_experts_per_tok * held / published``), the shared expert and
    its gate."""
    d = cfg["hidden_size"]
    routed = cfg.get("published_num_experts", cfg["num_experts"])
    held = (cfg.get("held") or (0, cfg["num_experts"]))[1]
    visits = cfg["num_experts_per_tok"] * held / routed
    return d * routed + visits * 3 * d * cfg["moe_intermediate_size"] + \
        3 * d * cfg["shared_expert_intermediate_size"] + d


def token_macs(cfg):
    """Multiply-accumulates per token of one forward pass, attention's
    score and value products aside."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hv = cfg["linear_num_value_heads"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = hv * cfg["linear_value_head_dim"]
    total = d * cfg["vocab_size"]                       # the untied head
    for kind in kinds(cfg):
        if kind == "linear_attention":
            total += d * (2 * keys + 2 * values) + d * 2 * hv + \
                cfg["linear_conv_kernel_dim"] * (2 * keys + values) + \
                rule_macs(cfg) + values * d
        else:
            total += d * 2 * h * hd + 2 * d * kv * hd + h * hd * d
        total += expert_layer_macs(cfg)
    return total


def attention_macs(cfg, seq):
    """Score and value products of ONE causal attention layer over one
    sequence: token i sees i positions."""
    return 2 * cfg["num_attention_heads"] * cfg["head_dim"] * \
        seq * (seq + 1) // 2


def train_flops(cfg, batch, seq):
    """FLOPs of one training step over ``batch`` sequences of ``seq``."""
    n_attn = sum(k == "full_attention" for k in kinds(cfg))
    macs = batch * (seq * token_macs(cfg) +
                    n_attn * attention_macs(cfg, seq))
    return 3 * 2 * macs


def rule_train_work(cfg, tokens):
    """(FLOPs, bytes) of ONE rule layer's recurrence over ``tokens``
    tokens of a training step: three times one forward pass, however often
    a program recomputes: a lower bound of what any implementation does."""
    return 3 * 2 * tokens * rule_macs(cfg), 3 * tokens * rule_bytes(cfg)
