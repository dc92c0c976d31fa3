"""Operations of the Laguna decoder's training step, from the
configuration's own shapes, by the rules of the other language-model
counts: 2 FLOPs per multiply-accumulate, forward x 3 (the backward pass
costs twice the forward; recomputation does not count). Products only:
norms, rotations, the gates' sigmoids, the gated products, the softmaxes
are not counted. Per layer: the projections at that layer's head count
(q, k, v, the per-head gate, o); the dense MLP or the router over all the
published experts, the EXPECTED visits to the experts held here
(``num_experts_per_tok * held / published``) and the shared expert.
Attention's score and value products are counted pair by pair at the
layer's own head count: every causal pair in a full layer, the banded
ones (a query and the last ``sliding_window`` keys up to itself) in a
window layer."""
from benchmark.lib.flops_smallthinker import seen_pairs


def heads(cfg):
    return cfg.get("num_attention_heads_per_layer") or \
        [cfg["num_attention_heads"]] * cfg["num_hidden_layers"]


def windows(cfg):
    """Per layer: the keys a query sees at most, 0 for every earlier
    one."""
    return [cfg["sliding_window"] if kind == "sliding_attention" else 0
            for kind in cfg["layer_types"]]


def kind_shape(cfg, kind):
    """(heads, window) of the layers of ``kind`` (``window`` / ``full``):
    the first such layer's."""
    want = "sliding_attention" if kind == "window" else "full_attention"
    i = cfg["layer_types"].index(want)
    return heads(cfg)[i], windows(cfg)[i]


def attention_macs(cfg, seq, n_heads, window=0):
    """Score and value products of ONE attention layer of ``n_heads``
    over one sequence."""
    return 2 * n_heads * cfg["head_dim"] * seen_pairs(seq, window)


def attention_fwd_flops(cfg, batch, seq, n_heads, window=0):
    """One forward pass of one attention layer's kernel over a batch."""
    return 2 * batch * attention_macs(cfg, seq, n_heads, window)


def ff_macs(cfg, i):
    """Layer ``i``'s feed-forward part per token."""
    d = cfg["hidden_size"]
    if cfg["mlp_layer_types"][i] == "dense":
        return 3 * d * cfg["intermediate_size"]
    routed = cfg.get("published_num_experts", cfg["num_experts"])
    held = (cfg.get("held") or (0, cfg["num_experts"]))[1]
    visits = cfg["num_experts_per_tok"] * held / routed
    return d * routed + visits * 3 * d * cfg["moe_intermediate_size"] + \
        3 * d * cfg["shared_expert_intermediate_size"]


def token_macs(cfg):
    """Multiply-accumulates per token of one forward pass, attention's
    score and value products aside."""
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], \
        cfg["num_key_value_heads"]
    layers = sum(2 * d * h * hd + 2 * d * kv * hd + d * h + ff_macs(cfg, i)
                 for i, h in enumerate(heads(cfg)))
    return d * cfg["vocab_size"] + layers


def train_flops(cfg, batch, seq):
    """FLOPs of one training step over ``batch`` sequences of ``seq``."""
    macs = batch * (seq * token_macs(cfg) + sum(
        attention_macs(cfg, seq, h, w)
        for h, w in zip(heads(cfg), windows(cfg))))
    return 3 * 2 * macs
