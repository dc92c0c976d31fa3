"""The Laguna decoder's whole training step as a share of the chip's bf16
peak: model FLOPs of one sequence (``lib/flops_laguna.train_flops``: 2 per
multiply-accumulate, forward x 3, the projections at each layer's own head
count, the dense MLP, the router over all the published experts, the held
experts at their expected visits, the shared expert, attention's pairs by
layer kind: every causal pair in a full layer, the banded ones in a window
layer) times sequences per second of the window. In a traced run the rate
is that of the part before the traced slice."""
from benchmark.lib import flops_laguna


def read(ctx):
    rate = ctx["summary"]["end_to_end"].get("train_img_per_s")
    seq = ctx["run"].get("seq")
    if not rate or not seq:
        return None
    per_sequence = flops_laguna.train_flops(ctx["cfg"], 1, seq)
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * per_sequence * rate / peak
