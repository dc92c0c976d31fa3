"""The DeepSeek-V3 decoder's (Moonlight-16B-A3B's) whole training step as a
share of the chip's bf16 peak: model FLOPs of one sequence
(``lib/flops_moonlight.train_flops``: 2 per multiply-accumulate, forward x
3, latent attention's four projections, the dense MLP, the router over all
the published experts, the held experts at their expected visits, the
shared experts, the score and value products over every causal pair at
their own widths, 192 and 128 lanes) times sequences per second of the
window. In a traced run the rate is that of the part before the traced
slice."""
from benchmark.lib import flops_moonlight


def read(ctx):
    rate = ctx["summary"]["end_to_end"].get("train_img_per_s")
    seq = ctx["run"].get("seq")
    if not rate or not seq:
        return None
    per_sequence = flops_moonlight.train_flops(ctx["cfg"], 1, seq)
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * per_sequence * rate / peak
