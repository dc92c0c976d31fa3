"""The whole training step's share of the chip's bf16 peak: model FLOPs
(2 per multiply-accumulate, forward x 3, from the configuration's own
convolution and dense shapes) times images per second of the window.
In a traced run the rate is that of the part before the traced slice."""
from benchmark.lib import flops


def read(ctx):
    rate = ctx["summary"]["end_to_end"].get("train_img_per_s")
    if not rate:
        return None
    per_image = flops.resnet_v1_train_flops(ctx["cfg"], 1)
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * per_image * rate / peak
