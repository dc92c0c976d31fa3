"""The whole serving step's share of the chip's bf16 peak: the FLOPs the
algorithm needs for every output token emitted inside the window (its
dense products, its attention over the positions it sees, one row of
logits; token 0 carries its whole prompt's prefill) over the window's
seconds. In a traced run the window is the part before the traced
slice."""
from benchmark.lib import flops


def read(ctx):
    run, cfg = ctx["run"], ctx["cfg"]
    total = 0
    for r in run["requests"]:
        for j, t in enumerate(r["token_ns"]):
            if run["w0_ns"] <= t < run["w1_ns"]:
                total += flops.generation_flops(cfg, r["prompt_len"], j,
                                                j + 1)
    if not total:
        return None
    seconds = (run["w1_ns"] - run["w0_ns"]) / 1e9
    peak = ctx["peaks"]["bf16_flops"] * ctx["chips"]
    return 100.0 * total / seconds / peak
