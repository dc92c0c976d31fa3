"""The flash-attention forward kernel's share of the chip's bf16 peak in
the traced slice. Its events are named ``_flash_call[.N]`` after the jitted
wrapper (``ops/pallas_kernels._flash_call``); every one is one attention
layer's forward over the step's whole batch, and its work is the causal
score and value products (``lib/flops_lm.attention_fwd_flops``: token i
sees i positions, 2 FLOPs per multiply-accumulate), at the configuration's
head width whatever the kernel pads it to. Compute bounds it: q, k, v and
the output are read and written once."""
from benchmark.lib import flops_lm, xplane

KERNEL = "_flash_call"


def read(ctx):
    run = ctx["run"]
    w0, w1 = xplane.window_of(ctx["planes"])
    events = [(s, e) for evs in xplane.device_ops(ctx["planes"]).values()
              for n, s, e, _ in evs
              if n.startswith(KERNEL) and s >= w0 and e <= w1]
    if not events or not run.get("seq"):
        return None
    flops = len(events) * flops_lm.attention_fwd_flops(
        ctx["cfg"], run["batch"], run["seq"])
    seconds = sum(e - s for s, e in events) / 1e9
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / seconds
