"""The flash-attention backward kernel's share of the chip's bf16 peak in
the DeepSeek-V3 decoder's traced slice. Its events are named
``_flash_bwd_call[.N]``; every one is one latent-attention layer's
backward over the step's whole batch, and its work is five products over
every causal pair at their true widths: the scores again, ``dk`` and
``dq`` at 192 lanes, ``dp`` and ``dv`` at 128, ``2 x pairs x heads x (3 x
192 + 2 x 128)`` (``lib/flops_moonlight.attention_bwd_flops``), whatever a
kernel pads the lanes to, so it cannot pass 100. Read only where the
driver's digest holds the scope ``moonlight.attn.mla``: a run without it
(the parent's, another model's) gives nothing."""
from benchmark.lib import flops_moonlight, xplane

KERNEL = "_flash_bwd_call"
SCOPE = "moonlight.attn.mla"


def read(ctx):
    run = ctx["run"]
    if not (run.get("scope_events") or {}).get(SCOPE) or not run.get("seq"):
        return None
    w0, w1 = xplane.window_of(ctx["planes"])
    ops = xplane.device_ops(ctx["planes"])
    first = ops.get(sorted(ops)[0], []) if ops else []
    events = [(s, e) for n, s, e, _ in first
              if n.startswith(KERNEL) and s >= w0 and e <= w1]
    if not events:
        return None
    flops = len(events) * flops_moonlight.attention_bwd_flops(
        ctx["cfg"], run["batch"], run["seq"])
    seconds = sum(e - s for s, e in events) / 1e9
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / seconds
