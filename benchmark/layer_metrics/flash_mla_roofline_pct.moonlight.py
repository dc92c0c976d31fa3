"""The flash-attention forward kernel's share of the chip's bf16 peak in
the DeepSeek-V3 decoder's traced slice, where latent attention issues it
with scores over 192 lanes (128 without position, 64 rotary) and values
over 128. Its events are named ``_flash_call[.N]`` and are taken where
they run under the scope ``moonlight.attn.mla`` (``lib/attn_kinds``);
every one is one layer's forward over the step's whole batch, and its work
is that layer's score and value products at their true widths,
``2 x pairs x heads x (192 + 128)`` over every causal pair
(``lib/flops_moonlight.attention_fwd_flops``), whatever a kernel pads the
lanes to, so it cannot pass 100. A run without the driver's digest of the
scope (the parent's, another model's) gives nothing."""
from benchmark.lib import attn_kinds, flops_moonlight

SCOPES = {"mla": "moonlight.attn.mla"}


def read(ctx):
    run = ctx["run"]
    events = (attn_kinds.kernel_events(ctx, SCOPES) or {}).get("mla")
    if not events or not run.get("seq"):
        return None
    flops = len(events) * flops_moonlight.attention_fwd_flops(
        ctx["cfg"], run["batch"], run["seq"])
    seconds = sum(e - s for s, e in events) / 1e9
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / seconds
