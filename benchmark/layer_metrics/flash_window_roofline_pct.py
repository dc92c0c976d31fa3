"""The flash-attention forward kernel's share of the chip's bf16 peak in
the traced slice, where layers of two kinds issue it. Its events are named
``_flash_call[.N]``; every one is one attention layer's forward over the
step's whole batch, and its work is that layer's score and value products
(``lib/flops_smallthinker.attention_fwd_flops``): every causal pair where
the layer that issued it is full, the banded pairs (a query and the last
``sliding_window_size`` keys up to itself) where it is windowed; the kind
comes from the scope the event runs under (``lib/attn_events``). It counts
the pairs the mathematics needs, whatever blocks a kernel visits to cover
them, so it reads the same work whatever implements the kernel and cannot
pass 100. Compute bounds it: q, k, v and the output are read and written
once."""
from benchmark.lib import attn_events, flops_smallthinker


def read(ctx):
    run, cfg = ctx["run"], ctx["cfg"]
    events = attn_events.kernel_events(ctx)
    if not events or not run.get("seq") or not any(events.values()):
        return None
    window = {"window": cfg["sliding_window_size"], "full": 0}
    flops = sum(len(evs) * flops_smallthinker.attention_fwd_flops(
        cfg, run["batch"], run["seq"], window[kind])
        for kind, evs in events.items())
    seconds = sum(e - s for evs in events.values() for s, e in evs) / 1e9
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / seconds
