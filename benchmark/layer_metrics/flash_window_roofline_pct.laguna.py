"""The flash-attention forward kernel's share of the chip's bf16 peak in
the Laguna decoder's traced slice, where layers of two kinds, with head
counts of their own, issue it. Its events are named ``_flash_call[.N]``;
every one is one attention layer's forward over the step's whole batch,
and its work is that layer's score and value products
(``lib/flops_laguna.attention_fwd_flops``) at the head count of its kind:
every causal pair where the layer is full, the banded pairs (a query and
the last ``sliding_window`` keys up to itself) where it is windowed; the
kind comes from the scope the event runs under (``lib/attn_kinds``). It
counts the pairs the mathematics needs, whatever blocks a kernel visits
to cover them, so it reads the same work whatever implements the kernel
and cannot pass 100."""
from benchmark.lib import attn_kinds, flops_laguna


def read(ctx):
    run, cfg = ctx["run"], ctx["cfg"]
    events = attn_kinds.kernel_events(ctx, attn_kinds.LAGUNA)
    if not events or not run.get("seq") or not any(events.values()):
        return None
    flops = sum(len(evs) * flops_laguna.attention_fwd_flops(
        cfg, run["batch"], run["seq"], *flops_laguna.kind_shape(cfg, kind))
        for kind, evs in events.items() if evs)
    seconds = sum(e - s for evs in events.values() for s, e in evs) / 1e9
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / seconds
