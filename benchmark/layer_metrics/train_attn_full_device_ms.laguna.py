"""Device time of the Laguna decoder's full-attention layers per step of
the traced slice, in ms: the operations under the named scope
``laguna.attn.full`` (norm, projections, YaRN's rotations, the per-head
gate, the flash kernels forward and backward; forward and backward
programs alike) and that kind's forward kernel events; the union of their
intervals, over all such layers (``lib/attn_kinds``)."""
from benchmark.lib import attn_kinds


def read(ctx):
    return attn_kinds.kind_ms(ctx, attn_kinds.LAGUNA, "full")
