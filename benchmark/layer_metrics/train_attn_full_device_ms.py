"""Device time of the full-attention layers per step of the traced slice,
in ms: the operations under the named scope ``smallthinker.attn.full``
(norm, projections, the forward kernel over every causal pair, the
backward scan; forward and backward programs alike); the union of their
intervals, over all such layers."""
from benchmark.lib import attn_events


def read(ctx):
    return attn_events.kind_ms(ctx, "full")
