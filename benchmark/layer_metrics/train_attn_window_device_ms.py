"""Device time of the window-attention layers per step of the traced
slice, in ms: the operations under the named scope
``smallthinker.attn.window`` (norm, projections, rotations, the forward
kernel, the banded backward; forward and backward programs alike); the
union of their intervals, over all such layers. Per layer it should sit
well under ``train_attn_full_device_ms``: at 8,192 tokens a window of 4,096
keeps three quarters of the causal pairs."""
from benchmark.lib import attn_events


def read(ctx):
    return attn_events.kind_ms(ctx, "window")
