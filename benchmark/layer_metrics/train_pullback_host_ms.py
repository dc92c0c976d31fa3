"""Host time inside ``autograd.pullback`` spans, per step of the traced
slice, in ms: the transposed programs, dispatched one by one."""
from benchmark.lib import spans


def read(ctx):
    return spans.mean_ms(ctx["planes"], "autograd.pullback")
