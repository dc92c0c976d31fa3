"""Host time inside gluon's ``block.call`` spans (the outermost
hybridized call: parameter walk, signature, cache lookup, dispatch of
the forward program, tape record, aux write-back), summed over a step
of the traced slice, in ms."""
from benchmark.lib import spans


def read(ctx):
    return spans.mean_ms(ctx["planes"], "block.call")
