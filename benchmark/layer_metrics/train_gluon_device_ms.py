"""Device time of gluon's programs (``jit_mx_<block>_<mode>``: the plain
forward, the linearised forward and the transposed program share the
name) per step of the traced slice, in ms. Every execution counts, so a
forward run twice reads twice."""
from benchmark.lib import spans


def read(ctx):
    return spans.module_ms(ctx["planes"], "jit_mx_")
