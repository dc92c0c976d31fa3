"""Device time of the optimizer's ``jit__step_mom`` programs per step of
the traced slice, in ms."""
from benchmark.lib import spans


def read(ctx):
    return spans.module_ms(ctx["planes"], "jit__step_mom(")
