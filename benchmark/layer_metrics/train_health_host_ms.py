"""Host time inside ``trainer.health`` spans, per step of the traced
slice, in ms: what the default-on health plane adds to a step (the
probe's program over every weight and gradient, and the step
boundary)."""
from benchmark.lib import spans


def read(ctx):
    return spans.mean_ms(ctx["planes"], "trainer.health")
