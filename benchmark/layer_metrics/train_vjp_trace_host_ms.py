"""Host time inside ``autograd.vjp`` spans, per step of the traced
slice, in ms: ``jax.vjp`` over the replayed tape, which traces the
forward anew every step and dispatches its linearised program."""
from benchmark.lib import spans


def read(ctx):
    return spans.mean_ms(ctx["planes"], "autograd.vjp")
