"""Host time autograd spends itself in ``autograd.backward`` (the tape
walk, building the replay, writing the gradients back), which is the
span less ``autograd.vjp`` and ``autograd.pullback`` inside it, summed
over a step of the traced slice, in ms."""
from benchmark.lib import spans


def read(ctx):
    return spans.self_ms(ctx["planes"], "autograd.backward",
                         ("autograd.vjp", "autograd.pullback"))
