"""How long the loop's thread stood blocked inside ``block.call`` and
``autograd.pullback`` spans, length less CPU time: the forward's and
the backward's launches waiting for the device or for buffers. The
yardstick of a bound on the steps in flight. Mean over the whole steps
of the untraced window that the ring holds, in ms."""
from benchmark.lib import ring

SPANS = ("block.call", "autograd.pullback")


def read(ctx):
    held = ring.steps(ctx["run"])
    if held is None:
        return None
    found = [v for v in map(held.blocked_ms, SPANS) if v is not None]
    return sum(found) if found else None
