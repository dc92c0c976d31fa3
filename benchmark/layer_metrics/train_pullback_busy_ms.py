"""CPU time of the loop's thread inside ``autograd.pullback`` spans
(the imperative remainder's transposes and one ``..._train_bwd``
dispatch per recorded call), without the launch's wait for the device
or for memory. Mean over the whole steps of the untraced window that
the ring holds, in ms."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    return held and held.busy_ms("autograd.pullback")
