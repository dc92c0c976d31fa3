"""CPU time of the loop's thread inside gluon's ``block.call`` spans
(parameter walk, signature, cache lookup, the forward's dispatch and
the buffers it returns, tape record, aux write-back), without the
launch's wait for the device or for memory. Mean over the whole steps
of the untraced window that the ring holds, in ms."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    return held and held.busy_ms("block.call")
