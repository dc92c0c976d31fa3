"""What a step spends under no program span: the step period less the
length of its root spans (``block.call``, ``autograd.backward``,
``trainer_step``). The driver's feed, the eager operations of the loss
where it is no hybridized block, the loss fetch every few steps and the
glue between them, working or waiting. Mean over the whole steps of the
untraced window that the ring holds, in ms."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    return held and held.unspanned_ms()
