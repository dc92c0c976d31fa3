"""The program's host work a step: CPU time the training loop's thread
burnt inside its root spans (``block.call``, ``autograd.backward``,
``trainer_step``, whatever they enclose included), which leaves out
every wait for the device or for a buffer inside them. Mean over the
whole steps of the untraced window that the ring holds, in ms."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    return held and held.roots_busy_ms()
