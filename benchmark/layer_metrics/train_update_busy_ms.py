"""CPU time of the loop's thread inside ``trainer.update`` spans (the
walk over the leaves, the one optimizer dispatch, the probe's rows).
Mean over the whole steps of the untraced window that the ring holds,
in ms."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    return held and held.busy_ms("trainer.update")
