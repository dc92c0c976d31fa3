"""How long the loop's thread stood blocked inside ``trainer.health``
spans, length less CPU time: the one read-back of the previous step's
table, which is the loop's back-pressure. Mean over the whole steps of
the untraced window that the ring holds, in ms."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    return held and held.blocked_ms("trainer.health")
