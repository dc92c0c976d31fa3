"""The host's floor for a step as a share of the step: the CPU time of
the root spans plus everything under no span, over the step period. At
100 the host bounds the step; what is left is the loop's thread blocked
inside a span, which a faster device gives back. The roots' CPU time is
never more than their length, so the share cannot pass 100. Over the
whole steps of the untraced window that the ring holds."""
from benchmark.lib import ring


def read(ctx):
    held = ring.steps(ctx["run"])
    busy = held and held.roots_busy_ms()
    if busy is None:
        return None
    return 100.0 * (busy + held.unspanned_ms()) / held.period_ms
