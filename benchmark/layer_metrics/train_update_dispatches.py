"""Jitted calls dispatched inside ``trainer.update`` per step of the
traced slice: one per trainable parameter while the optimizer updates
them one by one."""
from benchmark.lib import spans


def read(ctx):
    return spans.dispatches_per_step(ctx["planes"], "trainer.update")
