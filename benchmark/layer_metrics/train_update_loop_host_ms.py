"""Host time inside ``trainer.update`` spans, per step of the traced
slice, in ms: ``Trainer._update``'s loop over the parameters, one call
into the optimizer's updater each."""
from benchmark.lib import spans


def read(ctx):
    return spans.mean_ms(ctx["planes"], "trainer.update")
