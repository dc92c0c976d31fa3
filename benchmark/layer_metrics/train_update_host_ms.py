"""Host time inside ``Trainer.step``, mean per step of the window, in
ms: one jitted optimizer call per parameter."""
from benchmark.lib import stats


def read(ctx):
    run = ctx["run"]
    return stats.mean_span_ms(run["spans_ns"]["update"], run["w0_ns"],
                              run["w1_ns"])
