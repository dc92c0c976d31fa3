"""Host time from entering ``autograd.record()`` to the return of
``loss.backward()``, mean per step of the window, in ms: the gluon
front end re-dispatching the cached programs and autograd re-tracing
``jax.vjp``."""
from benchmark.lib import stats


def read(ctx):
    run = ctx["run"]
    return stats.mean_span_ms(run["spans_ns"]["fwd_bwd"], run["w0_ns"],
                              run["w1_ns"])
