"""Device time of the expert layers per step of the traced slice, in ms:
the operations under the named scopes ``lfm2.moe.route`` and
``lfm2.moe.experts`` (forward and backward programs alike) and the
compiler's own ``ragged-dot`` kernels, which carry no scope."""
from benchmark.lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, ("lfm2.moe.route", "lfm2.moe.experts"),
                           names=("ragged-dot",))
