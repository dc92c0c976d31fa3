"""Device time of Adam's tree program (``jit__adam_step``) per step of the
traced slice, in ms: what ``train_update_device_ms`` reads of
SGD-with-momentum's ``jit__step_mom``."""
from benchmark.lib import spans


def read(ctx):
    return spans.module_ms(ctx["planes"], "jit__adam_step(")
