"""Device time of the sequence-mixing operators per step of the traced
slice, in ms: the operations under the named scopes ``lfm2.conv`` and
``lfm2.attn`` (forward and backward programs alike), the flash kernel
``_flash_call`` among them."""
from benchmark.lib import scopes


def read(ctx):
    return scopes.scope_ms(ctx, ("lfm2.conv", "lfm2.attn"),
                           names=("_flash_call",))
