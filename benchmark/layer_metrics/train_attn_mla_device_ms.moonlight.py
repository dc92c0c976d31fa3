"""Device time of the DeepSeek-V3 decoder's latent attention per step of the
traced slice, in ms: the operations under the named scope
``moonlight.attn.mla`` (the norm, the three projections, the latent norm,
the rotations, ``o_proj``; forward, recomputed and backward programs
alike) and the flash kernels' own events (``_flash_call``,
``_flash_bwd_call``: every one in this model is latent attention's); the
union of their intervals, over all the layers. A run without the driver's
digest of the scopes (the parent's) gives nothing."""
from benchmark.lib import scopes

SCOPE = "moonlight.attn.mla"


def read(ctx):
    if not (ctx["run"].get("scope_events") or {}).get(SCOPE):
        return None
    return scopes.scope_ms(ctx, (SCOPE,),
                           names=("_flash_call", "_flash_bwd_call"))
