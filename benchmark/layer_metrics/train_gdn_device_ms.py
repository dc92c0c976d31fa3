"""Device time of the gated-delta-rule mixers per step of the traced
slice, in ms: the operations under the named scopes ``qwen3next.gdn.proj``,
``.conv``, ``.rule`` and ``.out`` (forward and backward programs alike);
the union of their intervals, so nothing counts twice."""
from benchmark.lib import scopes

SCOPES = ("qwen3next.gdn.proj", "qwen3next.gdn.conv", "qwen3next.gdn.rule",
          "qwen3next.gdn.out")


def read(ctx):
    return scopes.scope_ms(ctx, SCOPES)
