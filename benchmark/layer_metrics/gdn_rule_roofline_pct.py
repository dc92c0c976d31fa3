"""The gated delta rule's share of its roofline in the traced slice: the
least time the chip could take for one step's recurrences, over the time
a step spends in the operations under the named scope
``qwen3next.gdn.rule`` (forward and backward programs alike; the union of
their intervals). The work is three times one forward pass of the
recurrence in every rule layer (``lib/flops_qwen3next.rule_train_work``:
q, k, v, g, beta read and o written once, 3 x dk x dv multiply-accumulates
a token and value head), however often the program recomputes and
whatever implements it: the least any implementation does, so the share
cannot pass 100. The bound is the larger of FLOPs over the bf16 peak and
bytes over the memory's speed; at the published widths the bytes bound
it."""
from benchmark.lib import flops_qwen3next, scopes

SCOPE = "qwen3next.gdn.rule"


def read(ctx):
    run, cfg, peaks = ctx["run"], ctx["cfg"], ctx["peaks"]
    ms = scopes.scope_ms(ctx, (SCOPE,))
    if not ms or not run.get("seq"):
        return None
    flops, nbytes = flops_qwen3next.rule_train_work(
        cfg, run["batch"] * run["seq"])
    least = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * flops_qwen3next.rule_layers(cfg) * least / (ms / 1e3)
