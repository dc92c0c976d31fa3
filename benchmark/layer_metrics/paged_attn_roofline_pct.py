"""The paged decode-attention kernel's share of its roofline in the
traced slice.

The kernel's events are told apart by the instruction name the jitted
wrapper gives them, ``_paged_call.N`` (``ops/pallas_kernels._paged_call``;
no ``pallas_call`` in the program passes ``name=`` yet). The least time
is bytes over the chip's memory bandwidth: K and V of every valid cached
token of every sequence in the step, per layer call, from the requests'
own positions and not from the padded table. (Its FLOPs, 4 x hidden per
cached token, are under a hundredth of the bf16 peak at that byte rate,
so memory bounds it.) Bytes are counted for the decode steps that lie
wholly inside the slice; the kernel time also holds the steps cut by its
edges, so the share errs low, never high.
"""
from benchmark.lib import flops, xplane

KERNEL = "_paged_call"


def read(ctx):
    run, cfg = ctx["run"], ctx["cfg"]
    seconds = sum(xplane.op_seconds(
        ctx["planes"], lambda name, text: name.startswith(KERNEL)).values())
    if not seconds:
        return None
    t0, t1 = run["traced_ns"]
    steps = {}
    for r in run["requests"]:
        for j, (start, end, _, _, _) in enumerate(r["steps"]):
            if j and start >= t0 and end <= t1:
                # output token j is decoded at position prompt_len + j - 1
                # and sees prompt_len + j cached tokens, itself included
                steps[start] = steps.get(start, 0) + r["prompt_len"] + j
    if not steps:
        return None
    per_layer = flops.paged_attention_bytes(
        cfg, sum(steps.values()), cfg["kv_cache_bytes_per_value"])
    least = cfg["num_hidden_layers"] * per_layer / \
        ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
