"""The held experts' grouped products as a share of the chip's bf16 peak,
in the traced slice.

The products are ``lax.ragged_dot``s, which XLA:TPU turns into grouped
kernels of its own: in a capture their events are named
``ragged-dot-none[.N]`` (the product) and ``ragged-dot-metadata[.N]`` (the
group table before it); both count as the product's time. A step runs
them forward (as often as the forward runs) and, per product, twice in the
backward pass. Every such event of an expert layer multiplies that layer's
visits to held experts by one hidden x expert-width matrix, whichever way
round, so its work is 2 x visits x hidden x width; the visits are the
program's own count (the counter ``net.expert_tokens`` between the two
fences around the slice), never the expected number. Compute bounds it:
at 512 visits an expert the weights' bytes take a sixth of the products'
time at peak. Another implementation of the product is read by adding its
kernel's name to ``KERNELS``."""
from benchmark.lib import xplane

KERNELS = ("ragged-dot",)
PRODUCT = "ragged-dot-none"


def slice_visits(run, cfg):
    """(steps, visits to held experts summed over the expert layers) of
    the traced slice: between the driver's last two readings, each
    taken after a fence."""
    reads = run.get("counter_reads", ())
    if len(reads) < 2:
        return 0, 0
    (n0, c0), (n1, c1) = reads[-2:]
    first, count = cfg.get("held") or (0, cfg["num_experts"])
    visits = sum(sum(b[first:first + count]) - sum(a[first:first + count])
                 for a, b in zip(c0, c1))
    return n1 - n0, visits


def read(ctx):
    cfg = ctx["cfg"]
    w0, w1 = xplane.window_of(ctx["planes"])
    events = [(n, s, e) for evs in xplane.device_ops(ctx["planes"]).values()
              for n, s, e, _ in evs
              if n.startswith(KERNELS) and s >= w0 and e <= w1]
    products = sum(1 for n, _, _ in events if n.startswith(PRODUCT))
    steps, visits = slice_visits(ctx["run"], cfg)
    if not products or not steps or not visits:
        return None
    layers = sum(1 for i in range(len(cfg["layer_types"]))
                 if i >= cfg["num_dense_layers"])
    # every product event multiplies one layer's visits of one step
    flops = products * 2.0 * (visits / steps / layers) * \
        cfg["hidden_size"] * cfg["moe_intermediate_size"]
    seconds = sum(e - s for _, s, e in events) / 1e9
    return 100.0 * flops / ctx["peaks"]["bf16_flops"] / seconds
