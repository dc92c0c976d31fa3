"""A traced run's attention events by the kind of layer that issued them,
for a model whose layers of each kind run under a named scope of their
own: ``scopes`` maps a kind (``window``, ``full``) to its scope, read from
the driver's digest (``run["scope_events"]``). ``lib/attn_events`` is the
same reading with SmallThinker's scopes written in (ROADMAP A5: fold it
into this one).

The forward kernel's events are found by the instruction's name
(``_flash_call[.N]``) and given a kind by the scope they run under: the
scoped operation that starts last at or before the kernel's start is the
kernel itself where a capture gives it its scope, and otherwise one of
the same layer's (its projections and rotations run just before it).
A run without the digest or without these scopes (the parent's, another
model's) gives nothing."""
import bisect

from benchmark.lib import spans, xplane

KERNEL = "_flash_call"
LAGUNA = {"window": "laguna.attn.window", "full": "laguna.attn.full"}


def scoped(ctx, scopes):
    """{kind: sorted [(start_ns, end_ns)]} or None."""
    events = ctx["run"].get("scope_events")
    if not events or not any(events.get(s) for s in scopes.values()):
        return None
    return {kind: sorted(events.get(s, ())) for kind, s in scopes.items()}


def kernel_events(ctx, scopes):
    """{kind: [(start_ns, end_ns)]} of the forward kernel's events inside
    the traced window, or None."""
    by_kind = scoped(ctx, scopes)
    if by_kind is None:
        return None
    w0, w1 = xplane.window_of(ctx["planes"])
    ops = xplane.device_ops(ctx["planes"])
    first = ops.get(sorted(ops)[0], []) if ops else []
    starts = {kind: [s for s, _ in evs] for kind, evs in by_kind.items()}
    out = {kind: [] for kind in scopes}
    for name, s, e, _ in first:
        if not name.startswith(KERNEL) or s < w0 or e > w1:
            continue
        # the latest scoped start at or before the kernel's (a
        # nanosecond's room: the two readers round picoseconds apart)
        last = {}
        for kind, st in starts.items():
            i = bisect.bisect_right(st, s + 1)
            if i:
                last[kind] = st[i - 1]
        if last:
            out[max(last, key=last.get)].append((s, e))
    return out


def kind_ms(ctx, scopes, kind):
    """Device time per step of the traced slice, in ms, of the operations
    under the scope of ``kind`` with that kind's kernel events: the
    union, so nothing counts twice."""
    by_kind = scoped(ctx, scopes)
    planes = ctx["planes"]
    steps = len(spans.named(spans.host_lines(planes), spans.STEP_SPAN))
    if by_kind is None or not steps:
        return None
    w0, w1 = xplane.window_of(planes)
    every = by_kind[kind] + kernel_events(ctx, scopes)[kind]
    clipped = [(max(s, w0), min(e, w1)) for s, e in every
               if e > w0 and s < w1]
    if not clipped:
        return None
    return xplane.union_ns(clipped) / steps / 1e6
