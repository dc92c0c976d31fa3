"""Device-to-host reads inside ``trainer.health`` spans per step of the
traced slice: the host events named ``np.asarray(jax.Array)``, which
jaxlib writes round ``ArrayImpl._value`` for every ``float()``,
``int()``, ``jax.device_get`` or ``np.asarray`` of a device array, on
the thread that holds the span. One for each device scalar the health
plane reads by itself; 1 where a step's results come back as one
table."""
from benchmark.lib import spans

SPAN = "trainer.health"
READ = "np.asarray(jax.Array)"


def read(ctx):
    lines = spans.host_lines(ctx["planes"])
    n = len(spans.named(lines, spans.STEP_SPAN))
    if not n or not spans.named(lines, SPAN):
        return None
    count = 0
    for line in lines:
        span_end = -1
        for name, _, e in line:
            if name == SPAN:
                span_end = max(span_end, e)
            elif name == READ:
                count += e <= span_end
    return count / n
