"""The program's own spans, read out of a capture.

``mxnet_tpu.tracing.Span`` mirrors every open span onto the profiler's
timeline as a TraceMe of the same name (``block.call``,
``autograd.vjp``, ``trainer.update`` ...), on the thread that opened it,
beside the ``PjitFunction(<fn>)`` events jaxlib writes for each jitted
call. The helpers here work on ``ctx["planes"]`` as ``lib/xplane.load``
returns it, count a step per ``trainer_step`` span, and take only what
lies wholly inside ``bench.window``. A capture of a program that has no
such spans gives ``None`` everywhere, and the reader leaves its metric
out.

As seen in this benchmark's captures: jaxlib writes ``PjitFunction(f)``
for a jitted call, and may write it again inside the first for the same
call, so a dispatch is an *outermost* ``PjitFunction(`` event. On the
device plane the line ``XLA Modules`` has one event per executed
program, named ``jit_<fn>(<fingerprint>)``. ``jax.vjp`` hands a jitted
function's name on to the linearised and the transposed program, so
gluon's three programs of a step share ``jit_mx_<block>_train`` and
differ by fingerprint alone: they are timed together and counted.
"""
from benchmark.lib import xplane

STEP_SPAN = "trainer_step"
MODULES_LINE = "XLA Modules"
DISPATCH = "PjitFunction("


def host_lines(planes):
    """[[(name, start_ns, end_ns)]]: per host thread, the events that
    lie wholly inside the window, an outer event before those inside
    it."""
    w0, w1 = xplane.window_of(planes)
    out = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            evs = sorted((s, -e, n) for n, s, e, _ in line["events"]
                         if s >= w0 and e <= w1 and n != xplane.WINDOW_SPAN)
            if evs:
                out.append([(n, s, -e) for s, e, n in evs])
    return out


def named(lines, name):
    """Every event called ``name`` in ``host_lines``' threads."""
    return [ev for line in lines for ev in line if ev[0] == name]


def mean_ms(planes, name):
    """Summed length of the ``name`` spans over the steps of the slice,
    in ms; None where the capture holds no step or no such span."""
    lines = host_lines(planes)
    n, evs = len(named(lines, STEP_SPAN)), named(lines, name)
    if not n or not evs:
        return None
    return sum(e - s for _, s, e in evs) / n / 1e6


def self_ms(planes, name, children):
    """``mean_ms`` of the ``name`` spans less that of the ``children``
    spans, which the program opens only inside them: what the layer
    spends itself. None where ``name`` is not in the capture."""
    whole = mean_ms(planes, name)
    if whole is None:
        return None
    return whole - sum(mean_ms(planes, c) or 0.0 for c in children)


def dispatches_per_step(planes, span):
    """Jitted calls dispatched inside the ``span`` spans, per step: the
    ``PjitFunction(`` events that lie inside a ``span`` event and inside
    no other ``PjitFunction(``."""
    lines = host_lines(planes)
    n = len(named(lines, STEP_SPAN))
    if not n or not named(lines, span):
        return None
    count = 0
    for line in lines:
        span_end = call_end = -1
        for name, s, e in line:
            if name == span:
                span_end = e
            elif name.startswith(DISPATCH) and s >= call_end:
                call_end = e
                count += e <= span_end
    return count / n


def module_events(planes, prefix):
    """[(start_ns, end_ns)] of the first device's executed programs
    named ``prefix...`` inside the window."""
    w0, w1 = xplane.window_of(planes)
    for plane in sorted(planes, key=lambda p: p["name"]):
        if not plane["name"].startswith(xplane.DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE:
                return [(s, e) for n, s, e, _ in line["events"]
                        if n.startswith(prefix) and s >= w0 and e <= w1]
    return []


def module_ms(planes, prefix):
    """Device time of the programs named ``prefix...`` per step, ms."""
    n = len(named(host_lines(planes), STEP_SPAN))
    evs = module_events(planes, prefix)
    if not n or not evs:
        return None
    return sum(e - s for s, e in evs) / n / 1e6


def modules_per_step(planes, prefix):
    """Executions of the programs named ``prefix...`` per step."""
    n = len(named(host_lines(planes), STEP_SPAN))
    evs = module_events(planes, prefix)
    if not n or not evs:
        return None
    return len(evs) / n
