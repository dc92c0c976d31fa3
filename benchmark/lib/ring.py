"""The program's own spans, read out of the ring over the untraced
window.

``mxnet_tpu.tracing`` keeps every closed span in its thread's ring
(on by default, 2,048 spans a thread) on ``CLOCK_MONOTONIC``, the clock
of the driver's ``w0_ns``, ``w1_ns`` and ``step_ends_ns``, with the id
of the span that enclosed it (``parent``) and, beside its length
``dur_ns``, the CPU time its thread burnt inside it (``cpu_ns``). A
thread that waits for the device, for a buffer or for another thread
burns none, so ``cpu_ns`` is how long the thread worked and ``dur_ns -
cpu_ns`` how long it stood blocked. The readers run in the program's
process after the window, so the ring as it stands holds the steps the
rate came from, profiler off; ``lib/spans.py`` reads the same spans'
mirrors in the capture, which has one length a span and the traced
slice alone.

A step is the interval from one ``trainer_step``'s end to the next
one's end. Only the thread that ran ``trainer_step`` is read, only
spans that lie wholly inside ``[w0_ns, w1_ns]`` (the traced slice comes
after), and only whole steps: the ring drops its oldest records, so the
first ``trainer_step`` it still holds only marks where the first whole
step starts. A root span is one whose ``parent`` is none of these spans
(``block.call``, ``autograd.backward``, ``trainer_step``: the driver
opens no program span round them) and belongs to the step it ends in.

On the machine with the chip the thread clock ticks: a record's
``cpu_ns`` is a whole number of 10 ms ticks, 0 or more than the span's
length, and right only in the mean (``tracing/clock.py``). So nothing is
read from one record: every number here is a sum over all the held
steps' spans of a kind, divided by the steps, and the CPU time of such
a sum is bounded by its length, so that busy and blocked are each
between 0 and the length. A mean over n steps of a span that is open
once a step carries a standard error of at most 5 / sqrt(n) ms.

Under ``min_steps`` whole steps, or where a record has no ``cpu_ns``
(a program from before the span took it), ``steps`` gives ``None`` and
the reader leaves its metric out.
"""
STEP_SPAN = "trainer_step"
MIN_STEPS = 20


def _end(span):
    return span["start_ns"] + span["dur_ns"]


class Steps:
    """The whole steps held and the step thread's spans that end inside
    them. Every ``*_ms`` is a sum over those spans divided by the number
    of steps: a mean per step, in ms."""

    def __init__(self, first_ns, last_ns, count, spans):
        self.count = count
        self.period_ms = (last_ns - first_ns) / count / 1e6
        self.spans = spans
        ids = {s["span"] for s in spans}
        self.roots = [s for s in spans if s["parent"] not in ids]

    def named(self, name):
        return [s for s in self.spans if s["name"] == name]

    def _wall_ms(self, spans):
        if not spans:
            return None
        return sum(s["dur_ns"] for s in spans) / self.count / 1e6

    def _busy_ms(self, spans):
        """CPU time of ``spans`` a step, bounded by their length."""
        if not spans:
            return None
        cpu = sum(s["cpu_ns"] for s in spans) / self.count / 1e6
        return min(cpu, self._wall_ms(spans))

    def wall_ms(self, name):
        return self._wall_ms(self.named(name))

    def busy_ms(self, name):
        """CPU time of the ``name`` spans' thread inside them."""
        return self._busy_ms(self.named(name))

    def blocked_ms(self, name):
        """How long the thread stood blocked inside the ``name`` spans."""
        busy = self.busy_ms(name)
        return None if busy is None else self.wall_ms(name) - busy

    def self_busy_ms(self, name):
        """``busy_ms`` less the CPU time of the spans whose ``parent``
        is a ``name`` span: what the layer burns itself."""
        whole = self.busy_ms(name)
        if whole is None:
            return None
        ids = {s["span"] for s in self.named(name)}
        kids = [s for s in self.spans if s["parent"] in ids]
        return max(whole - (self._busy_ms(kids) or 0.0), 0.0)

    def roots_busy_ms(self):
        """The program's host work a step: CPU time of the root spans."""
        return self._busy_ms(self.roots)

    def roots_wall_ms(self):
        return self._wall_ms(self.roots)

    def unspanned_ms(self):
        """The step period less the root spans' length: what the loop
        does under no span (the feed, eager operations, glue)."""
        wall = self.roots_wall_ms()
        return None if wall is None else self.period_ms - wall


def steps(run, min_steps=MIN_STEPS, snapshot=None):
    """``Steps`` over ``run``'s untraced window, from the ring as it
    stands (or from ``snapshot``, a list of ring records); ``None``
    where it holds fewer than ``min_steps`` whole steps or records
    without ``cpu_ns``."""
    if snapshot is None:
        from mxnet_tpu import tracing

        snapshot = tracing.spans_snapshot()
    w0, w1 = run["w0_ns"], run["w1_ns"]
    inside = [s for s in snapshot if s["start_ns"] >= w0 and _end(s) <= w1]
    marks = [s for s in inside if s["name"] == STEP_SPAN]
    if not marks:
        return None
    tids = [s["tid"] for s in marks]
    tid = max(set(tids), key=tids.count)
    ends = sorted(_end(s) for s in marks if s["tid"] == tid)
    count = len(ends) - 1
    if count < max(min_steps, 1):
        return None
    held = [s for s in inside
            if s["tid"] == tid and ends[0] < _end(s) <= ends[-1]]
    if any(s.get("cpu_ns") is None for s in held):
        return None
    return Steps(ends[0], ends[-1], count, held)
