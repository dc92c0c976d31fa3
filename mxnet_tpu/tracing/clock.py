"""One monotonic clock for every timeline the process emits.

The profiler's chrome-trace events, the tracing spans, and the native
transport's server-side timestamps must live on a single time axis, or
a merged Perfetto artifact interleaves incompatible epochs (the PR 5
fix: profiler.py used its own ``perf_counter`` offset captured at its
import, spans would have used another — events recorded in the same
millisecond rendered minutes apart).

``EPOCH_NS`` is captured exactly once per process, at first import of
this module; everything that renders a relative timestamp subtracts it.
Absolute values are ``time.monotonic_ns()``: on Linux that is
CLOCK_MONOTONIC, the same clock C++'s ``steady_clock`` reads in
comm.cc, so worker-Python, server-Python and server-C++ timestamps on
one host are directly comparable. Across hosts (or artificially skewed
test traces) alignment is tools/trace_merge.py's job.
"""
from __future__ import annotations

import time

# process-wide monotonic epoch: captured ONCE, shared by profiler.py
# (chrome-trace ts) and tracing (span export) — never reassigned
EPOCH_NS = time.monotonic_ns()


def now_ns():
    """Current CLOCK_MONOTONIC time in nanoseconds (absolute)."""
    return time.monotonic_ns()


def thread_cpu_ns():
    """CPU time the calling thread has burnt, in nanoseconds
    (CLOCK_THREAD_CPUTIME_ID). It stands still while the thread is
    blocked — on the device, on a buffer, on a lock, in ``sleep`` — so
    over an interval, wall less this is how long the thread stood
    blocked. Differences only: the zero point is the thread's own.

    The kernel charges a thread from its scheduler clock. Where that
    clock has no fine-grained source (a virtual machine without a
    stable TSC falls back to the timer tick, 10 ms at HZ=100) a
    difference is the number of ticks that fell while the thread ran,
    times the tick: right in the mean over many intervals, 0 or a whole
    tick for one short interval. So nothing here or in a span clamps a
    reading to the interval's length; whoever adds readings up may
    bound the sum."""
    return time.thread_time_ns()


def rel_us(ns):
    """Absolute monotonic ns -> microseconds since the process epoch
    (the chrome-trace ``ts`` unit)."""
    return (ns - EPOCH_NS) / 1e3
