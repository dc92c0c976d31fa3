"""Admission work (other requests' prefills) that held a decode step,
from ``step_meta``'s interleave nanoseconds: mean per output token
emitted by a decode step inside the window, in ms."""


def read(ctx):
    run = ctx["run"]
    total = n = 0
    for r in run["requests"]:
        for _, end, inter, _, _ in r["steps"][1:]:
            if run["w0_ns"] <= end < run["w1_ns"]:
                total += inter
                n += 1
    if not n:
        return None
    return total / n / 1e6
