"""Real rows over padded rows of the decode steps in the window, from
``GenRequest.step_meta`` (rows, bucket): how full ``GenLane`` keeps the
compiled decode bucket."""


def decode_steps(run):
    """{step start: (end, interleave_ns, rows, bucket)} of the decode
    steps that ended inside the window. Every live request records the
    step it took part in, so steps are told apart by their start."""
    steps = {}
    for r in run["requests"]:
        for start, end, inter, rows, bucket in r["steps"][1:]:
            if run["w0_ns"] <= end < run["w1_ns"]:
                steps[start] = (end, inter, rows, bucket)
    return steps


def read(ctx):
    steps = decode_steps(ctx["run"])
    if not steps:
        return None
    rows = sum(s[2] for s in steps.values())
    padded = sum(s[3] for s in steps.values())
    return 100.0 * rows / padded
