"""Median gap between consecutive output tokens of one request, gaps
ending inside the window: the pace of a decode step with nobody's
prefill in between."""


def read(ctx):
    return ctx["summary"]["extra"].get("gen_gap_p50_ms")
