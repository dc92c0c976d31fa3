"""Percentiles and window accounting. Plain Python over lists, so the
tests can feed hand-made spans."""
import math


def percentile(values, p):
    """Nearest-rank percentile of a non-empty list."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    k = max(int(math.ceil(p / 100.0 * len(vals))) - 1, 0)
    return vals[k]


def train_window(step_ends_ns, batch, w0_ns, fence_ns):
    """Images of every whole step in the window over the seconds from
    the window's start to the fence that ends it."""
    steps = sum(1 for t in step_ends_ns if w0_ns <= t <= fence_ns)
    return {"steps": steps,
            "train_img_per_s": steps * batch / ((fence_ns - w0_ns) / 1e9)}


def gen_window(requests, w0_ns, w1_ns, drain_end_ns):
    """End-to-end numbers of a generation window.

    ``requests``: dicts with ``submit_ns``, ``token_ns`` (emit time of
    each output token) and ``ok``. Tokens count where they were emitted
    inside [w0, w1); the time to the first token over every request
    submitted inside the window, a request without a first token
    waiting until the drain ended; gaps over consecutive tokens of one
    request where the later one was emitted inside the window."""
    seconds = (w1_ns - w0_ns) / 1e9
    tokens, ttft, gaps = 0, [], []
    attempted = failed = 0
    for r in requests:
        toks = r["token_ns"]
        tokens += sum(1 for t in toks if w0_ns <= t < w1_ns)
        gaps.extend((b - a) / 1e6 for a, b in zip(toks, toks[1:])
                    if w0_ns <= b < w1_ns)
        if w0_ns <= r["submit_ns"] < w1_ns:
            attempted += 1
            failed += 0 if r["ok"] else 1
            first = toks[0] if toks else drain_end_ns
            ttft.append((first - r["submit_ns"]) / 1e6)
    out = {"attempted": attempted, "failed": failed, "tokens": tokens,
           "n_ttft": len(ttft), "n_gaps": len(gaps),
           "gen_tok_per_s": tokens / seconds}
    if ttft:
        out["gen_ttft_p95_ms"] = percentile(ttft, 95)
    if gaps:
        out["gen_gap_p95_ms"] = percentile(gaps, 95)
        out["gen_gap_p50_ms"] = percentile(gaps, 50)
    return out


def mean_span_ms(spans_ns, w0_ns, w1_ns):
    """Mean length in ms of the (start, end) spans that end inside the
    window, or None where there is none."""
    spans = [(s, e) for s, e in spans_ns if w0_ns <= e <= w1_ns]
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans) / 1e6
