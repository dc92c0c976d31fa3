"""Percentiles and window accounting on hand-made spans: a stall inside
the window moves every end-to-end number."""
from benchmark.lib import stats

MS = 1_000_000


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 50) == 50
    assert stats.percentile([7], 95) == 7
    assert stats.percentile([3, 1, 2], 100) == 3


def _requests(stall_ms=0):
    """Two requests, a token every 10 ms; the second is submitted at
    100 ms. A stall delays every token from 150 ms on."""
    def shift(t):
        return t + (stall_ms * MS if t >= 150 * MS else 0)

    a = [shift(t * MS) for t in range(20, 320, 10)]
    b = [shift(t * MS) for t in range(130, 330, 10)]
    return [{"submit_ns": 0, "token_ns": a, "ok": True},
            {"submit_ns": 100 * MS, "token_ns": b, "ok": True}]


def test_gen_window_counts_what_the_window_holds():
    got = stats.gen_window(_requests(), 50 * MS, 250 * MS, 400 * MS)
    # request a: tokens at 50..240 -> 20; request b: 130..240 -> 12
    assert got["tokens"] == 32
    assert got["gen_tok_per_s"] == 32 / 0.2
    assert got["attempted"] == 1 and got["failed"] == 0
    assert got["gen_ttft_p95_ms"] == 30.0
    assert got["gen_gap_p95_ms"] == 10.0
    assert got["gen_gap_p50_ms"] == 10.0


def test_a_stall_inside_the_window_moves_every_number():
    calm = stats.gen_window(_requests(), 50 * MS, 250 * MS, 400 * MS)
    slow = stats.gen_window(_requests(60), 50 * MS, 250 * MS, 400 * MS)
    assert slow["gen_tok_per_s"] < calm["gen_tok_per_s"]
    assert slow["gen_gap_p95_ms"] == 70.0 > calm["gen_gap_p95_ms"]
    # a stall that holds the first token of the request submitted in
    # the window
    held = _requests()
    held[1]["token_ns"] = [t + 60 * MS for t in held[1]["token_ns"]]
    assert stats.gen_window(held, 50 * MS, 250 * MS, 400 * MS)[
        "gen_ttft_p95_ms"] == 90.0


def test_a_request_without_a_token_waits_until_the_drain_ends():
    reqs = _requests() + [{"submit_ns": 200 * MS, "token_ns": [],
                           "ok": False}]
    got = stats.gen_window(reqs, 50 * MS, 250 * MS, 400 * MS)
    assert got["attempted"] == 2 and got["failed"] == 1
    assert got["gen_ttft_p95_ms"] == 200.0


def test_train_window():
    ends = [t * MS for t in range(100, 1100, 100)]      # ten steps
    got = stats.train_window(ends, 64, 250 * MS, 1050 * MS)
    assert got["steps"] == 8
    assert got["train_img_per_s"] == 8 * 64 / 0.8
    # a stall before the fence: the same steps over more seconds
    slow = stats.train_window(ends, 64, 250 * MS, 1450 * MS)
    assert slow["train_img_per_s"] == 8 * 64 / 1.2


def test_mean_span_ms():
    spans = [(0, 10 * MS), (20 * MS, 50 * MS), (90 * MS, 200 * MS)]
    assert stats.mean_span_ms(spans, 5 * MS, 100 * MS) == 20.0
    assert stats.mean_span_ms(spans, 300 * MS, 400 * MS) is None
