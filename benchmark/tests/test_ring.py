"""``lib/ring.py`` and the readers that stand on it: on a hand-made ring
and ``run`` (every time in ms below, ns in the records), and once on
the ring a real CPU run of the tiny train cell leaves behind."""
import io
import itertools
import json
import math
import os
import types

import pytest

from benchmark import run as harness
from benchmark.lib import ring

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000
PERIOD = 50
MAIN, OTHER = 11, 22
# name -> (start in the step, length, CPU time, name of its parent)
LAYOUT = [
    ("block.call", 5, 10, 4, None),
    ("autograd.backward", 16, 20, 5, None),
    ("autograd.vjp", 17, 3, 3, "autograd.backward"),
    ("autograd.pullback", 21, 12, 1, "autograd.backward"),
    ("trainer_step", 42, 8, 3, None),
    ("trainer.update", 43, 2, 2, "trainer_step"),
    ("trainer.health", 45.5, 1, 0.5, "trainer_step"),
    ("trainer.health", 46.5, 3, 0.25, "trainer_step"),
]
SPAN_READERS = {
    "train_host_busy_ms": 4 + 5 + 3,
    "train_host_unspanned_ms": PERIOD - (10 + 20 + 8),
    "train_host_floor_pct": 100.0 * (12 + 12) / PERIOD,
    "train_block_call_busy_ms": 4,
    "train_pullback_busy_ms": 1,
    "train_update_busy_ms": 2,
    "train_launch_blocked_ms": (10 - 4) + (12 - 1),
    "train_readback_blocked_ms": (1 - 0.5) + (3 - 0.25),
}


def reader(name):
    return harness.load_by_path(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "ring_reader_" + name).read


def step_records(k, ids, tid=MAIN, scale=1.0, cpu=True):
    """The records of step ``k``, which ends with its ``trainer_step``
    at (k + 1) x PERIOD, in the order their spans close."""
    by_name, out = {}, []
    for name, at, dur, busy, parent in LAYOUT:
        rec = {"name": name, "cat": None, "trace": 1, "span": next(ids),
               "parent": by_name[parent]["span"] if parent else None,
               "start_ns": int((k * PERIOD + at) * MS),
               "dur_ns": int(dur * scale * MS), "tid": tid,
               "thread": "t%d" % tid, "attrs": {}}
        if cpu:
            rec["cpu_ns"] = int(busy * scale * MS)
        by_name[name] = rec
        out.append(rec)
    return sorted(out, key=lambda r: r["start_ns"] + r["dur_ns"])


def made(steps=30, warm=3, traced=6, **kw):
    """A ring and its ``run``: ``warm`` steps of set-up before the
    window, ``steps`` inside it, then a traced slice of steps twice as
    long under the profiler, and a thread beside them that steps too."""
    ids = itertools.count(100)
    snapshot = []
    for k in range(warm + steps):
        snapshot += step_records(k, ids, **kw)
    for k in range(warm + steps, warm + steps + traced):
        snapshot += step_records(k, ids, scale=2.0, **kw)
    for k in range(warm, warm + 5):
        snapshot += step_records(k, ids, tid=OTHER, scale=0.5, **kw)
    run = {"w0_ns": int((warm * PERIOD + 1) * MS),
           "w1_ns": int(((warm + steps) * PERIOD + 1) * MS)}
    return snapshot, run


@pytest.fixture
def ring_of(monkeypatch):
    """Puts a hand-made snapshot where the readers look for the ring."""
    from mxnet_tpu import tracing

    def put(snapshot):
        monkeypatch.setattr(tracing, "spans_snapshot",
                            lambda: list(snapshot))
    return put


# -- the library -------------------------------------------------------
def test_steps_are_counted_from_one_step_end_to_the_next():
    snapshot, run = made(steps=30)
    held = ring.steps(run, snapshot=snapshot)
    # the window's first trainer_step only marks where a step starts
    assert held.count == 29
    assert held.period_ms == pytest.approx(PERIOD)
    assert {s["tid"] for s in held.spans} == {MAIN}
    assert len(held.spans) == 29 * len(LAYOUT)


def test_roots_and_self_time_follow_the_parent_link():
    snapshot, run = made()
    held = ring.steps(run, snapshot=snapshot)
    assert sorted({s["name"] for s in held.roots}) == [
        "autograd.backward", "block.call", "trainer_step"]
    assert held.roots_wall_ms() == pytest.approx(10 + 20 + 8)
    assert held.self_busy_ms("autograd.backward") == pytest.approx(5 - 3 - 1)
    assert held.self_busy_ms("trainer_step") == pytest.approx(3 - 2.75)
    assert held.self_busy_ms("block.call") == pytest.approx(4)
    assert held.wall_ms("trainer.health") == pytest.approx(4)
    assert held.busy_ms("no.such.span") is None
    # a span whose parent is no span of the window's steps (one left
    # open round the whole loop, say) is a root all the same
    for s in snapshot:
        if s["name"] == "block.call":
            s["parent"] = 7
    held = ring.steps(run, snapshot=snapshot)
    assert held.roots_busy_ms() == pytest.approx(12)


@pytest.mark.parametrize("dropped", [0, 3, len(LAYOUT), len(LAYOUT) + 5])
def test_a_ring_cut_inside_a_step_counts_whole_steps_only(dropped):
    """The ring drops its oldest records whatever step they are in."""
    snapshot, run = made(warm=0, steps=30, traced=0)
    main = [s for s in snapshot if s["tid"] == MAIN]
    held = ring.steps(run, snapshot=main[dropped:])
    # a step's trainer_step closes last: while it is held it marks the
    # start of the next step, and the step it ends is not whole
    assert held.count == 30 - dropped // len(LAYOUT) - 1
    assert held.period_ms == pytest.approx(PERIOD)
    assert held.roots_busy_ms() == pytest.approx(12)
    assert held.unspanned_ms() == pytest.approx(12)


def ticking(snapshot, tick_ms=10):
    """The same spans as a ticking thread clock reads them: a record
    says 0 until its kind's CPU time has filled a tick, then the tick."""
    owed = {}
    for s in sorted(snapshot, key=lambda r: r["start_ns"]):
        key = (s["tid"], s["name"])
        owed[key] = owed.get(key, 0) + s["cpu_ns"]
        s["cpu_ns"] = 0
        if owed[key] >= tick_ms * MS:
            owed[key] -= tick_ms * MS
            s["cpu_ns"] = tick_ms * MS
    return snapshot


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_reader_on_a_ring_whose_thread_clock_ticks(ring_of, name):
    """On the machine with the chip a record's CPU time is a whole
    number of 10 ms ticks; over 119 steps the means come out within a
    tick's share of a step, and no blocked time is negative."""
    snapshot, run = made(steps=120)
    ring_of(ticking(snapshot))
    got = reader(name)({"run": run})
    scale = 100.0 / PERIOD if name.endswith("_pct") else 1.0
    assert got == pytest.approx(SPAN_READERS[name], abs=3 * scale * 10 / 119)
    assert got >= 0


def test_cpu_time_of_a_sum_is_bounded_by_its_length():
    snapshot, run = made()
    for s in snapshot:
        if s["name"] == "trainer.update":       # 2 ms long, a tick each
            s["cpu_ns"] = 10 * MS
    held = ring.steps(run, snapshot=snapshot)
    assert held.busy_ms("trainer.update") == pytest.approx(2)
    assert held.blocked_ms("trainer.update") == 0
    assert held.self_busy_ms("trainer_step") == 0
    assert held.roots_busy_ms() <= held.roots_wall_ms()


def test_the_least_step_count_is_an_argument():
    snapshot, run = made(steps=6)
    assert ring.steps(run, snapshot=snapshot) is None
    assert ring.steps(run, min_steps=5, snapshot=snapshot).count == 5
    assert ring.steps(run, min_steps=6, snapshot=snapshot) is None


# -- the readers -------------------------------------------------------
@pytest.mark.parametrize("name", sorted(SPAN_READERS))
def test_reader_on_a_made_ring(ring_of, name):
    snapshot, run = made()
    ring_of(snapshot)
    # the traced slice's steps are twice as long: none of them is read
    assert reader(name)({"run": run}) == pytest.approx(SPAN_READERS[name])


@pytest.mark.parametrize("name", sorted(SPAN_READERS))
@pytest.mark.parametrize("why", ["cut_short", "no_cpu_ns", "empty",
                                 "no_step_span"])
def test_reader_says_nothing(ring_of, name, why):
    snapshot, run = made(steps=20) if why == "cut_short" else \
        made(cpu=why != "no_cpu_ns")
    if why == "empty":                  # MXTPU_TRACE_SAMPLE=0
        snapshot = []
    if why == "no_step_span":
        snapshot = [s for s in snapshot if s["name"] != "trainer_step"]
    ring_of(snapshot)
    assert reader(name)({"run": run}) is None


@pytest.mark.parametrize("absent,left", [
    ("trainer.health", {"train_readback_blocked_ms": None,
                        "train_update_busy_ms": 2}),
    ("autograd.pullback", {"train_launch_blocked_ms": 10 - 4,
                           "train_pullback_busy_ms": None})])
def test_reader_without_its_span(ring_of, absent, left):
    snapshot, run = made()
    ring_of([s for s in snapshot if s["name"] != absent])
    for name, want in left.items():
        got = reader(name)({"run": run})
        assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("stats,peak,want", [
    ({"bytes_limit": 16_000, "peak_bytes_in_use": 12_000}, 12_000, 75.0),
    ({"bytes_limit": 16_000}, 0, None),
    (None, 12_000, None)])
def test_hbm_peak_over_the_allocators_limit(monkeypatch, stats, peak, want):
    import jax

    device = types.SimpleNamespace(memory_stats=lambda: stats)
    monkeypatch.setattr(jax, "devices", lambda *a: [device])
    got = reader("train_hbm_peak_pct")(
        {"device": {"memory_peak_bytes": peak}})
    assert got == want


# -- the ring a real run leaves ----------------------------------------
def test_readers_on_the_ring_of_a_real_run(tiny_root, monkeypatch):
    """The tiny train cell, traced, with the nine entries added to its
    BENCHMARK.json. Its window holds few steps, so the readers' call
    passes a least step count of 2."""
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    names = sorted(SPAN_READERS) + ["train_hbm_peak_pct"]
    for name in names:
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_span", "layer": "whole step",
            "moves": "train_img_per_s", "workloads": ["tiny_train"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    real, seen = ring.steps, {}

    def steps(run, min_steps=2, snapshot=None):
        seen["run"] = run
        return real(run, min_steps, snapshot)

    monkeypatch.setattr(ring, "steps", steps)
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(tiny_root, "tiny_train", 3, 3.0, 1, gate=False,
                          peaks_kind="TPU v5 lite", out=out, err=err)
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"], err.getvalue()
    got = {n: result["metrics"][n]["value"] for n in SPAN_READERS}
    assert all(math.isfinite(v) and v >= 0 for v in got.values()), got
    assert got["train_host_busy_ms"] > 0
    # the CPU backend has no allocator statistics
    assert "train_hbm_peak_pct" not in result["metrics"]
    assert 0 < got["train_host_floor_pct"] <= 100
    assert got["train_block_call_busy_ms"] + got["train_pullback_busy_ms"] \
        + got["train_update_busy_ms"] <= got["train_host_busy_ms"]

    # the ring is still as the readers saw it: every whole step of the
    # untraced window, and none of the traced slice
    run = seen["run"]
    held = real(run, min_steps=2)
    in_window = [t for t in run["step_ends_ns"]
                 if run["w0_ns"] <= t <= run["w1_ns"]]
    assert held.count == len(in_window) - 1 == result["attempted"] - 1
    assert held.period_ms == pytest.approx(
        (in_window[-1] - in_window[0]) / held.count / 1e6, rel=0.02)
    assert got["train_host_busy_ms"] == pytest.approx(held.roots_busy_ms())
    assert held.roots_busy_ms() <= held.roots_wall_ms() < held.period_ms
    assert {s["name"] for s in held.roots} == {
        "block.call", "autograd.backward", "trainer_step"}
    # the driver's own timers round the same calls agree with the ring
    fwd_bwd = [e - s for s, e in run["spans_ns"]["fwd_bwd"]
               if in_window[0] < e <= in_window[-1]]
    ring_fwd_bwd = held.wall_ms("block.call") \
        + held.wall_ms("autograd.backward")
    assert ring_fwd_bwd <= sum(fwd_bwd) / held.count / 1e6
    assert real(run) is None or held.count >= ring.MIN_STEPS
