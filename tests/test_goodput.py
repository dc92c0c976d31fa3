"""Tier-1 tests for the fleet goodput & SLO plane (PR 19).

Covers: the shared timeline window math (bucket-delta quantile/CDF —
the cumulative-vs-delta bug class pinned where the implementation now
lives), ring boundedness + reset safety, per-seam goodput bin
classification over synthetic spans (including the nested-reshape
subtraction under lend spans), the ledger conservation cross-check,
SLO fast/slow burn-rate evaluation with None-means-no-signal
semantics for both policy consumers, the recorder's bounded
enabled-vs-disabled overhead, the committed goodput artifact + the
``perf_gate --goodput`` self-test with synthetic regressions, env-var
registration, and the MXL002 scope extension. Standalone-fast: no
training, no gateway — the producing colocation run is exercised by
``chaos_bench --goodput`` out of band.
"""
import copy
import json
import os
import subprocess
import sys
import time

import pytest

from mxnet_tpu.profiling import goodput
from mxnet_tpu.telemetry import metrics
from mxnet_tpu.telemetry.slo import SLOTracker
from mxnet_tpu.telemetry.timeline import (Timeline, delta_over,
                                          delta_quantile, dump,
                                          from_doc, stats_of)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOODPUT_ARTIFACT = os.path.join(REPO, "docs", "artifacts",
                                "GOODPUT_LAST_GOOD.json")


@pytest.fixture(autouse=True)
def _enabled_registry():
    metrics.set_enabled(True)
    yield
    metrics.set_enabled(True)


# ---------------------------------------------------------------------
# frame fabrication: a fake registry lets every windowed query run on
# exact, hand-built snapshots (no serving machinery, no real clock)
# ---------------------------------------------------------------------
class _FakeReg:
    def __init__(self):
        self.metrics = {}

    def snapshot(self):
        return {"ts": time.time(),
                "metrics": copy.deepcopy(self.metrics)}

    def counter(self, name, value, **labels):
        fam = self.metrics.setdefault(name, {"type": "counter",
                                             "series": []})
        fam["series"] = [s for s in fam["series"]
                         if s["labels"] != labels]
        fam["series"].append({"labels": labels, "value": value})

    def hist(self, name, buckets, count, total, **labels):
        """``buckets`` is the CUMULATIVE [(le, cum), ...] list ending
        in ("+Inf", count) — the snapshot series shape."""
        fam = self.metrics.setdefault(name, {"type": "histogram",
                                             "series": []})
        fam["series"] = [s for s in fam["series"]
                         if s["labels"] != labels]
        fam["series"].append({"labels": labels, "count": count,
                              "sum": total, "buckets": buckets})


def _ticked(reg, states):
    """A Timeline over ``reg`` with one frame per (ts, mutator)."""
    tl = Timeline(window=64, registry=reg, clock=time.time)
    for ts, mutate in states:
        mutate(reg)
        tl.tick(now=ts)
    return tl


# ---------------------------------------------------------------------
# timeline window math
# ---------------------------------------------------------------------
def test_delta_quantile_is_window_exact_not_cumulative():
    """The PR-14 bug class, pinned at the shared implementation: 50
    fast obs recorded BEFORE the window must not drag the window's
    p99 toward them."""
    buckets0 = [("0.005", 50), ("0.1", 50), ("1.0", 50), ("+Inf", 50)]
    # window adds 50 obs in (0.1, 1.0]
    buckets1 = [("0.005", 50), ("0.1", 50), ("1.0", 100),
                ("+Inf", 100)]
    p99 = delta_quantile((50, 0.25, buckets0), (100, 30.0, buckets1),
                         q=0.99)
    # all 50 window obs live in (0.1, 1.0]: interpolated p99 near 1.0
    assert 0.9 < p99 <= 1.0
    # cumulative read (both sides identical) = empty window = None
    assert delta_quantile((100, 30.0, buckets1),
                          (100, 30.0, buckets1)) is None
    assert delta_quantile(None, (100, 30.0, buckets1)) is None


def test_delta_quantile_interpolates_and_caps_at_inf():
    zero = [("0.01", 0), ("0.1", 0), ("+Inf", 0)]
    allfast = [("0.01", 100), ("0.1", 100), ("+Inf", 100)]
    p50 = delta_quantile((0, 0.0, zero), (100, 0.5, allfast), q=0.5)
    assert abs(p50 - 0.005) < 1e-9      # linear inside [0, 0.01]
    # everything beyond the last finite edge: ceiling estimate
    allslow = [("0.01", 0), ("0.1", 0), ("+Inf", 100)]
    assert delta_quantile((0, 0.0, zero), (100, 50.0, allslow),
                          q=0.99) == 0.1


def test_delta_over_cdf_complement():
    zero = [("0.05", 0), ("0.1", 0), ("+Inf", 0)]
    cur = [("0.05", 10), ("0.1", 15), ("+Inf", 20)]
    # 5 of 20 obs above the 0.1 edge
    frac = delta_over((0, 0.0, zero), (20, 2.0, cur), 0.1)
    assert abs(frac - 0.25) < 1e-9
    # threshold inside a bucket: linear interpolation of its density
    frac = delta_over((0, 0.0, zero), (20, 2.0, cur), 0.075)
    assert abs(frac - (1.0 - (10 + 5 * 0.5) / 20.0)) < 1e-9
    assert delta_over((20, 2.0, cur), (20, 2.0, cur), 0.1) is None


def test_timeline_rate_quantile_and_window_selection():
    reg = _FakeReg()
    hist = lambda cum, n: [("0.1", cum), ("1.0", n), ("+Inf", n)]
    tl = _ticked(reg, [
        (0.0, lambda r: (r.counter("t_req_total", 0.0),
                         r.hist("t_lat_seconds", hist(0, 0), 0, 0.0))),
        (10.0, lambda r: (r.counter("t_req_total", 50.0),
                          r.hist("t_lat_seconds", hist(50, 50), 50,
                                 2.5))),
        (20.0, lambda r: (r.counter("t_req_total", 150.0),
                          r.hist("t_lat_seconds", hist(50, 150), 150,
                                 60.0))),
    ])
    # last-two-frames semantics (the autoscaler's between-ticks read)
    assert tl.rate("t_req_total") == pytest.approx(10.0)
    assert tl.delta("t_req_total") == pytest.approx(100.0)
    # windowed: prev = newest frame at or before now - window_s
    assert tl.rate("t_req_total", window_s=20.0) == \
        pytest.approx(150.0 / 20.0)
    # last delta saw 100 obs, all in (0.1, 1.0]: median 0.55
    assert tl.quantile("t_lat_seconds", 0.5) == pytest.approx(0.55)
    # the 20s window adds the 50 fast obs: median of 150 drops to
    # the 25th slow obs = 0.1 + 0.25 * 0.9
    assert tl.quantile("t_lat_seconds", 0.5, window_s=20.0) == \
        pytest.approx(0.325)
    # absent series / single frame -> None, never 0
    assert tl.rate("t_missing_total") is None
    assert Timeline(window=4, registry=reg).rate("t_req_total") is None


def test_timeline_ring_bounded_and_reset_safe():
    reg = _FakeReg()
    tl = Timeline(window=4, registry=reg, clock=time.time)
    for i in range(10):
        reg.counter("t_req_total", float(i))
        tl.tick(now=float(i))
    assert len(tl) == 4                      # oldest evicted
    assert tl.ticks_total == 10
    assert [f["ts"] for f in tl.frames()] == [6.0, 7.0, 8.0, 9.0]
    with pytest.raises(ValueError):
        Timeline(window=1, registry=reg)
    # recorded frames are snapshots: zeroing the live registry must
    # not rewrite history (the registry reset() zeroes IN PLACE)
    reg.counter("t_req_total", 0.0)
    assert tl.frames()[-1]["metrics"]["t_req_total"]["series"][0][
        "value"] == 9.0
    tl.reset()
    assert len(tl) == 0
    reg.counter("t_req_total", 1.0)
    tl.tick(now=11.0)
    assert len(tl) == 1                      # capacity survives reset


def test_timeline_artifact_round_trip(tmp_path):
    reg = _FakeReg()
    reg.counter("t_req_total", 1.0)
    tl = Timeline(window=8, registry=reg)
    tl.tick(now=1.0)
    tl.tick(now=2.0)
    path = str(tmp_path / "tl.json")
    doc = dump(path, timeline=tl)
    assert doc["kind"] == "timeline/v1" and doc["version"] == 1
    with open(path, encoding="utf-8") as f:
        loaded = from_doc(json.load(f))
    assert len(loaded["frames"]) == 2
    with pytest.raises(ValueError):
        from_doc({"kind": "nope"})
    # chrome-trace merge renders frames as historical counter points
    from mxnet_tpu.telemetry import export
    trace = export.merge_chrome_trace(spans=[], timeline=loaded)
    pts = [e for e in trace["traceEvents"]
           if e.get("name") == "t_req_total" and e.get("ph") == "C"]
    assert len(pts) >= 2
    assert trace["metadata"]["timeline"]["frames"] == 2


# ---------------------------------------------------------------------
# goodput bin classification per seam
# ---------------------------------------------------------------------
def _span(name, start_s, dur_s, **attrs):
    return {"name": name, "start_ns": int(start_s * 1e9),
            "dur_ns": int(dur_s * 1e9), "attrs": attrs}


def test_classify_spans_per_seam_widths():
    spans = [
        _span("step", 0.0, 1.0, dp=4),                 # 4 dev-s
        _span("elastic.reshape", 2.0, 1.0,
              world_from=4, world_to=2),               # max(4,2)=4
        # lend [1.5, 3.5) contains the reshape [2, 3): only the
        # non-nested second bills at chip width -> (2-1) * 2
        _span("cluster.lend", 1.5, 2.0, chips=2),
        _span("generate.prefill", 0.0, 0.5),           # x1
        _span("generate.token", 0.0, 0.25),            # x1
        _span("generate.recover", 0.0, 0.1, mode="migrate"),
        _span("serving.execute", 0.0, 0.1),            # -> prefill bin
        _span("reshape.quiesce", 2.0, 0.5),            # child: unbilled
        _span("unrelated", 0.0, 9.0),
    ]
    bins, counts = goodput.classify_spans(spans)
    assert bins["train_compute"] == pytest.approx(4.0)
    assert bins["reshape_tax"] == pytest.approx(4.0)
    assert bins["lend_transition"] == pytest.approx(2.0)
    assert bins["serve_prefill"] == pytest.approx(0.6)
    assert bins["serve_decode"] == pytest.approx(0.25)
    assert bins["recovery_tax"] == pytest.approx(0.1)
    assert "idle" not in bins                  # needs the ledger total
    assert counts == {"step": 1, "elastic.reshape": 1,
                      "cluster.lend": 1, "generate.prefill": 1,
                      "generate.token": 1, "generate.recover": 1,
                      "serving.execute": 1}


def test_classify_spans_clips_to_window():
    spans = [_span("step", 0.0, 10.0, dp=2)]
    bins, _ = goodput.classify_spans(spans, t0_ns=int(4e9),
                                     t1_ns=int(6e9))
    assert bins["train_compute"] == pytest.approx(4.0)   # 2s * dp 2
    bins, counts = goodput.classify_spans(spans, t0_ns=int(20e9),
                                          t1_ns=int(30e9))
    assert bins["train_compute"] == 0.0 and not counts


# ---------------------------------------------------------------------
# conservation cross-check
# ---------------------------------------------------------------------
def _ds(training=6.0, serving=3.0, free=3.0, world=4, elapsed=3.0,
        conserved=True):
    return {"by_owner": {"training": training, "serving": serving,
                         "free": free},
            "total": training + serving + free,
            "world_size": world, "elapsed_s": elapsed,
            "conserved": conserved}


def test_collect_conserves_and_fills_idle():
    spans = [_span("step", 0.0, 1.0, dp=4),
             _span("generate.prefill", 0.0, 0.5)]
    doc = goodput.collect(_ds(), spans, t0_ns=0, t1_ns=int(3e9))
    assert doc["kind"] == "goodput/v1" and doc["version"] == 1
    assert doc["bins"]["idle"] == pytest.approx(12.0 - 4.5)
    assert doc["goodput"]["fraction"] == pytest.approx(4.5 / 12.0)
    con = doc["conservation"]
    assert con["ledger_conserved"] and con["owners_within"]
    assert con["conserved"] is True
    assert doc["by_owner"]["training"]["within"] is True


def test_collect_flags_double_billing_and_ledger_break():
    # 8 classified training dev-s against a 6 dev-s training lease
    spans = [_span("step", 0.0, 2.0, dp=4)]
    doc = goodput.collect(_ds(), spans, t0_ns=0, t1_ns=int(3e9))
    assert doc["by_owner"]["training"]["within"] is False
    assert doc["conservation"]["conserved"] is False
    # owner seconds that no longer sum to world x elapsed
    doc = goodput.collect(_ds(training=2.0), [], t0_ns=0, t1_ns=1)
    assert doc["conservation"]["ledger_conserved"] is False
    assert doc["conservation"]["conserved"] is False


def test_summary_is_bounded_and_provenance_marked():
    doc = goodput.collect(_ds(), [_span("step", 0.0, 1.0, dp=4)],
                          t0_ns=0, t1_ns=int(3e9),
                          slo={"objectives": [
                              {"name": "o%d" % i, "burn": 1.0}
                              for i in range(8)]})
    s = goodput.summary(doc)
    assert s["kind"] == "goodput_summary"
    assert s["source"] == "profiling.goodput"
    assert len(json.dumps(s)) <= 2048
    assert goodput.summary({"kind": "other"}) is None
    # the bound holds by shedding detail, never by overflowing
    tight = goodput.summary(doc, max_bytes=220)
    assert len(json.dumps(tight)) <= 220 or "bins" not in tight


# ---------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------
def _slo_states():
    """Three frames: a clean slow window, then a fast window burning
    rejections at 2x budget and inter-token latency over target."""
    it_hist = lambda le05, le1, n: [("0.05", le05), ("0.1", le1),
                                    ("+Inf", n)]

    def f0(r):
        r.counter("mx_serving_rejected_total", 0.0, model="m",
                  reason="busy")
        r.counter("mx_serving_requests_total", 0.0, model="m",
                  variant="fp32")
        r.hist("mx_serving_generate_inter_token_seconds",
               it_hist(0, 0, 0), 0, 0.0, model="m", phase="steady")

    def f1(r):
        r.counter("mx_serving_rejected_total", 0.0, model="m",
                  reason="busy")
        r.counter("mx_serving_requests_total", 100.0, model="m",
                  variant="fp32")
        r.hist("mx_serving_generate_inter_token_seconds",
               it_hist(100, 100, 100), 100, 3.0, model="m",
               phase="steady")

    def f2(r):
        r.counter("mx_serving_rejected_total", 10.0, model="m",
                  reason="busy")
        r.counter("mx_serving_requests_total", 200.0, model="m",
                  variant="fp32")
        # fast window: 100 new obs, 25 above the 0.1s target
        r.hist("mx_serving_generate_inter_token_seconds",
               it_hist(150, 175, 200), 200, 9.0, model="m",
               phase="steady")

    return [(0.0, f0), (50.0, f1), (60.0, f2)]


def test_slo_fast_slow_burn_pair():
    tl = _ticked(_FakeReg(), _slo_states())
    tracker = SLOTracker(timeline=tl, fast_s=10.0, slow_s=60.0)
    res = {r["name"]: r for r in tracker.evaluate(now=60.0)}
    rej = res["rejection_rate"]
    # fast: 10 rejects / 100 admissions over budget 0.05 -> burn 2
    assert rej["windows"]["fast"]["burn"] == pytest.approx(2.0)
    # slow: 10 / 200 -> burn 1; effective = min(fast, slow)
    assert rej["windows"]["slow"]["burn"] == pytest.approx(1.0)
    assert rej["burn"] == pytest.approx(1.0)
    it = res["inter_token_p99"]
    # fast window: 25 of 100 obs above 0.1s, budget 1 - 0.99
    assert it["windows"]["fast"]["err_frac"] == pytest.approx(0.25)
    assert it["windows"]["fast"]["burn"] == pytest.approx(25.0)
    # slow window: 25 of 200 obs over target -> 12.5; min(fast, slow)
    assert it["burn"] == pytest.approx(12.5)
    # e2e saw no traffic at all: None, not 0
    assert res["e2e_p99"]["burn"] is None
    # fleet burn = max over objectives with data in BOTH windows
    assert tracker.burn(now=60.0) == pytest.approx(12.5)
    doc = tracker.to_doc(now=60.0)
    assert doc["kind"] == "slo/v1" and len(doc["objectives"]) == 3


def test_slo_publishes_mx_slo_families_and_none_on_empty():
    reg = metrics.registry()
    tl = _ticked(_FakeReg(), _slo_states())
    SLOTracker(timeline=tl, fast_s=10.0, slow_s=60.0).evaluate(
        now=60.0)
    snap = reg.snapshot()["metrics"]
    assert "mx_slo_burn_rate" in snap
    labels = {(s["labels"]["objective"], s["labels"]["window"])
              for s in snap["mx_slo_burn_rate"]["series"]}
    assert ("rejection_rate", "fast") in labels
    assert "mx_slo_error_fraction" in snap
    assert sum(s["value"] for s in
               snap["mx_slo_evaluations_total"]["series"]) >= 1
    # an empty timeline gives no signal, never a numeric zero
    empty = Timeline(window=4, registry=_FakeReg())
    assert SLOTracker(timeline=empty).burn() is None


def test_policies_treat_burn_as_input_not_wedge():
    from mxnet_tpu.cluster.lending import LendingScheduler
    from mxnet_tpu.elastic.autoscale import Autoscaler

    sched = LendingScheduler.__new__(LendingScheduler)
    sched.burn_high = 1.0
    sched.slo = None
    assert sched._budget_healthy() is True          # no tracker
    sched.slo = lambda: None
    assert sched._budget_healthy() is True          # no signal
    sched.slo = lambda: 0.4
    assert sched._budget_healthy() is True          # under budget
    sched.slo = lambda: 2.5
    assert sched._budget_healthy() is False         # burning: defer
    def _broken():
        raise RuntimeError("tracker down")
    sched.slo = _broken
    assert sched._budget_healthy() is True          # survived

    scaler = Autoscaler.__new__(Autoscaler)
    scaler.model = "m"
    scaler.slo = None
    assert scaler._slo_burn({}) is None
    scaler.slo = lambda: 3.0
    assert scaler._slo_burn({}) == 3.0
    # an object with .burn() is consulted through it
    tl = _ticked(_FakeReg(), _slo_states())
    scaler.slo = SLOTracker(timeline=tl, fast_s=10.0, slow_s=60.0)
    assert scaler._slo_burn({}) > 1.0


# ---------------------------------------------------------------------
# recorder overhead: enabled vs disabled, min-of-N interleaved
# ---------------------------------------------------------------------
def test_recorder_overhead_bounded():
    """A workload updating metrics while a timeline records frames
    stays within 5% of the same workload without the recorder.
    Process CPU time, interleaved min-of-N with retries (the
    test_telemetry overhead idiom): noise only ever ADDS time, so min
    estimates the true cost of each mode."""
    # a registry of its own: the bound is on the recorder, and a frame
    # of the process registry also pays for every series and snapshot
    # collector (the live-array census) that earlier tests in this
    # worker happened to leave behind
    reg = metrics.MetricRegistry()
    c = reg.counter("t_gp_overhead_total", "t", labelnames=("k",))
    h = reg.histogram("t_gp_overhead_seconds", "t")

    def workload(tl, iters=4000, tick_every=1000):
        # ~4 frames per 4k hot-path updates — far denser than any
        # real MXTPU_TIMELINE_SEC cadence, so the bound is conservative
        t0 = time.process_time()
        for i in range(iters):
            c.labels(k=str(i % 4)).inc()
            h.labels().observe(0.01 * (i % 7))
            if tl is not None and i % tick_every == 0:
                tl.tick()
        return time.process_time() - t0

    workload(Timeline(window=8, registry=reg))     # warm both paths
    workload(None)
    best = None
    for _ in range(4):
        on, off = [], []
        for _ in range(4):
            on.append(workload(Timeline(window=8, registry=reg)))
            off.append(workload(None))
        ratio = min(on) / min(off)
        best = ratio if best is None else min(best, ratio)
        if best < 1.05:
            break
    assert best < 1.05, \
        "timeline recorder overhead %.1f%% (on=%s off=%s)" \
        % ((best - 1) * 100, on, off)


# ---------------------------------------------------------------------
# committed artifact + gate self-test
# ---------------------------------------------------------------------
def _artifact():
    with open(GOODPUT_ARTIFACT, encoding="utf-8") as f:
        return json.load(f)


def test_committed_artifact_is_conserved_and_tax_bearing():
    doc = _artifact()
    assert doc["kind"] == "goodput/v1"
    for b in goodput.BINS:
        assert b in doc["bins"], b
    # the colocation producer must exercise every transition seam
    for b in goodput.TAX_BINS:
        assert doc["bins"][b] > 0, b
    assert doc["goodput"]["fraction"] > 0
    # conservation recomputed from the raw numbers, not the flag
    ds = doc["device_seconds"]
    owner_sum = sum(ds["by_owner"].values())
    expect = ds["world_size"] * ds["elapsed_s"]
    assert abs(owner_sum - expect) <= 0.02 * expect
    for owner, owned in goodput.OWNER_BINS.items():
        cls = sum(doc["bins"][b] for b in owned)
        assert cls <= ds["by_owner"][owner] * 1.05 + 0.05, owner
    assert doc["slo"]["objectives"]


def _run_gate(path, last_good=GOODPUT_ARTIFACT):
    return subprocess.run(
        [sys.executable, "tools/perf_gate.py", str(path), "--goodput",
         "--last-good", str(last_good)],
        cwd=REPO, capture_output=True, text=True)


def test_gate_passes_committed_artifact():
    proc = _run_gate(GOODPUT_ARTIFACT)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "PASS" in proc.stdout


def test_gate_rejects_synthetic_regressions(tmp_path):
    base = _artifact()

    def tampered(name, mutate, want_rc=1):
        doc = copy.deepcopy(base)
        mutate(doc)
        p = tmp_path / ("%s.json" % name)
        p.write_text(json.dumps(doc))
        proc = _run_gate(p)
        assert proc.returncode == want_rc, \
            "%s: rc %d\n%s" % (name, proc.returncode, proc.stdout)
        return proc.stdout

    out = tampered("fraction_drop", lambda d: d["goodput"].update(
        fraction=d["goodput"]["fraction"] * 0.5))
    assert "fraction" in out
    tampered("conservation_break", lambda d: d["device_seconds"]
             ["by_owner"].update(training=1.0))
    tampered("dropped_device", lambda d: d["device_seconds"].update(
        world_size=d["device_seconds"]["world_size"] - 1))
    tampered("dropped_bin", lambda d: d["bins"].pop("recovery_tax"))
    tampered("zeroed_tax_bin", lambda d: d["bins"].update(
        lend_transition=0.0))
    tampered("missing_slo", lambda d: d.pop("slo"))
    tampered("dropped_objective", lambda d: d["slo"].update(
        objectives=d["slo"]["objectives"][:1]))
    tampered("double_billed", lambda d: d["bins"].update(
        train_compute=d["device_seconds"]["by_owner"]["training"] * 2))
    tampered("bare_zero", lambda d: d["goodput"].update(total_s=0.0),
             want_rc=3)
    tampered("wrong_kind", lambda d: d.update(kind="nope"),
             want_rc=2)


def test_goodput_report_renders_committed_artifact():
    proc = subprocess.run(
        [sys.executable, "tools/goodput_report.py", GOODPUT_ARTIFACT],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "goodput: fraction" in proc.stdout
    assert "train_compute" in proc.stdout
    diff = subprocess.run(
        [sys.executable, "tools/goodput_report.py", "--diff",
         GOODPUT_ARTIFACT, GOODPUT_ARTIFACT],
        cwd=REPO, capture_output=True, text=True)
    assert diff.returncode == 0, diff.stdout + diff.stderr


# ---------------------------------------------------------------------
# registration: env vars, MXL002 scope
# ---------------------------------------------------------------------
def test_timeline_env_vars_registered():
    from mxnet_tpu import libinfo

    doc = open(os.path.join(REPO, "docs", "env_vars.md"),
               encoding="utf-8").read()
    for var in ("MXTPU_TIMELINE_WINDOW", "MXTPU_TIMELINE_SEC",
                "MXTPU_SLO_FILE"):
        assert var in libinfo._ENV_VARS, var
        assert var in doc, var


def test_goodput_mxl002_scope_registered():
    from mxnet_tpu.analysis.rules.host_sync import _SCOPES

    scopes = {prefix: methods for prefix, methods, _ in _SCOPES}
    for name in ("tick", "bounds", "rate", "quantile", "over_fraction",
                 "delta_quantile", "delta_over", "evaluate", "burn"):
        assert name in scopes["mxnet_tpu/telemetry/"], name
    for name in ("classify_spans", "collect"):
        assert name in scopes["mxnet_tpu/profiling/"], name
    assert "_slo_burn" in scopes["mxnet_tpu/elastic/"]
    assert "_budget_healthy" in scopes["mxnet_tpu/cluster/"]
