"""Program spans on the profiler's clock.

A ``tracing.span`` lands in its thread's ring (``CLOCK_MONOTONIC``,
parent link) and, while a ``jax.profiler`` capture runs, as a TraceMe of
the same name on the host plane. These tests drive the gluon step and
at a tiny size on the CPU and read both records back, the capture
through the benchmark's own reader, and run the benchmark's span readers
on what they find.
"""
import importlib.util
import math
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, tracing
from mxnet_tpu.profiling import health
from mxnet_tpu.serving import Gateway
from mxnet_tpu.serving.generate import GenerativeDecoder

from benchmark.lib import spans, xplane

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 2

# child span -> the span it lies in (ISSUE 25's table); the others are
# opened under whatever the caller has open, here nothing
TRAIN_PARENT = {
    "block.call": None,
    "autograd.backward": None,
    "autograd.vjp": "autograd.backward",
    "autograd.pullback": "autograd.backward",
    "trainer_step": None,
    "trainer.update": "trainer_step",
    "trainer.health": "trainer_step",
}
PER_STEP = dict.fromkeys(TRAIN_PARENT, 1)
PER_STEP["trainer.health"] = 2        # the probe's commit, the boundary
HOST_READERS = ("train_block_call_host_ms", "train_vjp_trace_host_ms",
                "train_pullback_host_ms", "train_tape_host_ms",
                "train_update_loop_host_ms", "train_update_dispatches",
                "train_health_host_ms", "train_health_readbacks")
DEVICE_READERS = ("train_gluon_device_ms", "train_gluon_executions",
                  "train_update_device_ms")


def reader(name):
    path = os.path.join(REPO, "benchmark", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def small_net():
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.BatchNorm(),
                gluon.nn.Dense(3))
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((4, 5))
    net(x)                      # resolves the deferred shapes
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    y = mx.nd.array([0, 1, 2, 0])

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)

    trainable = [p for p in net.collect_params().values()
                 if p.grad_req != "null"]
    return step, net, len(trainable)


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Two warm steps under a capture: the planes, the ring's spans of
    the same two steps, the net and its number of trainable leaves."""
    step, net, n_params = small_net()
    health.reset()      # tables other files' trainers left to be folded
    step()
    directory = str(tmp_path_factory.mktemp("capture"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tracing.reset()
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
            for _ in range(STEPS):
                step()
            for p in net.collect_params().values():
                p.data().wait_to_read()
    finally:
        jax.profiler.stop_trace()
    ring = [s for s in tracing.spans_snapshot() if s["name"] in TRAIN_PARENT]
    return {"planes": xplane.load(directory), "ring": ring, "net": net,
            "n_params": n_params}


def _holder(events, parent, s, e):
    return [ev for ev in events if ev[0] == parent and ev[1] <= s
            and e <= ev[2]]


def _gluon_dispatches(events):
    """The outermost ``PjitFunction(mx_...)`` events of one thread."""
    out, end = [], -1
    for name, s, e in events:
        if name.startswith("PjitFunction(mx_") and s >= end:
            out.append((name, s, e))
            end = e
    return out


# -- (a) the same spans in the capture and in the ring ----------------------
def test_train_spans_on_the_host_plane_nested_as_the_table_says(captured):
    lines = [line for line in spans.host_lines(captured["planes"])
             if any(n == "trainer_step" for n, _, _ in line)]
    assert len(lines) == 1          # one thread drove the steps
    events = lines[0]
    for name, parent in TRAIN_PARENT.items():
        found = [ev for ev in events if ev[0] == name]
        assert len(found) == STEPS * PER_STEP[name], name
        if parent is not None:
            for _, s, e in found:
                assert len(_holder(events, parent, s, e)) == 1, name
    # one jitted call over all the trainable leaves, inside the update
    # span, and the gluon programs under the names the block gives them
    assert captured["n_params"] > 1
    assert spans.dispatches_per_step(captured["planes"], "trainer.update") \
        == 1
    # and a recorded call's two programs, the forward that writes the
    # residuals and the pullback over them: no plain forward beside them
    fns = _gluon_dispatches(events)
    assert [n for n, _, _ in fns] == [
        "PjitFunction(mx_hybridsequential_train_fwd)",
        "PjitFunction(mx_hybridsequential_train_bwd)"] * STEPS
    for (_, s, e), span in zip(fns, ["block.call", "autograd.pullback"]
                               * STEPS):
        assert len(_holder(events, span, s, e)) == 1


def test_ring_holds_the_same_spans_with_parent_links(captured):
    ring = captured["ring"]
    by_id = {s["span"]: s for s in ring}
    for name, parent in TRAIN_PARENT.items():
        found = [s for s in ring if s["name"] == name]
        assert len(found) == STEPS * PER_STEP[name], name
        for s in found:
            if parent is None:
                assert s["parent"] is None, name
            else:
                assert by_id[s["parent"]]["name"] == parent, name
    lines = spans.host_lines(captured["planes"])
    on_plane = {n: len(spans.named(lines, n)) for n in TRAIN_PARENT}
    assert on_plane == {n: STEPS * PER_STEP[n] for n in TRAIN_PARENT}


# -- (b) the readers --------------------------------------------------------
@pytest.mark.parametrize("name", HOST_READERS)
def test_host_reader_on_a_capture(captured, name):
    value = reader(name)({"planes": captured["planes"]})
    assert value is not None and math.isfinite(value) and value > 0
    if name in ("train_update_dispatches", "train_health_readbacks"):
        assert value == 1       # one program, one table read back


@pytest.mark.parametrize("name", ("train_gluon_device_ms",
                                  "train_gluon_executions"))
def test_gluon_device_reader_on_the_captured_dispatches(captured, name):
    """A CPU capture has no ``XLA Modules`` line. Lay one out with an
    execution for every gluon program the capture saw dispatched, named
    as a device names it: the readers find two a step."""
    events = next(line for line in spans.host_lines(captured["planes"])
                  if any(n == "trainer_step" for n, _, _ in line))
    modules = [("jit_%s(1)" % n[len("PjitFunction("):-1], s, e, None)
               for n, s, e in _gluon_dispatches(events)]
    planes = captured["planes"] + [
        {"name": xplane.DEVICE_PLANE + "0",
         "lines": [{"name": spans.MODULES_LINE, "events": modules}]}]
    value = reader(name)({"planes": planes})
    assert value is not None and value > 0
    if name == "train_gluon_executions":
        assert value == 2.0


def _made_planes(drop_an_execution=False):
    """Two steps as a chip capture shows them. Times in ns; the forward
    that writes the residuals runs 5 ms from ``block.call``, the
    transposed program 5 ms from ``autograd.pullback``, three updates
    0.5 ms each; autograd spends 10 - 4 - 2 = 4 ms itself."""
    ms = 1_000_000
    host, modules = [(xplane.WINDOW_SPAN, 0, 100 * ms, None)], []
    fwd, bwd = "mx_net0_train_fwd", "mx_net0_train_bwd"
    for k in range(2):
        t, d = 50 * k * ms, 50 * k * ms + 20 * ms
        host += [
            ("block.call", t + 1 * ms, t + 3 * ms, None),
            ("PjitFunction(%s)" % fwd, t + 2 * ms, t + 2 * ms + 400, None),
            # jaxlib's twin
            ("PjitFunction(%s)" % fwd, t + 2 * ms + 10, t + 2 * ms + 390,
             None),
            ("autograd.backward", t + 4 * ms, t + 14 * ms, None),
            ("autograd.vjp", t + 5 * ms, t + 9 * ms, None),
            ("autograd.pullback", t + 10 * ms, t + 12 * ms, None),
            ("PjitFunction(%s)" % bwd, t + 11 * ms, t + 11 * ms + 300, None),
            ("trainer_step", t + 15 * ms, t + 19 * ms, None),
            ("trainer.update", t + 16 * ms, t + 18 * ms, None),
        ]
        modules += [("jit_%s(11)" % fwd, d, d + 5 * ms, "jit_%s(11)" % fwd),
                    ("jit_%s(33)" % bwd, d + 5 * ms, d + 10 * ms,
                     "jit_%s(33)" % bwd)]
        for i in range(3):
            s = d + 10 * ms + i * ms
            host.append(("PjitFunction(_step_mom)", t + 16 * ms + i * 1000,
                         t + 16 * ms + i * 1000 + 500, None))
            modules.append(("jit__step_mom(7)", s, s + ms // 2,
                            "jit__step_mom(7)"))
    if drop_an_execution:
        modules = [m for m in modules if m[0] != "jit_%s(33)" % bwd]
    return [{"name": "/host:CPU",
             "lines": [{"name": "python", "events": host}]},
            {"name": "/device:TPU:0",
             "lines": [{"name": "XLA Modules", "events": modules},
                       {"name": "XLA Ops", "events": []}]}]


@pytest.mark.parametrize("name,want", [
    ("train_gluon_device_ms", 10.0), ("train_gluon_executions", 2.0),
    ("train_update_device_ms", 1.5)])
def test_device_reader_on_made_planes(name, want):
    assert reader(name)({"planes": _made_planes()}) == pytest.approx(want)


def test_readers_count_what_ran_and_say_nothing_without_spans(captured):
    # every execution counts: one fewer reads one fewer, by its own time
    planes = _made_planes(drop_an_execution=True)
    assert reader("train_gluon_device_ms")({"planes": planes}) == \
        pytest.approx(5.0)
    assert reader("train_gluon_executions")({"planes": planes}) == 1
    assert reader("train_update_dispatches")({"planes": planes}) == 3
    assert reader("train_tape_host_ms")({"planes": planes}) == \
        pytest.approx(4.0)
    # a CPU capture has no XLA Modules line; a program without the spans
    # (the parent commit) has no step to count
    for name in DEVICE_READERS:
        assert reader(name)({"planes": captured["planes"]}) is None
    bare = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        (xplane.WINDOW_SPAN, 0, 10, None),
        ("PjitFunction(pure_fn)", 1, 2, None)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": [
            ("jit_pure_fn(1)", 3, 4, None)]}]}]
    for name in HOST_READERS + DEVICE_READERS:
        assert reader(name)({"planes": bare}) is None


# -- (c) children inside their parent, self time not negative ---------------
def test_children_fit_their_parent_on_both_clocks(captured):
    ring = captured["ring"]
    for parent in ring:
        kids = [s for s in ring if s["parent"] == parent["span"]]
        for k in kids:
            assert k["start_ns"] >= parent["start_ns"]
            assert k["start_ns"] + k["dur_ns"] <= \
                parent["start_ns"] + parent["dur_ns"]
        assert sum(k["dur_ns"] for k in kids) <= parent["dur_ns"]
    events = [ev for line in spans.host_lines(captured["planes"])
              for ev in line if ev[0] in TRAIN_PARENT]
    for name in set(TRAIN_PARENT.values()) - {None}:
        for _, s0, e0 in [ev for ev in events if ev[0] == name]:
            inside = sum(e - s for n, s, e in events
                         if TRAIN_PARENT[n] == name and s0 <= s and e <= e0)
            assert 0 < inside <= e0 - s0


# -- (d) the GenLane loop keeps its ring for the request trees --------------
def test_genlane_loop_leaves_only_request_trees_in_its_ring():
    mx.random.seed(0)
    decoder = GenerativeDecoder(vocab_size=50, d_model=32, num_layers=2,
                                num_heads=4, max_prompt_tokens=12)
    gw = Gateway()
    try:
        gw.register_generator("lm", decoder, block_tokens=4, max_blocks=64,
                              max_new_tokens=8, max_decode_batch=4)
        tracing.reset()
        reqs = [gw.submit_generate("lm", np.arange(1, 4 + i), 6 + i)
                for i in range(2)]
        for r in reqs:
            r.result(timeout=120)
    finally:
        gw.close()
    ring = tracing.spans_snapshot()
    roots = [s for s in ring if s["parent"] is None]
    # one tree per request (what tailpath and the export read), and no
    # root per loop iteration pushing them out of the ring
    assert sorted(s["name"] for s in roots) == ["serving.generate"] * len(reqs)
    tokens = [s for s in ring if s["name"] == "generate.token"]
    assert len(tokens) == sum(len(r.token_spans) for r in reqs)
    assert {s["parent"] for s in tokens} == {s["span"] for s in roots}


# -- (e) the switch that was there ------------------------------------------
class _Counted:
    made = 0

    def __init__(self, name):
        type(self).made += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_sample_zero_records_nothing_and_builds_no_annotation(monkeypatch):
    step, _, _ = small_net()
    step()
    monkeypatch.setattr(tracing, "_annotation_cls", _Counted)
    _Counted.made = 0
    tracing.reset()
    tracing.set_sample(0)
    try:
        assert tracing.span("anything") is tracing.NOOP
        step()
        assert _Counted.made == 0
        assert tracing.spans_snapshot() == []
    finally:
        tracing.set_sample(1)
    step()
    recorded = len(tracing.spans_snapshot())
    assert recorded >= len(TRAIN_PARENT)
    assert _Counted.made >= recorded       # every span has its twin


# -- (g) a span's own thread: worked or blocked ------------------------------
def _spin(seconds):
    end = time.monotonic() + seconds
    while time.monotonic() < end:
        pass


def _tick_ns():
    """The thread clock's step: the least increment a spinning thread
    sees. Under a microsecond where the scheduler clock is fine, 10 ms
    where it ticks (``tracing/clock.py``)."""
    seen, last, end = [], time.thread_time_ns(), time.monotonic() + 0.2
    while len(seen) < 5 and time.monotonic() < end:
        now = time.thread_time_ns()
        if now != last:
            seen.append(now - last)
            last = now
    return min(seen)


def _cpu_share(body, seconds, tick_ns):
    tracing.reset()
    with tracing.span("probe"):
        body(seconds)
    rec, = tracing.spans_snapshot()
    assert 0 <= rec["cpu_ns"] <= rec["dur_ns"] + tick_ns
    return rec["cpu_ns"] / rec["dur_ns"]


@pytest.mark.parametrize("body,low,high", [(time.sleep, 0.0, 0.1),
                                           (_spin, 0.9, 1.1)],
                         ids=["sleep", "spin"])
def test_span_cpu_time_tells_work_from_waiting(body, low, high):
    """A thread that waits burns no CPU time, one that spins burns its
    whole length. Another process can take the core from a spin, so it
    has a few short tries and one has to come through whole."""
    tick = _tick_ns()
    seconds = max(0.02, 30 * tick / 1e9)
    shares = []
    for _ in range(20):
        shares.append(_cpu_share(body, seconds, tick))
        if low <= shares[-1] <= high:
            break
    assert low <= shares[-1] <= high, shares


def test_a_record_keeps_the_clocks_reading_where_it_ticks(monkeypatch):
    """One 10 ms tick falls inside a span a millisecond long: the record
    says 10 ms, more than its length. Bounding is for whoever sums."""
    ticks = iter([0, 10_000_000])
    monkeypatch.setattr(tracing.clock, "thread_cpu_ns", lambda: next(ticks))
    tracing.reset()
    with tracing.span("short"):
        time.sleep(0.001)
    rec, = tracing.spans_snapshot()
    assert rec["cpu_ns"] == 10_000_000 > rec["dur_ns"]


def test_a_span_finished_elsewhere_has_no_cpu_time():
    tracing.reset()
    tracing.record_span("remote", 7, 0, 100, 350)
    rec, = tracing.spans_snapshot()
    assert rec["dur_ns"] == 250 and rec["cpu_ns"] is None


def test_cpu_time_passes_through_the_trace_file_and_the_flight_view(
        tmp_path):
    tracing.reset()
    with tracing.span("here"):
        pass
    tracing.record_span("remote", 7, 0, 100, 350)
    path = str(tmp_path / "trace.json")
    tracing.export.write_trace(path)
    loaded = {s["name"]: s for s in tracing.export.load_trace(path)["spans"]}
    assert loaded["here"]["cpu_ns"] >= 0
    assert loaded["remote"]["cpu_ns"] is None
    assert len(tracing.export.chrome_events(loaded.values())) == 2
    recent = [s["name"] for t in tracing.flight.snapshot()["threads"]
              for s in t["recent"]]
    assert {"here", "remote"} <= set(recent)


@pytest.mark.parametrize("clock_fn", ["now_ns", "thread_cpu_ns"])
def test_sample_zero_reads_no_clock(monkeypatch, clock_fn):
    reads = []
    real = getattr(tracing.clock, clock_fn)
    monkeypatch.setattr(tracing.clock, clock_fn,
                        lambda: reads.append(1) or real())
    with tracing.span("sampled"):
        pass
    assert reads                    # the probe sees a span's reads
    del reads[:]
    tracing.set_sample(0)
    try:
        with tracing.span("unsampled") as sp:
            assert sp is tracing.NOOP
        assert tracing.record_span("remote", 7, 0, 100, 350) == 0
    finally:
        tracing.set_sample(1)
    assert reads == []


def test_step_spans_in_the_ring_carry_cpu_time(captured):
    tick = _tick_ns()
    by_id = {s["span"]: s for s in captured["ring"]}
    for rec in captured["ring"]:
        assert 0 <= rec["cpu_ns"] <= rec["dur_ns"] + tick, rec["name"]
        assert rec["cpu_ns"] > 0 or tick > 1_000_000, rec["name"]
        parent = by_id.get(rec["parent"])
        if parent is not None:      # one thread, one clock: a child's
            assert rec["cpu_ns"] <= parent["cpu_ns"]    # time is inside


# -- (f) names that do not change from process to process -------------------
_NAMES_SCRIPT = """
import glob, os, re, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.serving import Gateway
from mxnet_tpu.serving.generate import GenerativeDecoder

x = mx.nd.ones((2, 3))
# gluon numbers its blocks as they are built (hybridsequential0, 1, ...):
# the second process builds others first, and both build two nets
for _ in range(int(sys.argv[2])):
    gluon.nn.HybridSequential(), gluon.nn.Dense(2)
for prefix in (None, None, "encoder_"):
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(4, activation="relu"), gluon.nn.Dense(2))
    net.initialize()
    net.hybridize()
    net(x)
    net(x)
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
gw = Gateway()
gw.register_generator(
    "lm", GenerativeDecoder(vocab_size=20, d_model=16, num_layers=1,
                            num_heads=2, max_prompt_tokens=4),
    block_tokens=4, max_blocks=8, max_new_tokens=2, max_decode_batch=1)
gw.close()
names = set()
for path in glob.glob(os.path.join(sys.argv[1], "*")):
    m = re.match(r"module_\\d+\\.(jit_mx_\\w+?)\\.", os.path.basename(path))
    if m:
        names.add(m.group(1))
print("NAMES " + " ".join(sorted(names)))
"""


def _module_names(tmp_path, k):
    dump = tmp_path / ("dump%d" % k)
    env = dict(os.environ, PYTHONPATH=REPO)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_dump_to=%s "
                        "--xla_dump_hlo_module_re=jit_mx_.* "
                        "--xla_dump_hlo_as_text" % dump)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # a hit compiles nothing
    proc = subprocess.run([sys.executable, "-c", _NAMES_SCRIPT, str(dump),
                           str(3 * k)],
                          env=env, capture_output=True, text=True,
                          timeout=300, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("NAMES ")]
    return line[-1].split()[1:]


def test_program_names_are_the_same_in_two_fresh_processes(tmp_path):
    first, second = (_module_names(tmp_path, k) for k in range(2))
    assert first == second
    # a recorded call compiles its two programs and no plain forward
    assert first == ["jit_mx_decode", "jit_mx_encoder_eval",
                     "jit_mx_encoder_train_bwd", "jit_mx_encoder_train_fwd",
                     "jit_mx_hybridsequential_eval",
                     "jit_mx_hybridsequential_train_bwd",
                     "jit_mx_hybridsequential_train_fwd", "jit_mx_prefill"]
