"""Multi-tenant inference gateway tests (serving/): continuous
batching + max-wait bound, bucket-padding bit-identity vs direct
Predictor.forward, variant selection (bf16 + both int8 lowerings),
admission fast-reject, replica drain/redistribute, trace propagation,
telemetry families, the Predictor._build race fix, and the
perf_gate --serving self-test over the committed artifact."""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.serving import (Gateway, RejectedError, ServingError,
                               default_buckets, pad_batch, pick_bucket)
from mxnet_tpu.serving.batcher import Request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SERVING_ARTIFACT = os.path.join(REPO, "docs", "artifacts",
                                "SERVING_LAST_GOOD.json")


def tiny_mlp(seed=0, din=8, hidden=16, dout=4):
    rng = np.random.default_rng(seed)
    data = sym.var("data")
    h = sym.FullyConnected(data, sym.var("fc1_weight"),
                           sym.var("fc1_bias"), num_hidden=hidden,
                           name="fc1")
    a = sym.Activation(h, act_type="relu", name="act1")
    out = sym.FullyConnected(a, sym.var("fc2_weight"),
                             sym.var("fc2_bias"), num_hidden=dout,
                             name="fc2")
    args = {
        "fc1_weight": mx.nd.array(
            rng.normal(0, 0.5, (hidden, din)).astype(np.float32)),
        "fc1_bias": mx.nd.array(
            rng.normal(0, 0.5, (hidden,)).astype(np.float32)),
        "fc2_weight": mx.nd.array(
            rng.normal(0, 0.5, (dout, hidden)).astype(np.float32)),
        "fc2_bias": mx.nd.array(
            rng.normal(0, 0.5, (dout,)).astype(np.float32)),
    }
    return out, args, {}, (din,)


def tiny_cnn(seed=0):
    """Conv+BN+relu+fc: exercises BN folding + conv quantization."""
    rng = np.random.default_rng(seed)
    data = sym.var("data")
    c = sym.Convolution(data, name="conv0", kernel=(3, 3),
                        num_filter=4, pad=(1, 1))
    b = sym.BatchNorm(c, name="bn0")
    r = sym.Activation(b, act_type="relu")
    out = sym.FullyConnected(sym.Flatten(r), name="fc", num_hidden=3)
    args = {
        "conv0_weight": mx.nd.array(
            rng.normal(0, 0.3, (4, 2, 3, 3)).astype(np.float32)),
        "conv0_bias": mx.nd.array(np.zeros(4, np.float32)),
        "bn0_gamma": mx.nd.array(np.ones(4, np.float32)),
        "bn0_beta": mx.nd.array(np.zeros(4, np.float32)),
        "fc_weight": mx.nd.array(
            rng.normal(0, 0.3, (3, 4 * 6 * 6)).astype(np.float32)),
        "fc_bias": mx.nd.array(np.zeros(3, np.float32)),
    }
    aux = {
        "bn0_moving_mean": mx.nd.array(np.zeros(4, np.float32)),
        "bn0_moving_var": mx.nd.array(np.ones(4, np.float32)),
    }
    return out, args, aux, (2, 6, 6)


def _x(feature, rows=1, seed=1):
    return np.random.default_rng(seed).normal(
        0, 1, (rows,) + tuple(feature)).astype(np.float32)


# -- bucket / padding units --------------------------------------------------
def test_default_buckets_and_pick():
    assert default_buckets(8) == (1, 2, 4, 8)
    assert default_buckets(12) == (1, 2, 4, 8, 12)
    assert default_buckets(1) == (1,)
    assert pick_bucket((1, 2, 4, 8), 3) == 4
    assert pick_bucket((1, 2, 4, 8), 8) == 8
    with pytest.raises(mx.MXNetError):
        pick_bucket((1, 2), 3)


def test_pad_batch_layout():
    ctx = (0, 0)
    r1 = Request("m", "fp32", np.full((2, 3), 1.0, np.float32), ctx)
    r2 = Request("m", "fp32", np.full((1, 3), 2.0, np.float32), ctx)
    padded, rows = pad_batch([r1, r2], 4, (3,), np.float32)
    assert rows == 3 and padded.shape == (4, 3)
    assert (padded[:2] == 1.0).all() and (padded[2] == 2.0).all()
    assert (padded[3] == 0.0).all()


# -- gateway core ------------------------------------------------------------
def test_gateway_matches_direct_predictor_bitwise():
    """Padding to a bucket must not perturb live rows AT ALL: gateway
    output == direct Predictor.forward of the same rows in the same
    bucket shape, bitwise (the serving_bench divergence stage's tier-1
    twin). The natural shape is another XLA program, whose reductions
    may round differently in the last place — that is not padding's
    doing, so it is not what this compares."""
    symbol, args, aux, feature = tiny_cnn()
    gw = Gateway()
    try:
        gw.register("cnn", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1, 4),
                    max_wait_ms=0.0)
        for rows, bucket in ((1, 1), (3, 4)):
            x = _x(feature, rows)
            got = gw.infer("cnn", x)
            pred = mx.predictor.Predictor(
                symbol, args, aux, {"data": (bucket,) + feature})
            # garbage, not zeros, in the pad rows: live rows must not
            # depend on what sits beside them
            padded = np.concatenate(
                [x, np.full((bucket - rows,) + feature, 7.0, x.dtype)])
            want = pred.forward(data=padded)
            assert len(got) == len(want)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w[:rows])
    finally:
        gw.close()


def test_coalescing_and_max_wait_bound():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("mlp", symbol, args, aux,
                    input_shapes={"data": feature},
                    buckets=(1, 2, 4, 8), max_wait_ms=100.0)
        gw.infer("mlp", _x(feature))          # warm every... bucket 1
        reg = mx.telemetry.registry()
        b0 = reg.value("mx_serving_batches_total", model="mlp",
                       variant="fp32")
        n = 6
        reqs = [gw.submit("mlp", _x(feature, seed=i))
                for i in range(n)]
        outs = [r.result(10.0) for r in reqs]
        assert all(o[0].shape == (1, 4) for o in outs)
        batches = reg.value("mx_serving_batches_total", model="mlp",
                            variant="fp32") - b0
        # six submissions against one replica: the hold window must
        # coalesce them into fewer executions than requests
        assert 1 <= batches < n
        # max-wait BOUNDS latency: a lone request dispatches within
        # hold + execution, not when a bucket fills
        t0 = time.perf_counter()
        gw.infer("mlp", _x(feature), timeout=10.0)
        lone_s = time.perf_counter() - t0
        assert lone_s < 2.0, "lone request waited for a full bucket"
        # and the latency-optimal end: zero hold dispatches immediately
        gw.register("mlp0", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1, 8),
                    max_wait_ms=0.0)
        gw.infer("mlp0", _x(feature))
        t0 = time.perf_counter()
        gw.infer("mlp0", _x(feature), timeout=10.0)
        assert time.perf_counter() - t0 < 1.0
    finally:
        gw.close()


def test_coalesced_results_match_individual():
    """Coalesced execution returns each request ITS rows — results
    equal the per-request direct forward."""
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("mlp", symbol, args, aux,
                    input_shapes={"data": feature},
                    buckets=(1, 2, 4, 8), max_wait_ms=50.0)
        gw.infer("mlp", _x(feature))
        xs = [_x(feature, rows=1 + (i % 2), seed=10 + i)
              for i in range(5)]
        reqs = [gw.submit("mlp", x) for x in xs]
        outs = [r.result(10.0) for r in reqs]
        for x, out in zip(xs, outs):
            pred = mx.predictor.Predictor(
                symbol, args, aux, {"data": x.shape})
            # ulp tolerance: XLA CPU picks a different dot kernel per
            # batch size, so a rows=2 request padded into bucket 4 can
            # differ from the rows=2 direct program in the last bit
            # (test_gateway_matches_direct_predictor_bitwise pins the
            # cases where the kernels DO agree, and the committed
            # serving artifact pins them for the bench model)
            np.testing.assert_allclose(out[0], pred.forward(data=x)[0],
                                       rtol=1e-5, atol=1e-6)
    finally:
        gw.close()


def test_variant_selection_bf16_and_int8_lowerings():
    symbol, args, aux, feature = tiny_cnn()
    calib = _x(feature, rows=16, seed=3)
    gw = Gateway()
    try:
        gw.register("q", symbol, args, aux,
                    input_shapes={"data": feature},
                    variants=("fp32", "bf16", "int8"),
                    calib_data=calib, buckets=(1, 2),
                    max_wait_ms=0.0, int8_lowering="native")
        x = _x(feature, rows=2, seed=4)
        f32 = gw.infer("q", x)[0]
        bf = gw.infer("q", x, variant="bf16")[0]
        i8 = gw.infer("q", x, variant="int8")[0]
        # bf16: reduced precision, fp32-typed replies, close to fp32
        assert bf.dtype == np.float32
        assert not np.array_equal(bf, f32)
        np.testing.assert_allclose(bf, f32, atol=0.15, rtol=0.1)
        # int8 native: the QUANTIZED GRAPH executed (different, close)
        assert not np.array_equal(i8, f32)
        scale = max(np.abs(f32).max(), 1.0)
        assert np.abs(i8 - f32).max() < 0.2 * scale
        assert gw.stats()["q"]["int8_lowering"] == "native"
        # per-variant accounting
        reg = mx.telemetry.registry()
        for variant in ("fp32", "bf16", "int8"):
            assert reg.value("mx_serving_requests_total", model="q",
                             variant=variant) >= 1
        # dequant lowering: weight-only realization — fp32-speed
        # program, still carries the quantization's accuracy effect
        gw.register("qd", symbol, args, aux,
                    input_shapes={"data": feature}, variants=("int8",),
                    calib_data=calib, buckets=(1, 2),
                    max_wait_ms=0.0, int8_lowering="dequant")
        dq = gw.infer("qd", x, variant="int8")[0]
        assert not np.array_equal(dq, f32)
        assert np.abs(dq - f32).max() < 0.2 * scale
        assert gw.stats()["qd"]["int8_lowering"] == "dequant"
    finally:
        gw.close()


def test_unknown_model_variant_and_shape_errors():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1, 2),
                    max_wait_ms=0.0)
        with pytest.raises(ServingError):
            gw.infer("nope", _x(feature))
        with pytest.raises(ServingError):
            gw.infer("m", _x(feature), variant="int8")
        with pytest.raises(ServingError):
            gw.infer("m", np.zeros((1, 5), np.float32))
        with pytest.raises(ServingError):
            gw.infer("m", _x(feature, rows=3))   # > largest bucket
        with pytest.raises(ServingError):
            gw.register("m", symbol, args, aux,
                        input_shapes={"data": feature})
    finally:
        gw.close()


# -- admission control -------------------------------------------------------
def _block_replica(gw, model, idx=0):
    """Test seam: wrap one replica's executor so batches park on an
    Event — deterministic overload without timing games."""
    rep = gw.registry.get(model).replicas[idx]
    release = threading.Event()
    orig = rep.variant_set.run

    def blocked(variant, batch):
        release.wait(20.0)
        return orig(variant, batch)

    rep.variant_set.run = blocked
    return release, orig


def test_admission_queue_full_fast_reject():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0, max_queue=2)
        gw.infer("m", _x(feature))            # warm, then block
        release, _ = _block_replica(gw, "m")
        first = gw.submit("m", _x(feature))   # executing (parked)
        time.sleep(0.05)                      # replica takes it
        q1 = gw.submit("m", _x(feature))
        q2 = gw.submit("m", _x(feature))
        t0 = time.perf_counter()
        with pytest.raises(RejectedError) as ei:
            gw.submit("m", _x(feature))
        reject_s = time.perf_counter() - t0
        assert ei.value.reason == "queue_full"
        assert reject_s < 0.1, "fast-reject must not block"
        reg = mx.telemetry.registry()
        assert reg.value("mx_serving_rejected_total", model="m",
                         reason="queue_full") >= 1
        release.set()
        for r in (first, q1, q2):
            assert r.result(10.0)[0].shape == (1, 4)
    finally:
        gw.close()


def test_admission_slo_budget_reject():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0, max_queue=1000, slo_ms=1.0)
        for _ in range(3):                    # seed the EWMA estimates
            gw.infer("m", _x(feature))
        release, _ = _block_replica(gw, "m")
        pending = [gw.submit("m", _x(feature))]
        time.sleep(0.05)
        # backlog >> what a 1ms budget can drain at the observed rate
        rejected = None
        for _ in range(50):
            try:
                pending.append(gw.submit("m", _x(feature)))
            except RejectedError as e:
                rejected = e
                break
        assert rejected is not None and rejected.reason == "slo"
        release.set()
        for r in pending:
            r.result(10.0)
    finally:
        gw.close()


# -- replicas ----------------------------------------------------------------
def test_replica_failure_drains_and_redistributes():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        # buckets=(1,): every batch is one request, so the blocked
        # replica parks on its first take instead of scooping the
        # whole burst — the failing replica is guaranteed work
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0, replicas=2)
        gw.infer("m", _x(feature))
        # park replica 1, make replica 0 fail its next execution
        release, _ = _block_replica(gw, "m", idx=1)
        rep0 = gw.registry.get("m").replicas[0]
        orig0 = rep0.variant_set.run
        rep0.variant_set.run = lambda v, b: (_ for _ in ()).throw(
            RuntimeError("injected replica fault"))
        # submit until the fault lands: either replica may take any
        # one batch, but replica 1 can only absorb ONE (it parks), so
        # replica 0 fails within a couple of submissions
        reqs = []
        deadline = time.time() + 10
        while rep0.healthy and time.time() < deadline:
            reqs.append(gw.submit("m", _x(feature, seed=len(reqs))))
            time.sleep(0.02)
        assert not rep0.healthy, "fault never drained replica 0"
        release.set()                         # replica 1 serves all
        for r in reqs:
            assert r.result(10.0)[0].shape == (1, 4)
        assert gw.health()["m"] == [False, True]
        reg = mx.telemetry.registry()
        assert reg.value("mx_serving_replica_failures_total",
                         model="m") >= 1
        assert reg.value("mx_serving_replica_healthy", model="m",
                         replica="0") == 0
        # heal the executor; check_health revives the drained replica
        rep0.variant_set.run = orig0
        states = gw.check_health("m", revive=True)
        assert states["m"] == [True, True]
        assert gw.infer("m", _x(feature))[0].shape == (1, 4)
    finally:
        gw.close()


def test_all_replicas_down_rejects_no_replica():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0)
        gw.infer("m", _x(feature))
        rep = gw.registry.get("m").replicas[0]
        rep.variant_set.run = lambda v, b: (_ for _ in ()).throw(
            RuntimeError("boom"))
        # the failing request errors (no survivor to redistribute to)
        req = gw.submit("m", _x(feature))
        with pytest.raises(ServingError):
            req.result(10.0)
        with pytest.raises(RejectedError) as ei:
            gw.submit("m", _x(feature))
        assert ei.value.reason == "no_replica"
    finally:
        gw.close()


def test_replica_degrade_to_fewer_devices(caplog):
    import logging

    import jax
    from mxnet_tpu.parallel import mesh as mesh_mod
    mesh_mod._reset_degrade_warnings()
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway(devices=[jax.local_devices()[0]])
    try:
        with caplog.at_level(logging.WARNING,
                             logger="mxnet_tpu.serving.gateway"):
            gw.register("m", symbol, args, aux,
                        input_shapes={"data": feature}, buckets=(1,),
                        max_wait_ms=0.0, replicas=3)
        assert "degrading" in caplog.text
        st = gw.stats()["m"]
        assert len(st["replicas"]) == 3
        assert st["degraded"] is True
        assert len({r["device"] for r in st["replicas"]}) == 1
        assert gw.infer("m", _x(feature))[0].shape == (1, 4)
        # satellite: the SAME (ask, devices) wrap warns exactly once —
        # a second registration (an autoscaler's re-ask) is silent
        caplog.clear()
        with caplog.at_level(logging.WARNING,
                             logger="mxnet_tpu.serving.gateway"):
            gw.register("m2", symbol, args, aux,
                        input_shapes={"data": feature}, buckets=(1,),
                        max_wait_ms=0.0, replicas=3)
        assert "degrading" not in caplog.text
        assert gw.stats()["m2"]["degraded"] is True
    finally:
        gw.close()


def test_probe_drain_and_revive_does_not_leak_threads():
    """A probe-drained replica's scheduler stays parked in take_batch;
    revive spawns a FRESH generation and the stale lane retires on its
    next wake instead of double-serving — no thread accumulates across
    drain→revive cycles."""
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0)
        gw.infer("m", _x(feature))
        rep = gw.registry.get("m").replicas[0]
        orig = rep.variant_set.run
        rep.variant_set.run = lambda v, b: (_ for _ in ()).throw(
            RuntimeError("probe fault"))
        assert gw.check_health("m")["m"] == [False]
        thread_before = rep._thread
        assert thread_before.is_alive()       # parked in take_batch
        rep.variant_set.run = orig
        assert gw.check_health("m", revive=True)["m"] == [True]
        # requests serve through the revived lane, and the stale
        # generation retires once woken (hand-back, never a second
        # serving lane)
        for i in range(3):
            assert gw.infer("m", _x(feature, seed=i))[0].shape == (1, 4)
        thread_before.join(5.0)
        assert not thread_before.is_alive(), "stale lane still running"
        assert rep._thread is not thread_before
        assert rep._thread.is_alive()
    finally:
        gw.close()


def test_concurrent_last_replica_failures_fail_cleanly():
    """Both replicas failing in the same window must not strand
    requeued requests in a queue nobody serves — the _redistribute
    re-check drain-fails them."""
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0, replicas=2)
        gw.infer("m", _x(feature))
        for rep in gw.registry.get("m").replicas:
            rep.variant_set.run = lambda v, b: (_ for _ in ()).throw(
                RuntimeError("double fault"))
        reqs = []
        for i in range(4):
            try:
                reqs.append(gw.submit("m", _x(feature, seed=i)))
            except RejectedError as e:
                # both lanes already died: fast-reject is the correct
                # answer for late arrivals
                assert e.reason == "no_replica"
        assert reqs, "no request was admitted before the lanes died"
        for r in reqs:
            with pytest.raises(ServingError):
                r.result(10.0)                # clean error, no hang
        assert gw.health()["m"] == [False, False]
    finally:
        gw.close()


def test_register_rejects_zero_replicas():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        with pytest.raises(ServingError):
            gw.register("m", symbol, args, aux,
                        input_shapes={"data": feature}, replicas=0)
    finally:
        gw.close()


def test_close_fails_pending_cleanly():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    gw.register("m", symbol, args, aux,
                input_shapes={"data": feature}, buckets=(1,),
                max_wait_ms=0.0)
    gw.infer("m", _x(feature))
    release, _ = _block_replica(gw, "m")
    taken = gw.submit("m", _x(feature))
    time.sleep(0.05)
    queued = gw.submit("m", _x(feature))
    closer = threading.Thread(target=gw.close)
    closer.start()
    time.sleep(0.1)
    release.set()
    closer.join(15.0)
    assert not closer.is_alive()
    # the in-flight batch finished; the queued one failed cleanly
    assert taken.result(5.0)[0].shape == (1, 4)
    with pytest.raises(ServingError):
        queued.result(5.0)
    with pytest.raises(ServingError):
        gw.register("late", symbol, args, aux,
                    input_shapes={"data": feature})


# -- observability -----------------------------------------------------------
def test_trace_id_propagates_through_span_chain():
    from mxnet_tpu import tracing
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("m", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1,),
                    max_wait_ms=0.0)
        gw.infer("m", _x(feature))            # warm (own trace)
        with tracing.span("client_call") as client:
            trace_id = client.trace_id
            gw.infer("m", _x(feature))
        spans = tracing.spans_snapshot()
        mine = [s for s in spans if s["trace"] == trace_id]
        names = {s["name"] for s in mine}
        assert {"client_call", "serving.request", "serving.queue",
                "serving.batch", "serving.execute",
                "serving.reply"} <= names
        root = next(s for s in mine if s["name"] == "serving.request")
        # the request root parents to the client's span; every stage
        # span parents to the root — one tree per request
        assert root["parent"] == client.span_id
        for name in ("serving.queue", "serving.batch",
                     "serving.execute", "serving.reply"):
            s = next(x for x in mine if x["name"] == name)
            assert s["parent"] == root["span"]
        ex = next(s for s in mine if s["name"] == "serving.execute")
        assert ex["attrs"]["bucket"] == 1
    finally:
        gw.close()


def test_new_context_respects_fractional_sampling(monkeypatch):
    """Serving mints a trace per request via new_context — it must
    roll the same MXTPU_TRACE_SAMPLE dice a root span() does, or a 1%
    setting still traces 100% of requests."""
    from mxnet_tpu import tracing

    class FakeRng:
        def __init__(self, roll):
            self.roll = roll

        def random(self):
            return self.roll

        def getrandbits(self, n):
            return 12345

    old = tracing._SAMPLE[0]
    try:
        tracing.set_sample(0.0)
        assert tracing.new_context() == (0, 0)
        tracing.set_sample(1.0)
        assert tracing.new_context()[0] != 0
        tracing.set_sample(0.5)
        monkeypatch.setattr(tracing, "_rng", FakeRng(0.9))
        assert tracing.new_context() == (0, 0)     # lost the roll
        monkeypatch.setattr(tracing, "_rng", FakeRng(0.1))
        assert tracing.new_context()[0] != 0       # won the roll
    finally:
        tracing.set_sample(old)


def test_serving_telemetry_families_registered_and_nonzero():
    symbol, args, aux, feature = tiny_mlp()
    gw = Gateway()
    try:
        gw.register("tm", symbol, args, aux,
                    input_shapes={"data": feature}, buckets=(1, 2),
                    max_wait_ms=0.0)
        for i in range(4):
            gw.infer("tm", _x(feature, seed=i))
        snap = mx.telemetry.snapshot()["metrics"]
        for fam in ("mx_serving_requests_total",
                    "mx_serving_batches_total",
                    "mx_serving_queue_depth",
                    "mx_serving_batch_rows",
                    "mx_serving_latency_seconds",
                    "mx_serving_replica_healthy"):
            assert fam in snap, fam
        reg = mx.telemetry.registry()
        assert reg.value("mx_serving_requests_total", model="tm",
                         variant="fp32") >= 4
        lat = snap["mx_serving_latency_seconds"]["series"]
        stages = {s["labels"]["stage"] for s in lat
                  if s["labels"]["model"] == "tm"}
        assert {"queue", "batch", "execute", "e2e"} <= stages
        e2e = next(s for s in lat if s["labels"]["model"] == "tm"
                   and s["labels"]["stage"] == "e2e")
        assert e2e["count"] >= 4 and e2e["sum"] > 0
    finally:
        gw.close()


# -- predictor race fix ------------------------------------------------------
def test_predictor_concurrent_first_forward_builds_once():
    symbol, args, aux, feature = tiny_mlp()
    pred = mx.predictor.Predictor(symbol, args, aux,
                                  {"data": (1,) + feature})
    builds = []
    orig_build = pred._build

    def counting_build():
        builds.append(threading.get_ident())
        time.sleep(0.02)                      # widen the race window
        orig_build()

    pred._build = counting_build
    x = _x(feature)
    want = None
    outs = [None] * 8
    errs = []
    barrier = threading.Barrier(8)

    def fire(i):
        try:
            barrier.wait(5.0)
            outs[i] = pred.forward(data=x)
        except Exception as e:  # noqa: BLE001 — collected for assert
            errs.append(e)

    threads = [threading.Thread(target=fire, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(15.0)
    assert not errs
    assert len(builds) == 1, "lazy _build ran %d times" % len(builds)
    want = mx.predictor.Predictor(
        symbol, args, aux, {"data": (1,) + feature}).forward(data=x)
    for out in outs:
        assert out is not None
        np.testing.assert_array_equal(out[0], want[0])


def test_predictor_explicit_device_pin():
    import jax
    symbol, args, aux, feature = tiny_mlp()
    dev = jax.local_devices()[-1]
    pred = mx.predictor.Predictor(symbol, args, aux,
                                  {"data": (1,) + feature},
                                  device=dev)
    out = pred.forward(data=_x(feature))
    assert out[0].shape == (1, 4)
    assert all(v.devices() == {dev} for v in pred._param_vals)


# -- perf gate / artifact ----------------------------------------------------
def test_perf_gate_serving_selftest_over_committed_artifact(capsys):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import perf_gate
    # the COMMITTED artifact must meet the strict contract: >=3x gain,
    # int8 <= fp32 (1.0, no noise slack), bitwise-zero divergence
    rc = perf_gate.main([SERVING_ARTIFACT, "--serving",
                         "--serving-int8-max", "1.0"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "batching gain" in out and "PASS" in out


def test_perf_gate_serving_rejects_regressions():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import perf_gate
    with open(SERVING_ARTIFACT, encoding="utf-8") as f:
        good = json.load(f)

    bad = json.loads(json.dumps(good))
    bad["ratios"]["batching_gain"] = 1.2
    rc, msgs = perf_gate.gate_serving(bad, good)
    assert rc == 1 and any("batching gain" in m for m in msgs)

    bad = json.loads(json.dumps(good))
    bad["divergence"] = {"max_abs_fp32": 1e-6, "bitwise_equal": False}
    rc, msgs = perf_gate.gate_serving(bad, good)
    assert rc == 1 and any("diverges" in m for m in msgs)

    bad = json.loads(json.dumps(good))
    bad["ratios"]["int8_vs_fp32_bs1"] = 1.5
    rc, _ = perf_gate.gate_serving(bad, good)
    assert rc == 1

    bad = json.loads(json.dumps(good))
    bad["stages"]["gateway_concurrent_fp32"]["req_per_s"] /= 10.0
    rc, _ = perf_gate.gate_serving(bad, good)
    assert rc == 1

    bad = json.loads(json.dumps(good))
    del bad["stages"]["dispatch_overhead_bs1"]
    rc, msgs = perf_gate.gate_serving(bad, good)
    assert rc == 1 and any("dispatch" in m for m in msgs)

    # a collapsed concurrent stage (no completed requests -> no
    # p99_ms) must fail the latency ceiling, not skip it
    bad = json.loads(json.dumps(good))
    del bad["stages"]["gateway_concurrent_fp32"]["p99_ms"]
    rc, msgs = perf_gate.gate_serving(bad, good)
    assert rc == 1 and any("no p99_ms" in m for m in msgs)

    rc, _ = perf_gate.gate_serving({"tool": "other"}, good)
    assert rc == 2


def test_committed_serving_artifact_meets_contract():
    """The acceptance criteria live IN the committed artifact: >=3x
    batching gain at bounded p99, int8 bs=1 <= fp32 bs=1, zero
    divergence, dispatch-overhead number present."""
    with open(SERVING_ARTIFACT, encoding="utf-8") as f:
        doc = json.load(f)
    assert doc["tool"] == "serving_bench" and doc["version"] == 1
    assert doc["ratios"]["batching_gain"] >= 3.0
    assert doc["ratios"]["int8_vs_fp32_bs1"] <= 1.0
    assert doc["divergence"]["max_abs_fp32"] == 0.0
    assert doc["divergence"]["bitwise_equal"] is True
    conc = doc["stages"]["gateway_concurrent_fp32"]
    assert conc["p99_ms"] < 10 * conc["p50_ms"] + 100, \
        "p99 unbounded relative to p50"
    disp = doc["stages"]["dispatch_overhead_bs1"]
    assert disp["python_dispatch_ms"] >= 0
    assert doc["stages"]["gateway_bs1_int8_native"]["p50_ms"] > 0
    # dated artifact + last-good tier are both committed
    import glob
    dated = glob.glob(os.path.join(REPO, "docs", "artifacts",
                                   "serving_bench_*.json"))
    assert dated, "no dated serving_bench artifact committed"


def test_dequantize_offline_params_roundtrip():
    """contrib helper behind the dequant lowering: the int8 triple
    folds back through its symmetric scale to within one quantization
    step of the original weight."""
    from mxnet_tpu.contrib.quantization import (
        INT8_RANGE, dequantize_offline_params)
    rng = np.random.default_rng(0)
    w = rng.normal(0, 0.5, (4, 3)).astype(np.float32)
    amax = float(np.abs(w).max())
    q = np.clip(np.rint(w * (INT8_RANGE / amax)),
                -INT8_RANGE, INT8_RANGE).astype(np.int8)
    qarg = {"fc_weight_int8": mx.nd.array(q),
            "fc_weight_int8_min": mx.nd.array(
                np.array(-amax, np.float32)),
            "fc_weight_int8_max": mx.nd.array(
                np.array(amax, np.float32)),
            "unrelated": mx.nd.array(np.ones(2, np.float32))}
    back = dequantize_offline_params(qarg)
    assert set(back) == {"fc_weight"}
    step = amax / INT8_RANGE
    np.testing.assert_allclose(back["fc_weight"].asnumpy(), w,
                               atol=step * 0.51)


def test_replica_devices_helper():
    import jax

    from mxnet_tpu.parallel.mesh import replica_devices
    devs = jax.local_devices()
    picked, degraded = replica_devices(2)
    assert len(picked) == 2 and not degraded
    picked, degraded = replica_devices(3, devices=devs[:1])
    assert degraded and len(picked) == 3
    assert all(d == devs[0] for d in picked)


def test_serving_env_vars_registered():
    from mxnet_tpu import libinfo
    with open(os.path.join(REPO, "docs", "env_vars.md"),
              encoding="utf-8") as f:
        docs = f.read()
    for var in ("MXTPU_SERVING_MAX_WAIT_MS", "MXTPU_SERVING_MAX_QUEUE",
                "MXTPU_SERVING_SLO_MS", "MXTPU_SERVING_REPLICAS",
                "MXTPU_SERVING_HEALTH_SEC"):
        assert var in libinfo._ENV_VARS, var
        assert var in docs, var
