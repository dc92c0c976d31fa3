"""Model-health plane tests (nonfinite sentry, norm/loss telemetry,
first-NaN postmortem, drift fingerprints — profiling/health.py).

Covers the acceptance criteria chip-free on the CPU backend:

- the sentry accumulates lazy device scalars and trips on injected
  NaNs at every seam (executor forward/backward, Trainer gradients,
  optimizer updater, sharded train step),
- an injected-NaN training run (poisoned lr / crafted graph) produces
  a postmortem artifact naming the exact first offending op via
  named-scope attribution, end-to-end,
- loss EWMA z-score spike + plateau anomaly detection,
- drift fingerprints: deterministic, order-independent, value- and
  name-sensitive; consistency.run_sweep stamps per-op rows,
- the rebuilt Monitor adds zero syncs to an armed training step and
  folds its whole interval in ONE batched device_get,
- enabled-vs-disabled Trainer.step process-CPU overhead < 5%
  (the PR 4/5 budget),
- health_report CLI over the committed health-bearing artifact;
  chrome-trace counter track; mxlint MXL002 over every instrumented
  file.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, sym
from mxnet_tpu.profiling import health
from mxnet_tpu.telemetry import export, metrics

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEALTH_LAST_GOOD = os.path.join(REPO, "docs", "artifacts",
                                "HEALTH_LAST_GOOD.json")
NAN_EXAMPLE = os.path.join(REPO, "docs", "artifacts",
                           "NAN_POSTMORTEM_EXAMPLE.json")


@pytest.fixture(autouse=True)
def _fresh_health(tmp_path, monkeypatch):
    """Every test gets clean sentry state, a warn policy, and a
    private postmortem path."""
    monkeypatch.setenv("MXTPU_HEALTH_DUMP_PATH",
                       str(tmp_path / "pm.json"))
    health.reset()
    health.set_enabled(True)
    health.set_norms_enabled(True)
    yield
    health.reset()
    health.set_enabled(True)


def _pm_path():
    return os.environ["MXTPU_HEALTH_DUMP_PATH"]


# ------------------------------------------------------------- sentry
def test_sentry_clean_tree_stays_clean():
    import jax.numpy as jnp
    health.check("unit", [jnp.ones((4,)), jnp.zeros((2, 2))])
    doc = health.flush()
    assert doc["sentry"]["verdict"] == "clean"
    assert doc["sentry"]["nonfinite_total"] == 0


def test_sentry_counts_nan_and_inf_per_source():
    import jax.numpy as jnp
    health.check("a", [jnp.array([1.0, float("nan")])])
    health.check("b", [jnp.array([float("inf"), float("-inf")])])
    health.check("a", [jnp.array([float("nan")])])
    doc = health.flush()
    assert doc["sentry"]["verdict"] == "nonfinite"
    assert doc["sentry"]["by_source"] == {"a": 2, "b": 2}
    assert doc["sentry"]["first_trip"]["source"] in ("a", "b")
    assert os.path.exists(_pm_path())


def test_sentry_ignores_integer_leaves():
    import jax.numpy as jnp
    health.check("ints", [jnp.arange(4)])
    doc = health.flush()
    # an all-integer tree records nothing at all
    assert doc["sentry"]["by_source"] == {}


def test_sentry_disabled_is_noop():
    import jax.numpy as jnp
    health.set_enabled(False)
    health.check("unit", [jnp.array([float("nan")])])
    health.observe_loss(float("nan"))
    assert health.step_boundary("t") is None
    doc = health.snapshot_doc()
    assert doc["sentry"]["verdict"] == "disabled"
    assert doc["sentry"]["nonfinite_total"] == 0


def test_fold_lag_defers_reads():
    """Buckets fold only >= _FOLD_LAG boundaries after dispatch."""
    import jax.numpy as jnp
    health.check("lagged", [jnp.array([float("nan")])])
    for _ in range(health._FOLD_LAG):
        health.step_boundary("t")
        # not yet folded: the bucket is younger than the lag
    assert health.snapshot_doc(fold=False)["sentry"][
        "nonfinite_total"] == 0
    health.step_boundary("t")
    assert health.snapshot_doc(fold=False)["sentry"][
        "nonfinite_total"] == 1


def test_raise_policy_raises_at_boundary_not_at_seam():
    import jax.numpy as jnp
    health.set_enabled("raise")
    health.check("unit", [jnp.array([float("nan")])])  # must not raise
    with pytest.raises(health.NonfiniteError) as ei:
        for _ in range(health._FOLD_LAG + 1):
            health.step_boundary("t")
    assert "unit" in str(ei.value)
    assert ei.value.postmortem is not None


# -------------------------------------------- first-NaN localization
def _poisoned_executor(batch=2, grad_req="null"):
    """data -> fc -> log (NaN born here on negative fc outputs) ->
    relu; weights force negatives so log produces NaNs mid-graph."""
    data = sym.var("data")
    fc = sym.FullyConnected(data, name="fc", num_hidden=4)
    lg = sym.log(fc, name="poison_log")
    out = sym.Activation(lg, name="relu", act_type="relu")
    return out.bind(args={
        "data": nd.ones((batch, 3)),
        "fc_weight": nd.array(-np.ones((4, 3), np.float32)),
        "fc_bias": nd.zeros((4,))}, grad_req=grad_req)


def test_executor_forward_postmortem_names_first_op_end_to_end():
    ex = _poisoned_executor()
    ex.forward()
    doc = health.flush()
    assert doc["sentry"]["first_trip"]["source"] == "executor_forward"
    pm = json.load(open(_pm_path()))
    assert pm["kind"] == "nan_postmortem"
    first = pm["first_op"]
    # the exact first offending op, through named-scope attribution
    assert first["node"] == "poison_log"
    assert first["op"] == "log"
    assert first["named_scope"] == "mx.log"
    # binary search: log2(n) probes, not n transfers
    assert first["probes"] <= first["internals"].bit_length() + 1
    assert first["output"]["nonfinite"] > 0
    # input stats name the producer and show it was finite
    assert first["inputs"][0]["name"] == "fc"
    assert first["inputs"][0]["nonfinite"] == 0
    # resume vocabulary present
    assert "mx_key" in pm["rng"]
    assert "flight" in pm


def test_localizer_finds_first_not_any():
    """Two nonfinite producers: the TOPO-FIRST one is named."""
    data = sym.var("data")
    lg1 = sym.log(data, name="first_bad")     # log(-1) = nan
    lg2 = sym.log(lg1, name="second_bad")
    ex = lg2.bind(args={"data": nd.array(
        -np.ones((2, 2), np.float32))}, grad_req="null")
    ex.forward()
    health.flush()
    pm = json.load(open(_pm_path()))
    assert pm["first_op"]["node"] == "first_bad"


def test_backward_born_nan_attributes_seam():
    """sqrt'(0) = inf appears only in backward: forward internals are
    finite, the artifact records the seam and first_op null."""
    data = sym.var("data")
    sq = sym.sqrt(data, name="sq")
    ex = sq.bind(args={"data": nd.zeros((2, 2))}, grad_req="write")
    ex.forward(is_train=True)
    ex.backward()
    health.flush()
    pm = json.load(open(_pm_path()))
    assert pm["source"] == "executor_backward"
    assert pm.get("first_op") is None


def _tiny_fit(num_epoch=2, batch=16, n=64, feat=8, out=4, lr=0.05,
              clock=time.perf_counter, feed_loss=False):
    net = gluon.nn.Dense(out)
    net.initialize(force_reinit=True)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr})
    rs = np.random.RandomState(7)
    X = rs.rand(n, feat).astype("float32")
    Y = rs.rand(n, out).astype("float32")
    it = mx.io.NDArrayIter(X, Y, batch_size=batch)
    loss_fn = gluon.loss.L2Loss()
    t0 = clock()
    for _ in range(num_epoch):
        it.reset()
        for b in it:
            with autograd.record():
                loss = loss_fn(net(b.data[0]), b.label[0])
            loss.backward()
            if feed_loss:
                health.observe_loss(loss.mean())
            trainer.step(batch)
    return clock() - t0


def test_poisoned_lr_training_run_trips_and_raises():
    """The acceptance scenario: a poisoned lr blows the weights to
    inf/NaN mid-run; the sentry trips at a training seam, the
    postmortem lands, and MXTPU_HEALTH=raise surfaces a typed error
    from Trainer.step."""
    health.set_enabled("raise")
    with pytest.raises(health.NonfiniteError):
        _tiny_fit(num_epoch=30, lr=1e30)
    assert os.path.exists(_pm_path())
    pm = json.load(open(_pm_path()))
    src = pm["source"]
    assert src.startswith(("optimizer/", "trainer_grad",
                           "trainer_param", "executor_")), src
    # the ranked grad-norm table rode along
    assert "grad_norms" in pm


# -------------------------------------------------- telemetry & spans
def test_trainer_emits_health_telemetry_and_span_attrs():
    metrics.registry().reset()
    _tiny_fit(feed_loss=True)
    doc = health.flush()
    assert doc["sentry"]["verdict"] == "clean"
    assert doc["loss"]["ewma"] is not None
    groups = doc["norms"]["by_group"]
    assert groups, "no per-group norms recorded"
    g = next(iter(groups.values()))
    assert g["weight_norm"] > 0 and g["grad_norm"] > 0
    assert 0 < g["update_ratio"] < 10
    snap = export.snapshot()["metrics"]
    for fam in ("mx_health_grad_norm", "mx_health_weight_norm",
                "mx_health_grad_norm_group", "mx_health_update_ratio",
                "mx_health_loss_ewma", "mx_health_update_to_weight"):
        assert any(True for _ in snap[fam]["series"]), fam
    from mxnet_tpu import tracing
    spans = [s for s in tracing.spans_snapshot()
             if s["name"] == "trainer_step"]
    assert spans
    attrs = spans[-1]["attrs"]
    assert attrs.get("health_nonfinite") == 0
    assert "loss_ewma" in attrs and "grad_norm" in attrs


def test_norms_gate_disables_per_group_cost():
    metrics.registry().reset()
    health.set_norms_enabled(False)
    try:
        _tiny_fit(num_epoch=1)
    finally:
        health.set_norms_enabled(True)
    doc = health.flush()
    assert doc["norms"]["by_group"] == {}
    assert doc["sentry"]["verdict"] == "clean"  # sentry stayed on


def test_sharded_train_step_seam():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from mxnet_tpu.parallel import make_sharded_train_step

    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs, ("dp",))

    def loss_fn(params, batch):
        return jnp.mean((batch @ params["w"]) ** 2)

    params = {"w": jnp.ones((4, 2))}
    batch = jnp.ones((2, 4))
    step, p0, o0 = make_sharded_train_step(
        loss_fn, mesh, params, batch, lr=0.1)
    p, o, loss = step(p0, o0, batch)
    # poison: a batch with inf makes the loss nonfinite
    bad = batch.at[0, 0].set(float("inf"))
    for _ in range(health._FOLD_LAG + 2):
        p, o, loss = step(p, o, bad)
    doc = health.flush()
    assert doc["sentry"]["by_source"].get("sharded_train_step")
    assert doc["loss"]["observed"] > 0


# ------------------------------------------------------ loss anomalies
def test_loss_spike_anomaly_z_score():
    for i in range(40):
        health.observe_loss(2.0 - 0.01 * i + (80.0 if i == 35 else 0))
        health.step_boundary("t")
    doc = health.flush()
    kinds = [a["kind"] for a in doc["loss"]["anomalies"]]
    assert "spike" in kinds
    snap = export.snapshot()["metrics"]
    series = snap["mx_health_loss_anomalies_total"]["series"]
    assert any(s["labels"]["kind"] == "spike" and s["value"] >= 1
               for s in series)


def test_loss_plateau_anomaly_fires_once_per_streak():
    for _ in range(80):
        health.observe_loss(1.0)
        health.step_boundary("t")
    doc = health.flush()
    kinds = [a["kind"] for a in doc["loss"]["anomalies"]]
    assert kinds.count("plateau") == 1


def test_anomaly_z_env_override(monkeypatch):
    monkeypatch.setenv("MXTPU_HEALTH_ANOMALY_Z", "1000000")
    for i in range(40):
        health.observe_loss(2.0 + (80.0 if i == 35 else 0))
        health.step_boundary("t")
    doc = health.flush()
    assert not [a for a in doc["loss"]["anomalies"]
                if a["kind"] == "spike"]


# ------------------------------------------------------- fingerprints
def test_fingerprint_deterministic_and_order_independent():
    a = {"x": np.arange(6, dtype=np.float32).reshape(2, 3),
         "y": [np.ones(2), None]}
    b = {"y": [np.ones(2), None],
         "x": np.arange(6, dtype=np.float32).reshape(2, 3)}
    assert health.fingerprint_params(a) == health.fingerprint_params(b)


def test_fingerprint_sensitive_to_values_names_shapes():
    base = {"x": np.zeros((2, 2), np.float32)}
    fp = health.fingerprint_params(base)
    assert fp != health.fingerprint_params(
        {"x": np.full((2, 2), 1e-8, np.float32)})
    assert fp != health.fingerprint_params(
        {"z": np.zeros((2, 2), np.float32)})
    assert fp != health.fingerprint_params(
        {"x": np.zeros((4,), np.float32)})
    assert fp != health.fingerprint_params(
        {"x": np.zeros((2, 2), np.float64)})


def test_fingerprint_accepts_ndarray_and_jax():
    import jax.numpy as jnp
    v = np.arange(4, dtype=np.float32)
    assert health.fingerprint_params({"a": nd.array(v)}) == \
        health.fingerprint_params({"a": jnp.asarray(v)}) == \
        health.fingerprint_params({"a": v})


def test_consistency_rows_carry_fingerprints():
    from mxnet_tpu.consistency import run_sweep
    res = run_sweep("float32", ops=["exp", "relu", "clip"])
    assert res["fail"] == 0, res["failures"]
    assert [r["name"] for r in res["rows"]] == ["exp", "relu", "clip"]
    assert all(r["ok"] and isinstance(r["fingerprint"], str)
               and len(r["fingerprint"]) == 32 for r in res["rows"])
    # deterministic across reruns (same seed): the drift contract
    res2 = run_sweep("float32", ops=["exp", "relu", "clip"])
    assert [r["fingerprint"] for r in res["rows"]] == \
        [r["fingerprint"] for r in res2["rows"]]


# --------------------------------------------------- Monitor sync gate
def test_armed_monitor_adds_zero_syncs_to_training_step(monkeypatch):
    """The satellite regression gate: with a Monitor armed, the
    forward/backward/update step performs NO host sync; toc() then
    folds the whole interval in exactly ONE batched device_get."""
    import jax
    from mxnet_tpu.ndarray.ndarray import NDArray
    from mxnet_tpu import optimizer as opt_mod

    data = sym.var("data")
    fc = sym.FullyConnected(data, name="fc", num_hidden=4)
    out = sym.Activation(fc, name="relu", act_type="relu")
    ex = out.bind(args={"data": nd.ones((2, 3)),
                        "fc_weight": nd.ones((4, 3)),
                        "fc_bias": nd.zeros((4,))}, grad_req="write")
    mon = mx.Monitor(interval=1, pattern=".*")
    mon.install(ex)
    updater = opt_mod.get_updater(opt_mod.create("sgd"))

    counts = {"asnumpy": 0, "device_get": 0}
    real_asnumpy = NDArray.asnumpy
    real_device_get = jax.device_get

    def counting_asnumpy(self):
        counts["asnumpy"] += 1
        return real_asnumpy(self)

    def counting_device_get(x):
        counts["device_get"] += 1
        return real_device_get(x)

    monkeypatch.setattr(NDArray, "asnumpy", counting_asnumpy)
    monkeypatch.setattr(jax, "device_get", counting_device_get)

    mon.tic()
    ex.forward(is_train=True)
    ex.backward()
    for i, name in enumerate(ex.arg_names):
        g = ex.grad_dict.get(name)
        if g is not None:
            updater(i, g, ex.arg_dict[name])
    assert mon.queue, "monitor collected nothing"
    assert counts == {"asnumpy": 0, "device_get": 0}, \
        "armed Monitor synced during the step: %r" % counts
    stats = mon.toc()
    assert stats and counts["device_get"] == 1
    assert counts["asnumpy"] == 0
    names = [n for _s, n, _v in stats]
    assert any("fc_output" in n for n in names)


def _count_reads(monkeypatch):
    """Every read of a device array's value — ``float()``, ``int()``,
    ``tolist()``, ``jax.device_get``, ``__array__`` (all ``np.asarray``
    has on a TPU; on the CPU it takes the buffer protocol instead) —
    goes through the property ``ArrayImpl._value``. Returns the list
    the patched property appends (phase, array) to, and
    ``phase(name)``, a context manager naming the part of the step a
    read happens in."""
    import contextlib
    from jax._src import array as jarray

    real = jarray.ArrayImpl._value
    reads, where = [], ["elsewhere"]

    def fget(self):
        reads.append((where[0], self))
        return real.fget(self)

    @contextlib.contextmanager
    def phase(name):
        prev, where[0] = where[0], name
        try:
            yield
        finally:
            where[0] = prev

    monkeypatch.setattr(jarray.ArrayImpl, "_value", property(fget))
    return reads, phase


def test_gluon_trainer_step_no_syncs_with_health_armed(monkeypatch):
    """Trainer.step with the full health plane on reads ONE device
    array a step: nothing inside ``commit()``, and at the boundary the
    table dispatched ``_TABLE_LAG`` steps before — never the one of the
    step in flight."""
    net = gluon.nn.Dense(4)
    net.initialize(force_reinit=True)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    rs = np.random.RandomState(7)
    X = nd.array(rs.rand(16, 8).astype("float32"))
    Y = nd.array(rs.rand(16, 4).astype("float32"))
    loss_fn = gluon.loss.L2Loss()
    assert health._TABLE_LAG == 1

    reads, phase = _count_reads(monkeypatch)
    real_commit = health.StepProbe.commit
    real_boundary = health.step_boundary

    def commit(self):
        with phase("commit"):
            return real_commit(self)

    def boundary(*a, **k):
        with phase("boundary"):
            return real_boundary(*a, **k)

    monkeypatch.setattr(health.StepProbe, "commit", commit)
    monkeypatch.setattr(health, "step_boundary", boundary)

    def pending_tables():
        with health._state.lock:
            return [t for _, _, t in health._state.norm_pending]

    for step in range(4):
        with autograd.record():
            loss = loss_fn(net(X), Y)
        loss.backward()
        health.observe_loss(loss.mean())
        before = pending_tables()
        del reads[:]
        with phase("step"):
            trainer.step(16)
        after = pending_tables()
        assert len(after) == 1            # this step's, still unread
        assert [ph for ph, _ in reads if ph != "boundary"] == []
        folded = [arr for ph, arr in reads if ph == "boundary"]
        if step < health._TABLE_LAG:
            # (the first step also compiles; nothing is old enough)
            assert folded == [] and before == []
        else:
            assert len(folded) == 1 and folded[0] is before[0]
        assert all(arr is not after[0] for _, arr in reads)
    # flush reads what is left: the last step's table and the losses
    del reads[:]
    health.flush()
    assert sum(arr is after[0] for _, arr in reads) == 1


# the probe's table against numpy: (leaves, their shapes, dtype,
# optimizer, its parameters, MXTPU_HEALTH_NORMS)
_TABLE_CASES = {
    "one_group": (["fc_weight", "fc_bias"], [(5, 3), (5,)], "float32",
                  "sgd", {"learning_rate": 0.1, "momentum": 0.9}, True),
    # more groups than telemetry's pending window of 64 device scalars
    "107_groups": (["l%d_weight" % i for i in range(107)],
                   [(2, 3)] * 107, "float32",
                   "sgd", {"learning_rate": 0.1, "wd": 1e-3}, True),
    "norms_off": (["a_weight", "a_bias", "b_weight"],
                  [(4, 4), (4,), (3, 4)], "float32",
                  "sgd", {"learning_rate": 0.1}, False),
    "bf16_multi_precision_adam": (
        ["a_weight", "a_bias", "b_weight"], [(8, 8), (8,), (3, 8)],
        "bfloat16", "adam",
        {"learning_rate": 0.01, "multi_precision": True}, True),
}


def _table_trainer(case):
    from mxnet_tpu.gluon.parameter import Parameter

    names, shapes, dtype, opt, opt_params, norms = _TABLE_CASES[case]
    health.set_norms_enabled(norms)
    params = [Parameter(n, shape=s, dtype=dtype)
              for n, s in zip(names, shapes)]
    for p in params:
        p.initialize(mx.init.Uniform(0.5))
    return params, gluon.Trainer(params, opt, dict(opt_params)), norms


def _f32(arr):
    return np.asarray(arr.asnumpy(), dtype=np.float32).astype(np.float64)


def _set_grads(params, rs, poison=None):
    """Write a fresh random gradient into every leaf; ``poison`` =
    (leaf index, how many values become NaN)."""
    for i, p in enumerate(params):
        g = rs.standard_normal(p.shape).astype("float32")
        if poison is not None and poison[0] == i:
            g.reshape(-1)[:poison[1]] = np.nan
        p.grad()[:] = nd.array(g).astype(p.dtype)


@pytest.mark.parametrize("case", sorted(_TABLE_CASES))
def test_probe_table_folds_to_what_numpy_reads(case):
    """After k steps and ``flush()``: the norm table is numpy's on the
    last step's arrays, the histogram holds one observation per group
    and step, the gauges show the last step."""
    metrics.registry().reset()
    params, trainer, norms = _table_trainer(case)
    rs = np.random.RandomState(11)
    k = 3
    for _ in range(k):
        _set_grads(params, rs)
        olds = [_f32(p.data()) for p in params]
        trainer.step(2)
    doc = health.flush()
    assert doc["sentry"]["verdict"] == "clean"
    snap = export.snapshot()["metrics"]
    hist = snap["mx_health_update_to_weight"]["series"]
    if not norms:
        assert doc["norms"] == {"grad_norm": None, "by_group": {}}
        assert sum(s["count"] for s in hist) == 0
        return
    want = {}
    for p, old in zip(params, olds):
        w, g = _f32(p.data()), _f32(p.grad())
        acc = want.setdefault(health.group_of(p.name), [0.0, 0.0, 0.0])
        acc[0] += (w * w).sum()
        acc[1] += (g * g).sum()
        acc[2] += ((w - old) ** 2).sum()
    got = doc["norms"]["by_group"]
    assert list(got) == list(want)        # first-occurrence order
    for grp, (w2, g2, u2) in want.items():
        assert got[grp]["weight_norm"] == pytest.approx(w2 ** 0.5, rel=1e-5)
        assert got[grp]["grad_norm"] == pytest.approx(g2 ** 0.5, rel=1e-5)
        assert got[grp]["update_ratio"] == pytest.approx(
            (u2 / w2) ** 0.5, rel=1e-4)
        assert got[grp]["update_ratio"] > 0
    assert doc["norms"]["grad_norm"] == pytest.approx(
        sum(g2 for _, g2, _ in want.values()) ** 0.5, rel=1e-5)
    assert sum(s["count"] for s in hist) == len(want) * k
    ratio = {s["labels"]["group"]: s["value"]
             for s in snap["mx_health_update_ratio"]["series"]}
    # (a registry reset zeroes other tests' series, it keeps them)
    assert {g: ratio[g] for g in got} == \
        {g: v["update_ratio"] for g, v in got.items()}
    assert snap["mx_health_grad_norm"]["series"][0]["value"] == \
        doc["norms"]["grad_norm"]


@pytest.mark.parametrize("case", sorted(_TABLE_CASES))
def test_probe_table_trips_with_the_exact_count_at_the_poisoned_step(case):
    """Three NaNs planted in one leaf's gradient at step 2: the count
    is exact and carries that step; under ``raise`` the error surfaces
    at the next boundary, ``_TABLE_LAG`` + 1 counting the step's own."""
    params, trainer, _ = _table_trainer(case)
    rs = np.random.RandomState(12)
    leaf = len(params) - 1
    for step in range(5):
        _set_grads(params, rs, poison=(leaf, 3) if step == 2 else None)
        trainer.step(2)
        seen = health.snapshot_doc(fold=False)["sentry"]
        if step < 2 + health._TABLE_LAG:
            assert seen["nonfinite_total"] == 0
        else:
            assert seen["by_source"]["trainer_grad"] == 3
    doc = health.flush()["sentry"]
    assert doc["by_source"]["trainer_grad"] == 3
    # the three weights they updated went NaN and stay so: steps 2, 3, 4
    assert doc["by_source"]["trainer_param"] == 3 * 3
    assert doc["first_trip"]["step"] == 2
    assert doc["first_trip"]["source"] == "trainer_grad"
    assert doc["first_trip"]["folded_by"] == "trainer"

    health.reset()
    health.set_enabled("raise")
    params, trainer, _ = _table_trainer(case)
    boundaries = 0
    with pytest.raises(health.NonfiniteError) as ei:
        for step in range(5):
            _set_grads(params, rs, poison=(leaf, 3) if step == 2 else None)
            boundaries = step
            trainer.step(2)
    assert boundaries == 2 + health._TABLE_LAG
    assert "trainer_grad" in str(ei.value) and "step 2" in str(ei.value)


def test_tables_stay_bounded_in_a_loop_without_a_boundary():
    """``Trainer.update`` alone never reaches a boundary: the queue
    stays at ``_MAX_TABLES`` and the overflow's counts are folded, not
    dropped."""
    params, trainer, _ = _table_trainer("one_group")
    rs = np.random.RandomState(13)
    n = health._MAX_TABLES + 3
    for i in range(n):
        _set_grads(params, rs, poison=(0, 2) if i == 0 else None)
        trainer.update(2)
    with health._state.lock:
        assert len(health._state.norm_pending) == health._MAX_TABLES
    assert health.snapshot_doc(fold=False)["sentry"]["by_source"][
        "trainer_grad"] == 2
    assert health.flush()["sentry"]["by_source"]["trainer_grad"] == 2


def test_mxlint_health_scope_clean():
    """MXL002 + the full rule set over every file this PR
    instrumented, via the real CLI (the telemetry PR's gate
    pattern)."""
    proc = subprocess.run(
        [sys.executable, "tools/mxlint.py",
         "mxnet_tpu/profiling/health.py",
         "mxnet_tpu/monitor.py",
         "mxnet_tpu/gluon/trainer.py",
         "mxnet_tpu/optimizer/optimizer.py",
         "mxnet_tpu/executor.py",
         "mxnet_tpu/parallel/train_step.py",
         "tools/health_report.py"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------------------ overhead bound
def test_health_enabled_overhead_bounded():
    """Health-enabled step time within 5% of disabled on process CPU
    time (the PR 4/5 budget + measurement method: min-of-N
    interleaved trials on process_time, immune to CI scheduler
    noise)."""
    # a bigger-than-micro step: the plane's cost is O(parameter
    # groups) per step (one probe dispatch + bucket banking), so the
    # honest relative bound needs a step that isn't degenerate
    dims = dict(feat=32, out=16, n=128, batch=32)
    health.set_enabled(False)
    _tiny_fit(num_epoch=1, **dims)
    health.set_enabled(True)
    _tiny_fit(num_epoch=1, **dims)   # warm both paths (+ the probe)
    best = None
    for _ in range(4):
        on, off = [], []
        for _ in range(4):
            # feed_loss in BOTH modes: the loss.mean() dispatch is the
            # caller's, not health's — observe_loss itself no-ops when
            # disabled, so the delta isolates the plane's own cost
            health.set_enabled(True)
            health.reset()
            on.append(_tiny_fit(num_epoch=2, clock=time.process_time,
                                feed_loss=True, **dims))
            health.set_enabled(False)
            health.reset()
            off.append(_tiny_fit(num_epoch=2, clock=time.process_time,
                                 feed_loss=True, **dims))
        ratio = min(on) / min(off)
        best = ratio if best is None else min(best, ratio)
        if best < 1.05:
            break
    health.set_enabled(True)
    assert best < 1.05, \
        "health overhead %.1f%% across retries (last on=%s off=%s)" \
        % ((best - 1) * 100, on, off)


# ------------------------------------------------- artifacts, reports
def test_committed_postmortem_example_shape():
    pm = json.load(open(NAN_EXAMPLE))
    assert pm["kind"] == "nan_postmortem"
    assert pm["first_op"]["op"] == "log"
    assert pm["first_op"]["named_scope"] == "mx.log"
    assert pm["first_op"]["inputs"][0]["nonfinite"] == 0


def test_health_report_cli(tmp_path):
    out = subprocess.run(
        [sys.executable, "tools/health_report.py", HEALTH_LAST_GOOD],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "verdict clean" in out.stdout
    assert "fingerprint" in out.stdout

    out = subprocess.run(
        [sys.executable, "tools/health_report.py", "--diff",
         HEALTH_LAST_GOOD, HEALTH_LAST_GOOD],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "MATCH" in out.stdout

    out = subprocess.run(
        [sys.executable, "tools/health_report.py", "--postmortem",
         NAN_EXAMPLE],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "FIRST offending op: log" in out.stdout

    # not-a-postmortem rejects cleanly
    p = tmp_path / "x.json"
    p.write_text("{}")
    out = subprocess.run(
        [sys.executable, "tools/health_report.py", "--postmortem",
         str(p)],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 2


def test_chrome_trace_health_counter_track():
    health.observe_loss(1.5)
    for _ in range(health._FOLD_LAG + 1):
        health.step_boundary("t")
    trace = export.merge_chrome_trace(spans=[], health=True)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "mx_health_loss" in names
    assert "mx_health_nonfinite_total" in names
    assert "health" in trace["metadata"]
    meta = [e for e in trace["traceEvents"]
            if e.get("ph") == "M" and
            "model health" in str(e.get("args", {}).get("name", ""))]
    assert meta, "no health process_name metadata row"


def test_env_vars_registered_and_documented():
    from mxnet_tpu import libinfo
    docs = open(os.path.join(REPO, "docs", "env_vars.md")).read()
    for name in ("MXTPU_HEALTH", "MXTPU_HEALTH_DUMP_PATH",
                 "MXTPU_HEALTH_NORMS", "MXTPU_HEALTH_ANOMALY_Z"):
        assert name in libinfo._ENV_VARS
        assert name in docs, "%s missing from docs/env_vars.md" % name


def test_raise_policy_does_not_rearm_on_clean_boundaries():
    """A caller that catches NonfiniteError and keeps training (skip
    the poisoned batch, restore weights) must not be re-raised at
    every later clean boundary — only NEW nonfinites raise again."""
    import jax.numpy as jnp
    health.set_enabled("raise")
    health.check("unit", [jnp.array([float("nan")])])
    with pytest.raises(health.NonfiniteError):
        for _ in range(health._FOLD_LAG + 1):
            health.step_boundary("t")
    for _ in range(10):          # clean continuation: no re-raise
        health.step_boundary("t")
    health.check("unit", [jnp.array([float("inf")])])
    with pytest.raises(health.NonfiniteError):
        for _ in range(health._FOLD_LAG + 1):
            health.step_boundary("t")


def test_trips_counter_counts_bursts_not_first_only():
    import jax.numpy as jnp
    metrics.registry().reset()
    health.check("unit", [jnp.array([float("nan")])])
    health.flush()
    health._state.last_postmortem = -10.0   # age out the burst window
    health.check("unit", [jnp.array([float("nan")])])
    health.flush()
    snap = export.snapshot()["metrics"]
    total = sum(s["value"]
                for s in snap["mx_health_trips_total"]["series"])
    assert total == 2


def test_localizer_slot_is_bounded_to_latest_payload():
    """One localizer slot per source: repeated executor forwards must
    not pin one batch payload per banked step (the closure holds the
    step's full inputs)."""
    ex = _poisoned_executor()
    for _ in range(6):
        ex.forward()
        health.step_boundary("t")
    with health._state.lock:
        assert len(health._state.latest_loc) == 1
        # banked buckets carry counts only, never payload closures
        for entry in health._state.pending:
            assert len(entry) == 2
    health.flush()
    pm = json.load(open(_pm_path()))
    assert pm["first_op"]["node"] == "poison_log"
    assert pm["captured_at_step"] >= pm["step"]


def test_nan_loss_keeps_chrome_trace_strict_json():
    """A poisoned run's NaN loss gauge must not serialize as a bare
    NaN literal — Perfetto would reject the one trace generated to
    debug that exact run."""
    from mxnet_tpu import tracing
    health.observe_loss(float("nan"))
    for _ in range(health._FOLD_LAG + 1):
        health.step_boundary("t")
    with tracing.span("trainer_step", cat="step"):
        trace = export.merge_chrome_trace(spans=[], health=True)
    json.dumps(trace, allow_nan=False)   # raises on bare NaN/Infinity


def test_health_report_postmortem_renders_inflight_spans(tmp_path):
    """Trips fire inside open spans, so real postmortems carry
    in-flight span DICTS in the flight section — the CLI must render,
    not crash (TypeError on join)."""
    from mxnet_tpu import tracing
    with tracing.span("trainer_step", cat="step"):
        health.nan_postmortem(step=3, source="unit", count=1)
    out = subprocess.run(
        [sys.executable, "tools/health_report.py", "--postmortem",
         _pm_path()],
        cwd=REPO, capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "trainer_step" in out.stdout
