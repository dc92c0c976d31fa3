"""Performance-attribution subsystem tests (PR 6).

Covers the three cooperating pieces end to end on the CPU backend —
the whole point of wrapping XLA's cost analysis at the ``compile()``
seam is that every one of these runs chip-free:

- the HLO parser + analytic cost model (unit fixtures, and the
  committed acceptance bound: ResNet-50's ledger FLOPs agree with the
  benchmark's analytic forward count within 15%),
- framework-op attribution through all three channels (dispatch-layer
  ``jit(<fn>)`` scopes, executor ``mx.<Op>`` named scopes, fusion-rule
  mapping for ``_sg_xla_conv``),
- the xplane wire parser (synthetic protobuf fixtures),
- the CLIs: mfu_report (table/diff), trace_merge single-rank behavior.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.profiling import hlo, ledger, xplane

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


# ------------------------------------------------------------ HLO parser
_HLO_FIXTURE = """\
HloModule test_mod, entry_computation_layout={(f32[4,8]{1,0})->f32[]}

%fused_add (p0: f32[4,8], p1: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %p1 = f32[4,8]{1,0} parameter(1)
  ROOT %add.1 = f32[4,8]{1,0} add(f32[4,8]{1,0} %p0, f32[4,8]{1,0} %p1), metadata={op_name="jit(f)/jit(main)/add"}
}

ENTRY %main.9 (Arg_0.1: f32[4,8]) -> f32[] {
  %Arg_0.1 = f32[4,8]{1,0} parameter(0)
  %w = f32[8,16]{1,0} constant({...})
  %dot.2 = f32[4,16]{1,0} dot(f32[4,8]{1,0} %Arg_0.1, f32[8,16]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/jit(fully_connected)/dot_general" source_file="x.py" source_line=4}
  %conv.3 = f32[4,4,4,16]{3,2,1,0} convolution(f32[4,4,4,8]{3,2,1,0} %Arg_r, f32[3,3,8,16]{3,2,1,0} %w2), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(f)/mx.Convolution/conv_general_dilated"}
  %fusion.4 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %Arg_0.1, f32[4,8]{1,0} %Arg_0.1), kind=kLoop, calls=%fused_add
  %ar.5 = f32[4,8]{1,0} all-reduce(f32[4,8]{1,0} %fusion.4), replica_groups={}, to_apply=%fused_add
  ROOT %reduce.6 = f32[] reduce(f32[4,8]{1,0} %ar.5, f32[] %Arg_0.1), dimensions={0,1}, to_apply=%fused_add, metadata={op_name="jit(f)/jit(main)/reduce_sum"}
}
"""


def test_hlo_parser_instructions_and_metadata():
    mod = hlo.parse_module(_HLO_FIXTURE)
    assert mod.name == "test_mod"
    assert mod.entry == "main.9"
    names = [i.name for i in mod.entry_instructions]
    assert "dot.2" in names and "fusion.4" in names
    dot = next(i for i in mod.entry_instructions if i.name == "dot.2")
    assert dot.opcode == "dot"
    assert dot.op_name.endswith("jit(fully_connected)/dot_general")
    fusion = next(i for i in mod.entry_instructions
                  if i.name == "fusion.4")
    assert fusion.calls == ["fused_add"]
    root = next(i for i in mod.entry_instructions if i.is_root)
    assert root.name == "reduce.6"


def test_hlo_flop_model():
    mod = hlo.parse_module(_HLO_FIXTURE)
    by = {i.name: i for i in mod.entry_instructions}
    # dot: 2 * M*N * K = 2 * (4*16) * 8
    flops, nbytes = hlo.instr_cost(by["dot.2"], mod)
    assert flops == 2 * 4 * 16 * 8
    assert nbytes == (4 * 8 + 8 * 16 + 4 * 16) * 4
    # conv: 2 * out_elems * k_spatial * rhs_input_features
    flops, _ = hlo.instr_cost(by["conv.3"], mod)
    assert flops == 2 * (4 * 4 * 4 * 16) * 9 * 8
    # fusion prices the called computation (one add = 32 elems), bytes
    # stay the fusion's own operands+output
    flops, nbytes = hlo.instr_cost(by["fusion.4"], mod)
    assert flops == 32
    assert nbytes == 3 * 32 * 4
    # collective: comms-classified, zero flops
    assert hlo.is_comms(by["ar.5"])
    assert hlo.instr_cost(by["ar.5"], mod)[0] == 0


def test_attribute_op_name_channels():
    fn_map = {"fully_connected": "FullyConnected"}
    att = ledger.attribute_op_name
    assert att("jit(f)/jit(main)/jit(fully_connected)/dot_general",
               fn_map) == "FullyConnected"
    assert att("jit(f)/mx.Convolution/conv_general_dilated",
               fn_map) == "Convolution"
    assert att("jit(f)/jit(main)/reduce_sum", fn_map) == "reduce_sum"
    assert att(None, fn_map) is None


def test_ledger_fixture_rows_and_bounds():
    doc = ledger.build_ledger(
        _HLO_FIXTURE, peak_tflops=100.0, peak_hbm_gbs=1000.0,
        fn_map={"fully_connected": "FullyConnected"}, rule_map={})
    rows = {r["instr"]: r for r in doc["rows"]}
    assert rows["ar.5"]["bound"] == "comms"
    assert rows["dot.2"]["op"] == "FullyConnected"
    assert rows["conv.3"]["op"] == "Convolution"
    assert doc["totals"]["flops"] == sum(r["flops"]
                                         for r in doc["rows"])
    # parameters/constants never get rows
    assert "Arg_0.1" not in rows and "w" not in rows
    est = ledger.mfu_estimate(doc, items_per_step=4)
    assert est["gflops_per_item"] >= 0
    assert est["mfu_at_roofline"] > 0


# --------------------------------------------------- ResNet-50 acceptance
def test_resnet50_ledger_flops_within_15pct_of_analytic():
    """Satellite acceptance: the cost-ledger FLOPs for the ResNet-50
    forward agree within 15% with the benchmark's analytic count
    (benchmark/lib/flops.py: convolutions and the classifier, 2 FLOPs
    a multiply-accumulate, as the ledger counts them)."""
    import jax.numpy as jnp

    sys.path.insert(0, TOOLS)
    import programs
    from benchmark.lib import flops

    batch = 2
    fwd, pvals = programs.build_forward(batch)
    data = jnp.zeros((batch, 3, 224, 224), jnp.bfloat16)
    doc = ledger.from_compiled(fwd.lower(pvals, data).compile())
    with open(os.path.join(REPO, "benchmark", "configs",
                           "resnet50_v1.json")) as f:
        analytic = flops.resnet_v1_forward_flops(json.load(f), batch)
    assert abs(doc["totals"]["flops"] - analytic) <= 0.15 * analytic, \
        (doc["totals"]["flops"], analytic)
    # the analytic model must also agree with XLA's own aggregate
    assert 0.8 <= doc.get("flops_vs_xla", 1.0) <= 1.25
    # attribution lands on framework ops, not raw primitives
    ops = {g["op"] for g in doc["by_op"]}
    assert "Convolution" in ops and "FullyConnected" in ops


def test_fused_cluster_attributes_to_fusion_rule():
    """A conv+BN+relu chain fused by the XLA subgraph property prices
    under _sg_xla_conv with the property's rule name attached."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.block import _flatten, infer_shapes
    from mxnet_tpu.ndarray.ndarray import NDArray

    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1, use_bias=False))
    net.add(nn.BatchNorm())
    net.add(nn.Activation("relu"))
    net.initialize()
    infer_shapes(net, (2, 3, 16, 16))
    net.hybridize()
    net._optimized_backend = "XLA"
    plist = sorted(net.collect_params().items())
    pvals = tuple(p.data()._data for _, p in plist)
    x = NDArray(jnp.zeros((2, 3, 16, 16), jnp.float32))
    _, in_spec = _flatten([x])
    jfn, _o, _a = net._build_cached(plist, in_spec, training=False)
    compiled = jfn.lower(pvals, jax.random.PRNGKey(0),
                         x._data).compile()
    doc = ledger.from_compiled(compiled)
    fused = [g for g in doc["by_op"] if g["op"] == "_sg_xla_conv"]
    assert fused, [g["op"] for g in doc["by_op"]]
    assert fused[0]["rule"] == "XLA/conv_bn_add_relu"
    assert fused[0]["flops"] > 0


def test_executor_named_scope_attribution():
    """The graph executor stamps mx.<OpName> scopes at trace time, so
    a simple_bind'd symbol's lowered HLO attributes per framework op."""
    import jax

    data = mx.sym.var("data")
    w = mx.sym.var("w")
    out = mx.sym.FullyConnected(data, w, num_hidden=8, no_bias=True,
                                name="fc1")
    ex = out.simple_bind(mx.cpu(), data=(4, 16))
    arg_vals = {n: a._data for n, a in ex.arg_dict.items()}
    aux_vals = {n: a._data for n, a in ex.aux_dict.items()}
    txt = ex._jitted_forward(False).lower(
        arg_vals, aux_vals, jax.random.PRNGKey(0)).compile().as_text()
    assert "mx.FullyConnected" in txt
    doc = ledger.build_ledger(txt)
    assert any(g["op"] == "FullyConnected" for g in doc["by_op"])


# ------------------------------------------------------- xplane parser
def _varint(n):
    out = b""
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(fn, wt, payload):
    tag = _varint((fn << 3) | wt)
    if wt == 2:
        return tag + _varint(len(payload)) + payload
    return tag + _varint(payload)


def _xevent(mid, off_ps, dur_ps):
    return (_field(1, 0, mid) + _field(2, 0, off_ps)
            + _field(3, 0, dur_ps))


def _xspace(plane_name, line_name, metas, events, ts_ns=0):
    meta_msgs = b"".join(
        _field(4, 2, _field(1, 0, mid)
               + _field(2, 2, _field(1, 0, mid)
                        + _field(2, 2, name.encode())))
        for mid, name in metas.items())
    line = (_field(2, 2, line_name.encode()) + _field(3, 0, ts_ns)
            + b"".join(_field(4, 2, _xevent(*e)) for e in events))
    plane = (_field(2, 2, plane_name.encode()) + meta_msgs
             + _field(3, 2, line))
    return _field(1, 2, plane)


def test_xplane_parser_synthetic():
    data = _xspace("/device:TPU:0", "XLA Ops",
                   {1: "dot.2", 2: "fusion.4.clone"},
                   [(1, 1000, 5000), (2, 7000, 2000)], ts_ns=10)
    planes = xplane.parse_xspace(data)
    assert len(planes) == 1
    p = planes[0]
    assert p["name"] == "/device:TPU:0"
    assert p["event_metadata"] == {1: "dot.2", 2: "fusion.4.clone"}
    (line,) = p["lines"]
    assert line["timestamp_ns"] == 10
    assert line["events"] == [(1, 1000, 5000), (2, 7000, 2000)]
    assert xplane.normalize_event_name("fusion.4.clone") == "fusion.4"


def test_measure_ops_self_time_and_window():
    # call.1 [0, 10000] wraps fused.2 [1000, 9000]; dot.3 disjoint
    data = _xspace("/device:TPU:0", "XLA Ops",
                   {1: "call.1", 2: "fused.2", 3: "dot.3"},
                   [(1, 0, 10000), (2, 1000, 8000), (3, 20000, 4000)])
    planes = xplane.parse_xspace(data)
    m = xplane.measure_ops(planes, {"call.1", "fused.2", "dot.3"})
    assert m["ops"]["call.1"]["self_s"] == pytest.approx(2000 / 1e12)
    assert m["ops"]["call.1"]["total_s"] == pytest.approx(10000 / 1e12)
    assert m["ops"]["fused.2"]["self_s"] == pytest.approx(8000 / 1e12)
    assert m["covered_s"] == pytest.approx(14000 / 1e12)
    assert m["window_s"] == pytest.approx(14000 / 1e12)
    # unmatched wrapper events still extend the device window
    m2 = xplane.measure_ops(planes, {"dot.3"})
    assert m2["ops"].keys() == {"dot.3"}
    assert m2["window_s"] == pytest.approx(14000 / 1e12)


# ------------------------------------------------------------- mfu_report
def test_mfu_report_table_and_diff(tmp_path):
    sys.path.insert(0, TOOLS)
    import mfu_report

    doc = ledger.build_ledger(
        _HLO_FIXTURE, peak_tflops=100.0, peak_hbm_gbs=1000.0,
        fn_map={"fully_connected": "FullyConnected"}, rule_map={})
    before = str(tmp_path / "before.json")
    ledger.dump(doc, before)
    rc = mfu_report.main([before])
    assert rc == 0
    table = mfu_report.format_table(doc)
    assert "FullyConnected" in table and "bound" in table
    # diff: make FullyConnected cheaper
    doc2 = json.loads(json.dumps(doc))
    for g in doc2["by_op"]:
        if g["op"] == "FullyConnected":
            g["est_s"] *= 0.5
    after = str(tmp_path / "after.json")
    ledger.dump(doc2, after)
    d = ledger.diff(doc, doc2)
    fc = next(r for r in d if r["op"] == "FullyConnected")
    assert fc["delta_s"] < 0
    assert mfu_report.main(["--diff", before, after]) == 0


# ------------------------------------------------ trace_merge single rank
def _single_rank_trace(tmp_path):
    doc = {
        "version": 1, "clock": "monotonic_ns",
        "meta": {"pid": 1, "role": "worker", "rank": 0},
        "spans": [
            {"name": "step", "cat": "step", "trace": 1, "span": 2,
             "parent": None, "start_ns": 1000, "dur_ns": 10_000_000,
             "tid": 1, "thread": "main", "attrs": {"step": 0}},
            {"name": "data", "cat": "io", "trace": 1, "span": 3,
             "parent": 2, "start_ns": 2000, "dur_ns": 2_000_000,
             "tid": 1, "thread": "main", "attrs": {}},
        ],
    }
    p = tmp_path / "trace.worker0.json"
    p.write_text(json.dumps(doc))
    return str(p)


def test_trace_merge_single_rank(tmp_path, capsys):
    sys.path.insert(0, TOOLS)
    import trace_merge

    path = _single_rank_trace(tmp_path)
    out = str(tmp_path / "merged.json")
    rc = trace_merge.main([path, "-o", out, "--report"])
    stdout = capsys.readouterr().out
    assert rc == 0
    assert "identity (no server peer)" in stdout
    assert "straggler: n/a" in stdout
    merged = json.loads(open(out).read())
    report = merged["metadata"]["straggler_report"]
    assert report["overall"]["single_rank"] is True
    assert report["overall"]["straggler_rank"] == "n/a"
    assert report["steps"][0]["straggler"] == "n/a"
    # the timeline itself is intact: step + io spans survived
    cats = {e.get("cat") for e in merged["traceEvents"]}
    assert "step" in cats and "io" in cats
    # per-rank numbers still report for the one rank
    ranks = report["steps"][0]["ranks"]
    assert ranks["worker0"]["data_ms"] == pytest.approx(2.0)


def test_trace_merge_multi_rank_still_names_straggler(tmp_path):
    sys.path.insert(0, TOOLS)
    import trace_merge

    docs = []
    for rank, compute_ms in ((0, 5), (1, 9)):
        doc = {
            "version": 1, "clock": "monotonic_ns",
            "meta": {"pid": rank, "role": "worker", "rank": rank},
            "spans": [
                {"name": "step", "cat": "step", "trace": 1,
                 "span": 10 + rank, "parent": None, "start_ns": 0,
                 "dur_ns": 10_000_000, "tid": 1, "thread": "main",
                 "attrs": {"step": 0}},
                {"name": "kv.push", "cat": "comm", "trace": 1,
                 "span": 20 + rank, "parent": 10 + rank,
                 "start_ns": compute_ms * 1_000_000,
                 "dur_ns": (10 - compute_ms) * 1_000_000, "tid": 1,
                 "thread": "main", "attrs": {}},
            ],
        }
        p = tmp_path / ("trace.worker%d.json" % rank)
        p.write_text(json.dumps(doc))
        docs.append(str(p))
    report = trace_merge.straggler_report(
        [trace_merge.load_trace(p) for p in docs])
    assert report["overall"].get("single_rank") is None
    assert report["overall"]["straggler_rank"] == "worker1"


# ------------------------------------------------------ env registration
def test_env_registry_and_docs_agree():
    """docs/env_vars.md lists exactly the registered names."""
    import re

    from mxnet_tpu import libinfo

    docs = open(os.path.join(REPO, "docs", "env_vars.md")).read()
    documented = set(re.findall(r"^\| `([A-Z0-9_]+)` \|", docs, re.M))
    assert documented == set(libinfo._ENV_VARS)


def test_mxl002_scope_covers_profiling(tmp_path):
    """The host-sync rule now patrols the profiling recorders: a sync
    planted in measure_ops must be flagged."""
    from mxnet_tpu.analysis.lint import run_lint
    from mxnet_tpu.analysis.rules.host_sync import HostSyncRule

    bad = tmp_path / "mxnet_tpu" / "profiling"
    bad.mkdir(parents=True)
    f = bad / "evil.py"
    f.write_text(
        "def measure_ops(planes, names):\n"
        "    x.asnumpy()\n"
        "    return {}\n")
    result = run_lint(str(tmp_path), [HostSyncRule()], files=[str(f)])
    assert any(fd.code == "MXL002" for fd in result.findings)
