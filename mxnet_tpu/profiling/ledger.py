"""The XLA cost ledger: per-op/per-fusion FLOPs, bytes, roofline.

Builds a ranked attribution table from a compiled executable's
optimized HLO (``hlo.py`` prices each instruction analytically) and
keys every row back to a *framework* op name:

- jax stamps each HLO instruction with an ``op_name`` metadata path
  ("jit(forward)/.../jit(convolution)/conv_general_dilated"). Ops
  dispatched through ``ops/registry.OpDef`` ride their own inner
  ``jit(<fn name>)`` scope, and the graph executor wraps each node in
  ``jax.named_scope("mx.<OpName>")`` — both survive XLA optimization,
  so the rightmost recognizable component names the framework op.
- A fused cluster created by a subgraph property (``_sg_xla_conv``
  from ``subgraph/xla_fuse.py``) attributes to that property's rule —
  the TVM/Relay move (PAPERS.md): cost lands on the fusion decision
  that produced the cluster, so "did this fusion rule pay?" is a
  ledger diff, not a guess.

Every row gets a roofline classification against the peaks of the
chip (``DEVICE_PEAKS``, keyed by ``device_kind``): ``compute`` when
flops/peak dominates the estimated time, ``hbm`` when bytes/bandwidth
does, ``comms`` for collectives, ``trivial`` for costless plumbing.

The ledger document is plain JSON (versioned) so ``tools/
mfu_report.py`` renders and diffs it standalone.
"""
from __future__ import annotations

import json
import os
import re
import sys

from ..base import MXNetError
from . import hlo

LEDGER_VERSION = 1

# published per-chip peaks keyed by jax's ``device_kind`` (v5e: Google
# Cloud documentation, "TPU v5e"). A device that is not here is an
# error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gbs": 819.0},
}
# the chip the cost model prices for when no accelerator is attached
# (the chip-free ledger is a statement about this chip, not the host)
MODEL_DEVICE_KIND = "TPU v5 lite"

_JIT_SCOPE = re.compile(r"^jit\(([^)]*)\)$")


def device_peaks(device_kind):
    """The peaks row for a ``device_kind``; an unknown kind raises."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise MXNetError(
            "no published peaks for device kind %r (known: %s); add its "
            "row to profiling.ledger.DEVICE_PEAKS with its source"
            % (device_kind, sorted(DEVICE_PEAKS))) from None


def _peaks(peak_tflops=None, peak_hbm_gbs=None):
    """Explicit peaks win; otherwise the attached TPU's row (an unknown
    kind raises), or the modelled chip's where no accelerator is
    attached. Never imports jax itself: the stdlib-side tools price HLO
    text with no backend at all."""
    if peak_tflops is None or peak_hbm_gbs is None:
        kind = MODEL_DEVICE_KIND
        jax = sys.modules.get("jax")
        if jax is not None:
            dev = jax.devices()[0]
            if dev.platform == "tpu":
                kind = dev.device_kind
        row = device_peaks(kind)
        if peak_tflops is None:
            peak_tflops = row["bf16_tflops"]
        if peak_hbm_gbs is None:
            peak_hbm_gbs = row["hbm_gbs"]
    return peak_tflops, peak_hbm_gbs


def framework_fn_map():
    """{python fn name: canonical op name} over the live op registry
    (empty when the framework isn't importable — attribution then
    falls back to raw jax primitive names)."""
    try:
        from ..ops import registry as _reg
    except Exception:  # noqa: BLE001 — standalone tool loads
        return {}
    return _reg.fn_name_map()


def fusion_rule_map():
    """{fused op name: subgraph backend rule} from the live subgraph
    property registry (e.g. {"_sg_xla_conv": "XLA/conv_bn_add_relu"})."""
    try:
        from ..subgraph import partition as _part
    except Exception:  # noqa: BLE001 — standalone tool loads
        return {}
    out = {}
    for backend, prop in _part.registered_properties().items():
        # a backend may be one property or a whole rule fleet; either
        # way every rule's fused op name attributes to "backend/rule"
        props = prop if isinstance(prop, (list, tuple)) else (prop,)
        for p in props:
            rule = getattr(p, "rule_name", None)
            out[p.op_name] = ("%s/%s" % (backend, rule) if rule
                             else backend)
    return out


def attribute_op_name(op_name_path, fn_map):
    """Framework op for a jax metadata ``op_name`` path: the rightmost
    ``mx.<Name>`` named-scope or ``jit(<registered fn>)`` component,
    else the leaf primitive name."""
    if not op_name_path:
        return None
    parts = op_name_path.split("/")
    for part in reversed(parts):
        if part.startswith("mx."):
            return part[3:]
        m = _JIT_SCOPE.match(part)
        if m and m.group(1) in fn_map:
            return fn_map[m.group(1)]
        # an unregistered jit(<fn>) scope deliberately does NOT win
        # over the leaf primitive: any unlisted entry-point name
        # (jit(fwd), jit(predict), ...) would swallow every
        # instruction without an inner scope and collapse the table
        # onto one row
    leaf = parts[-1]
    return leaf.split("[", 1)[0] or None


def build_ledger(hlo_text, peak_tflops=None, peak_hbm_gbs=None,
                 fn_map=None, rule_map=None, module=None):
    """Price an optimized-HLO module into a ledger document."""
    peak_tflops, peak_hbm_gbs = _peaks(peak_tflops, peak_hbm_gbs)
    mod = module if module is not None else hlo.parse_module(hlo_text)
    if fn_map is None:
        fn_map = framework_fn_map()
    if rule_map is None:
        rule_map = fusion_rule_map()
    peak_fs = peak_tflops * 1e12
    peak_bs = peak_hbm_gbs * 1e9
    rows = []
    tot_f = tot_b = tot_t = 0
    for instr in mod.entry_instructions:
        flops, nbytes = hlo.instr_cost(instr, mod)
        if instr.opcode in hlo.TRIVIAL_OPCODES:
            continue
        t_flops = flops / peak_fs
        t_bytes = nbytes / peak_bs
        est_s = max(t_flops, t_bytes)
        if hlo.is_comms(instr):
            bound = "comms"
        elif flops == 0 and nbytes == 0:
            bound = "trivial"
        elif t_flops >= t_bytes:
            bound = "compute"
        else:
            bound = "hbm"
        op = attribute_op_name(instr.op_name, fn_map)
        row = {
            "instr": instr.name,
            "hlo_op": instr.opcode,
            "op": op,
            "flops": flops,
            "bytes": nbytes,
            "est_s": est_s,
            "bound": bound,
        }
        rule = rule_map.get(op)
        if rule:
            row["rule"] = rule
        rows.append(row)
        tot_f += flops
        tot_b += nbytes
        tot_t += est_s
    rows.sort(key=lambda r: -r["est_s"])
    return {
        "version": LEDGER_VERSION,
        "kind": "cost_ledger",
        "module": mod.name,
        "peak_tflops": peak_tflops,
        "peak_hbm_gbs": peak_hbm_gbs,
        "totals": {"flops": tot_f, "bytes": tot_b, "est_s": tot_t,
                   "rows": len(rows)},
        "rows": rows,
        "by_op": group_by_op(rows, peak_tflops, peak_hbm_gbs),
    }


def group_by_op(rows, peak_tflops=None, peak_hbm_gbs=None):
    """Ledger rows re-aggregated on the framework-op attribution; the
    group's roofline bound is recomputed from its summed flops/bytes
    (majority-of-cost, not majority-of-instructions)."""
    peak_tflops, peak_hbm_gbs = _peaks(peak_tflops, peak_hbm_gbs)
    agg = {}
    comms = set()
    for r in rows:
        key = r.get("op") or r["hlo_op"]
        a = agg.setdefault(key, {
            "op": key, "instrs": 0, "flops": 0, "bytes": 0,
            "est_s": 0.0})
        a["instrs"] += 1
        a["flops"] += r["flops"]
        a["bytes"] += r["bytes"]
        a["est_s"] += r["est_s"]
        if r.get("rule"):
            a["rule"] = r["rule"]
        if r["bound"] == "comms":
            comms.add(key)
    out = sorted(agg.values(), key=lambda a: -a["est_s"])
    for a in out:
        if a["op"] in comms:
            a["bound"] = "comms"
        elif a["flops"] == 0 and a["bytes"] == 0:
            a["bound"] = "trivial"
        else:
            a["bound"] = ("compute"
                          if a["flops"] / (peak_tflops * 1e12)
                          >= a["bytes"] / (peak_hbm_gbs * 1e9)
                          else "hbm")
    return out


def from_compiled(compiled, hlo_text=None, **kwargs):
    """Ledger from a ``jax.stages.Compiled`` — folds in XLA's own
    aggregate ``cost_analysis`` as a cross-check. Pass ``hlo_text``/
    ``module=`` to share one serialization/parse with other passes
    over the same executable (flops AND memory)."""
    if hlo_text is None:
        hlo_text = compiled.as_text()
    doc = build_ledger(hlo_text, **kwargs)
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        doc["xla_cost_analysis"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        }
        xf = doc["xla_cost_analysis"]["flops"]
        if xf > 0 and doc["totals"]["flops"] > 0:
            doc["flops_vs_xla"] = round(doc["totals"]["flops"] / xf, 4)
    except Exception:  # noqa: BLE001 — cost_analysis is backend-best-effort
        pass
    return doc


def from_fn(fn, *args, **kwargs):
    """Lower+compile ``fn`` on the current backend and price it.
    ``fn`` may already be jitted; plain callables are jitted here."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return from_compiled(jitted.lower(*args).compile(), **kwargs)


def mfu_estimate(doc, items_per_step=None):
    """Cost-model MFU numbers from a ledger document alone.

    - ``mfu_at_roofline``: flops_total / (est_s * peak) — the MFU the
      roofline model says this module could reach if every op hit its
      bound: a ceiling that needs no chip run.
    - with ``items_per_step``: ``gflops_per_item`` for throughput math.
    """
    peak_fs = doc["peak_tflops"] * 1e12
    flops = doc["totals"]["flops"]
    est_s = doc["totals"]["est_s"]
    out = {"flops_total": flops,
           "gflops_total": round(flops / 1e9, 3),
           "est_step_s": round(est_s, 6),
           "mfu_at_roofline": round(flops / (est_s * peak_fs), 4)
           if est_s > 0 else 0.0}
    if items_per_step:
        out["gflops_per_item"] = round(flops / items_per_step / 1e9, 3)
    return out


def diff(before, after):
    """Ranked per-op delta between two ledger documents — the
    mfu_report --diff payload."""
    def index(doc):
        return {a["op"]: a for a in doc.get("by_op", [])}

    ia, ib = index(before), index(after)
    out = []
    for op in sorted(set(ia) | set(ib)):
        a = ia.get(op, {})
        b = ib.get(op, {})
        ta = a.get("est_s", 0.0)
        tb = b.get("est_s", 0.0)
        out.append({
            "op": op,
            "before_s": ta, "after_s": tb, "delta_s": tb - ta,
            "before_flops": a.get("flops", 0),
            "after_flops": b.get("flops", 0),
        })
    out.sort(key=lambda r: -abs(r["delta_s"]))
    return out


def load(path):
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "rows" not in doc or \
            "totals" not in doc:
        raise ValueError("%s is not a ledger document" % path)
    return doc


def dump(doc, path):
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return doc
