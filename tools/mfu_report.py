#!/usr/bin/env python
"""mfu_report — render or diff per-op cost ledgers.

    python tools/mfu_report.py ledger.json              # ranked table
    python tools/mfu_report.py --diff before.json after.json
    python tools/mfu_report.py --hlo compiled.hlo.txt   # price a dump

Input files are ``mxnet_tpu.profiling`` cost-ledger documents
(``profiling/ledger.py``) and the partition cost reports of
``subgraph/cost.py``. The ``--diff`` mode is the perf-PR workflow:
price on main, price on the branch, attach the ranked per-op delta —
the cost-attributed analogue of ``telemetry_dump.py --diff``
(docs/observability.md "MFU accounting & roofline").

Everything here imports only the stdlib side of the profiling package
(no jax).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_profiling():
    """The profiling package without executing mxnet_tpu/__init__.py
    (which initializes the jax backend) — same pattern as
    telemetry_dump."""
    import importlib
    name = "_mfu_mxtpu"
    if name not in sys.modules:
        pkg = types.ModuleType(name)
        pkg.__path__ = [os.path.join(REPO, "mxnet_tpu")]
        sys.modules[name] = pkg
    return importlib.import_module(name + ".profiling")


def _read_doc(path):
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print("mfu_report: cannot read %s: %s" % (path, e),
              file=sys.stderr)
        raise SystemExit(2)
    if not isinstance(doc, dict) or (
            "rows" not in doc
            and doc.get("kind") != "partition_cost_report"):
        print("mfu_report: %s is not a ledger/attribution/partition-"
              "cost document (no 'rows' key)" % path, file=sys.stderr)
        raise SystemExit(2)
    return doc


def _fmt_bytes(n):
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if n >= div:
            return "%.2f%s" % (n / div, unit)
    return "%dB" % n


def format_partition_report(doc, top=25):
    """Ranked fusion-decision table from a subgraph/cost.py partition
    cost report — the decision trail of a cost-tracked partitioning
    pass (docs/observability.md "Reading a fusion PR")."""
    s = doc.get("summary", {})
    lines = [
        "# partition_cost_report: backend %s  (peak %.0f TFLOP/s, "
        "%.0f GB/s HBM)" % (doc.get("backend"),
                            doc.get("peak_tflops", 0.0),
                            doc.get("peak_hbm_gbs", 0.0)),
        "# clusters %d: %d accepted, %d rejected on cost, %d rejected "
        "structurally; est saved %.4f ms, HBM saved %s/step, peak "
        "delta %+d bytes"
        % (s.get("clusters", 0), s.get("accepted", 0),
           s.get("rejected_cost", 0), s.get("rejected_structural", 0),
           s.get("est_saved_s", 0.0) * 1e3,
           _fmt_bytes(max(s.get("hbm_bytes_saved", 0), 0)),
           s.get("peak_delta_bytes", 0)),
        "%-28s %-8s %10s %10s %10s %s" % (
            "rule", "verdict", "save_ms", "save_frac", "peak_delta",
            "cluster / reason"),
    ]
    for d in doc.get("decisions", [])[:top]:
        cluster = ",".join(d.get("nodes", []))[:40]
        reason = d.get("reason", "")
        lines.append("%-28s %-8s %10.4f %9.1f%% %10d %s" % (
            d.get("rule", "?")[:28],
            "ACCEPT" if d.get("accepted") else "reject",
            d.get("est_saving_s", 0.0) * 1e3,
            d.get("est_saving_frac", 0.0) * 100,
            d.get("peak_delta_bytes", 0),
            cluster if d.get("accepted") else
            "%s [%s]" % (cluster, reason)))
    return "\n".join(lines)


def format_table(doc, top=25):
    """Ranked per-op cost table."""
    if doc.get("kind") == "partition_cost_report":
        return format_partition_report(doc, top=top)
    lines = []
    lines.append("# %s: %s  (peak %.0f TFLOP/s, %.0f GB/s HBM)"
                 % (doc.get("kind", "ledger"),
                    doc.get("module", "?"), doc["peak_tflops"],
                    doc["peak_hbm_gbs"]))
    t = doc["totals"]
    lines.append("# totals: %.3f GFLOP, %s, roofline est %.3f ms"
                 % (t["flops"] / 1e9, _fmt_bytes(t["bytes"]),
                    t["est_s"] * 1e3))
    lines.append("%-28s %6s %10s %10s %10s %8s" % (
        "op", "instrs", "GFLOP", "bytes", "est_ms", "bound"))
    for g in doc.get("by_op", [])[:top]:
        row = "%-28s %6d %10.3f %10s %10.4f %8s" % (
            (g.get("op") or "?")[:28], g.get("instrs", 0),
            g["flops"] / 1e9, _fmt_bytes(g["bytes"]),
            g["est_s"] * 1e3, g.get("bound", "?"))
        if g.get("rule"):
            row += "  rule=%s" % g["rule"]
        lines.append(row)
    return "\n".join(lines)


def format_diff(before, after, prof, top=25):
    rows = prof.ledger.diff(before, after)
    lines = ["# per-op attribution delta (ranked by |delta time|)",
             "%-28s %12s %12s %12s %14s" % (
                 "op", "before_ms", "after_ms", "delta_ms",
                 "delta_GFLOP")]
    for r in rows[:top]:
        if r["delta_s"] == 0 and r["after_flops"] == r["before_flops"]:
            continue
        lines.append("%-28s %12.4f %12.4f %+12.4f %+14.3f" % (
            r["op"][:28], r["before_s"] * 1e3, r["after_s"] * 1e3,
            r["delta_s"] * 1e3,
            (r["after_flops"] - r["before_flops"]) / 1e9))
    if len(lines) == 2:
        lines.append("(no per-op change)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="mfu_report",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="ledger document(s)")
    ap.add_argument("--diff", action="store_true",
                    help="diff two documents (before after)")
    ap.add_argument("-o", "--out", help="write the document here as "
                                        "JSON")
    ap.add_argument("--hlo", metavar="PATH",
                    help="price a raw optimized-HLO text dump")
    ap.add_argument("--json", action="store_true",
                    help="emit the document itself instead of a table")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)

    if args.diff:
        if len(args.paths) != 2:
            print("mfu_report: --diff takes exactly two documents",
                  file=sys.stderr)
            return 2
        prof = _load_profiling()
        before, after = _read_doc(args.paths[0]), _read_doc(
            args.paths[1])
        if args.json:
            print(json.dumps(prof.ledger.diff(before, after),
                             indent=1))
        else:
            print(format_diff(before, after, prof, top=args.top))
        return 0

    if args.hlo:
        prof = _load_profiling()
        with open(args.hlo, "r", encoding="utf-8") as f:
            doc = prof.ledger.build_ledger(f.read())
        _finish(doc, args, prof)
        return 0

    if len(args.paths) != 1:
        print("mfu_report: exactly one document unless --diff/--hlo",
              file=sys.stderr)
        return 2
    prof = _load_profiling()
    doc = _read_doc(args.paths[0])
    _finish(doc, args, prof)
    return 0


def _finish(doc, args, prof):
    if args.out:
        prof.ledger.dump(doc, args.out)
    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(format_table(doc, top=args.top))


if __name__ == "__main__":
    sys.exit(main())
