#!/usr/bin/env python
"""health_report — render, diff, or pretty-print model-health documents.

    python tools/health_report.py BENCH_r06.json        # health table
    python tools/health_report.py health.json           # bare summary
    python tools/health_report.py --diff before.json after.json
    python tools/health_report.py --postmortem nan_postmortem.json
    python tools/health_report.py --live                # fold this process

Inputs are ``mxnet_tpu.profiling.health`` summary documents
({"kind": "health_summary"}) — bare, or embedded under a bench
artifact's ``health`` key — and, for ``--postmortem``, the first-NaN
artifact ({"kind": "nan_postmortem"}) a sentry trip writes to
``MXTPU_HEALTH_DUMP_PATH``. ``--diff`` is the training-health PR
workflow: run on main, run on the branch, attach the loss-EWMA /
grad-norm / per-group deltas and the fingerprint verdict — mirroring
``memory_report --diff`` / ``mfu_report --diff``; the pass/fail *gate*
lives in ``tools/perf_gate.py --health``.

Rendering and diffing are stdlib-only (no jax); ``--live`` imports
mxnet_tpu and folds the current process's health state.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read_json(path):
    try:
        with open(path, "r", encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        print("health_report: cannot read %s: %s" % (path, e),
              file=sys.stderr)
        raise SystemExit(2)


def extract_summary(doc):
    """A health summary from a bare document or a bench artifact
    (driver round file / raw line / last-good wrapper all accepted)."""
    if not isinstance(doc, dict):
        return None
    if doc.get("kind") == "health_summary":
        return doc
    if isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if isinstance(doc.get("line"), str):
        try:
            doc = json.loads(doc["line"])
        except ValueError:
            return None
    h = doc.get("health")
    if not isinstance(h, dict):
        return None
    if "sentry" in h:
        return h
    # artifact embeds are flattened (docs/artifacts/
    # HEALTH_LAST_GOOD.json): lift them back into the summary shape so
    # one renderer serves both
    out = {
        "kind": "health_summary",
        "steps": h.get("steps"),
        "sentry": {"verdict": h.get("verdict"),
                   "nonfinite_total": h.get("nonfinite_total", 0),
                   "first_trip": h.get("first_trip")},
        "loss": {"last": h.get("loss_last"), "ewma": h.get("loss_ewma"),
                 "observed": h.get("steps"),
                 "anomalies_total": h.get("loss_anomalies", 0),
                 "anomalies": []},
        "norms": {"grad_norm": h.get("grad_norm"), "by_group": {}},
    }
    if h.get("fingerprint"):
        out["fingerprint"] = h["fingerprint"]
    return out


def _fmt(v, nd=6):
    if v is None:
        return "-"
    if isinstance(v, float):
        return "%.*g" % (nd, v)
    return str(v)


def format_table(doc):
    """Sentry headline + loss state + ranked per-group norm table."""
    lines = []
    sentry = doc.get("sentry", {})
    head = ("# health: verdict %s · %s nonfinite · %s steps"
            % (sentry.get("verdict", "?"),
               sentry.get("nonfinite_total", 0),
               doc.get("steps", "?")))
    if doc.get("policy"):
        head += " · policy %s" % doc["policy"]
    lines.append(head)
    trip = sentry.get("first_trip")
    if trip:
        lines.append("# first trip: seam %s at step %s (%s values)"
                     % (trip.get("source"), trip.get("step"),
                        trip.get("count")))
    for src, n in sorted((sentry.get("by_source") or {}).items(),
                         key=lambda kv: -kv[1]):
        lines.append("  %-32s %8d nonfinite" % (src, n))
    loss = doc.get("loss", {})
    if loss.get("observed"):
        lines.append("# loss: last %s · ewma %s · std %s · %s observed"
                     % (_fmt(loss.get("last")), _fmt(loss.get("ewma")),
                        _fmt(loss.get("std")), loss.get("observed")))
        for a in loss.get("anomalies", []):
            lines.append("  anomaly %-8s step %-6s loss %s (ewma %s)"
                         % (a.get("kind"), a.get("step"),
                            _fmt(a.get("loss")), _fmt(a.get("ewma"))))
    norms = doc.get("norms", {})
    groups = norms.get("by_group") or {}
    if norms.get("grad_norm") is not None or groups:
        lines.append("# global grad norm: %s"
                     % _fmt(norms.get("grad_norm")))
    if groups:
        lines.append("%-28s %12s %12s %14s" % (
            "group", "||w||", "||g||", "||dw||/||w||"))
        ranked = sorted(groups.items(),
                        key=lambda kv: -(kv[1].get("grad_norm") or 0))
        for grp, g in ranked:
            lines.append("%-28s %12s %12s %14s" % (
                grp[:28], _fmt(g.get("weight_norm")),
                _fmt(g.get("grad_norm")), _fmt(g.get("update_ratio"))))
    if doc.get("fingerprint"):
        lines.append("# params fingerprint: %s" % doc["fingerprint"])
    return "\n".join(lines)


def diff(before, after):
    """Machine-readable health delta between two summaries."""
    def groups(d):
        return (d.get("norms", {}).get("by_group") or {})

    ga, gb = groups(before), groups(after)
    by_group = []
    for grp in sorted(set(ga) | set(gb)):
        a, b = ga.get(grp, {}), gb.get(grp, {})
        row = {"group": grp}
        for k in ("weight_norm", "grad_norm", "update_ratio"):
            va, vb = a.get(k), b.get(k)
            if isinstance(va, (int, float)) and \
                    isinstance(vb, (int, float)):
                row[k + "_delta"] = vb - va
        by_group.append(row)
    by_group.sort(key=lambda r: -abs(r.get("grad_norm_delta", 0.0)))

    def val(d, *ks):
        for k in ks:
            d = d.get(k) if isinstance(d, dict) else None
        return d

    out = {
        "nonfinite_before": val(before, "sentry", "nonfinite_total"),
        "nonfinite_after": val(after, "sentry", "nonfinite_total"),
        "loss_ewma_before": val(before, "loss", "ewma"),
        "loss_ewma_after": val(after, "loss", "ewma"),
        "by_group": by_group,
    }
    fa, fb = before.get("fingerprint"), after.get("fingerprint")
    if fa and fb:
        out["fingerprint_match"] = fa == fb
    return out


def format_diff(d):
    lines = ["# nonfinite: %s -> %s" % (d.get("nonfinite_before"),
                                        d.get("nonfinite_after")),
             "# loss ewma: %s -> %s" % (_fmt(d.get("loss_ewma_before")),
                                        _fmt(d.get("loss_ewma_after")))]
    if "fingerprint_match" in d:
        lines.append("# params fingerprint: %s"
                     % ("MATCH (bit-identical)"
                        if d["fingerprint_match"] else "DIFFER"))
    shown = 0
    for r in d["by_group"]:
        deltas = " ".join("%s %+.4g" % (k[:-6], v)
                          for k, v in sorted(r.items())
                          if k.endswith("_delta"))
        if deltas:
            lines.append("  %-28s %s" % (r["group"][:28], deltas))
            shown += 1
    if not shown:
        lines.append("(no per-group change)")
    return "\n".join(lines)


def format_postmortem(doc):
    """Triage view of a first-NaN artifact (docs/observability.md
    'Model health' walks this exact output)."""
    lines = ["# nan_postmortem: seam %s · step %s · %s nonfinite "
             "values"
             % (doc.get("source", "?"), doc.get("step", "?"),
                doc.get("nonfinite_count", "?"))]
    first = doc.get("first_op")
    if first:
        lines.append("# FIRST offending op: %s (node %s, scope %s) — "
                     "localized in %s probes over %s internals"
                     % (first.get("op"), first.get("node"),
                        first.get("named_scope"), first.get("probes"),
                        first.get("internals")))
        out = first.get("output", {})
        lines.append("  output %s %s: %s nonfinite, finite range "
                     "[%s, %s]"
                     % (out.get("dtype"), out.get("shape"),
                        out.get("nonfinite", "?"), _fmt(out.get("min")),
                        _fmt(out.get("max"))))
        for i in first.get("inputs", []):
            lines.append("  input  %-20s %s %s nonfinite=%s range "
                         "[%s, %s] mean %s"
                         % (i.get("name"), i.get("dtype", "?"),
                            i.get("shape", "?"), i.get("nonfinite", "-"),
                            _fmt(i.get("min")), _fmt(i.get("max")),
                            _fmt(i.get("mean"))))
    elif "first_op_error" in doc:
        lines.append("# localization failed: %s" % doc["first_op_error"])
    else:
        lines.append("# no forward internal was nonfinite (the value "
                     "was born in backward/update) — seam above is "
                     "the attribution")
    gn = doc.get("grad_norms", {})
    if gn.get("ranked"):
        lines.append("# grad norms (global %s):" % _fmt(gn.get("global")))
        for r in gn["ranked"][:10]:
            lines.append("  %-28s ||g|| %-12s ||w|| %-12s ratio %s"
                         % (r.get("group", "?")[:28],
                            _fmt(r.get("grad_norm")),
                            _fmt(r.get("weight_norm")),
                            _fmt(r.get("update_ratio"))))
    loss = doc.get("loss", {})
    if loss.get("observed"):
        lines.append("# loss: last %s ewma %s · %d anomalies"
                     % (_fmt(loss.get("last")), _fmt(loss.get("ewma")),
                        loss.get("anomalies_total", 0)))
    rng = doc.get("rng")
    if rng:
        lines.append("# rng: mx key %s · numpy %s pos %s"
                     % (rng.get("mx_key"),
                        (rng.get("numpy") or {}).get("algo"),
                        (rng.get("numpy") or {}).get("pos")))
    if doc.get("iter_state") is not None:
        lines.append("# iterator state captured (resume vocabulary): %s"
                     % json.dumps(doc["iter_state"])[:160])
    if doc.get("flight"):
        fl = doc["flight"]

        def innermost(t):
            # in_flight spans render as dicts (flight._fmt_span) but
            # older dumps may carry plain strings — show the deepest
            # open span's name either way, else the thread name
            spans = t.get("in_flight")
            if isinstance(spans, list) and spans:
                last = spans[-1]
                if isinstance(last, dict):
                    return str(last.get("name", "?"))
                return str(last)
            return str(t.get("thread", ""))

        lines.append("# flight recorder: pid %s · %s"
                     % (fl.get("pid"), ", ".join(
                         innermost(t)
                         for t in (fl.get("threads") or [])[:3])))
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="health_report",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*",
                    help="health summary / bench artifact document(s)")
    ap.add_argument("--diff", action="store_true",
                    help="diff two documents (before after)")
    ap.add_argument("--postmortem", metavar="PATH",
                    help="pretty-print a first-NaN postmortem artifact")
    ap.add_argument("--live", action="store_true",
                    help="fold + render THIS process's health state "
                         "(imports mxnet_tpu)")
    ap.add_argument("--json", action="store_true",
                    help="emit the document itself instead of a table")
    args = ap.parse_args(argv)

    if args.postmortem:
        doc = _read_json(args.postmortem)
        if doc.get("kind") != "nan_postmortem":
            print("health_report: %s is not a nan_postmortem document"
                  % args.postmortem, file=sys.stderr)
            return 2
        print(json.dumps(doc, indent=1, sort_keys=True) if args.json
              else format_postmortem(doc))
        return 0

    if args.live:
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from mxnet_tpu.profiling import health as _health
        doc = _health.flush()
        print(json.dumps(doc, indent=1, sort_keys=True) if args.json
              else format_table(doc))
        return 0

    if args.diff:
        if len(args.paths) != 2:
            print("health_report: --diff takes exactly two documents",
                  file=sys.stderr)
            return 2
        docs = []
        for p in args.paths:
            h = extract_summary(_read_json(p))
            if h is None:
                print("health_report: %s carries no health summary"
                      % p, file=sys.stderr)
                return 2
            docs.append(h)
        d = diff(*docs)
        print(json.dumps(d, indent=1, sort_keys=True) if args.json
              else format_diff(d))
        return 0

    if len(args.paths) != 1:
        print("health_report: exactly one document unless --diff/"
              "--postmortem/--live", file=sys.stderr)
        return 2
    h = extract_summary(_read_json(args.paths[0]))
    if h is None:
        print("health_report: %s carries no health summary"
              % args.paths[0], file=sys.stderr)
        return 2
    print(json.dumps(h, indent=1, sort_keys=True) if args.json
          else format_table(h))
    return 0


if __name__ == "__main__":
    sys.exit(main())
