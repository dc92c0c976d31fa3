#!/usr/bin/env python
"""memory_report — render, diff, or produce HBM memory ledgers.

    python tools/memory_report.py memory.json          # ranked table
    python tools/memory_report.py --diff before.json after.json
    python tools/memory_report.py --capture resnet50-infer --batch 2 \\
        -o memory.json                                 # compile + price
    python tools/memory_report.py --hlo compiled.hlo.txt
    python tools/memory_report.py --census             # live arrays now

Input files are ``mxnet_tpu.profiling.memory`` ledger documents: peak
live bytes over the compiled program, the instruction at the peak,
and the ranked table of buffers live at that point, attributed to
framework ops (``docs/observability.md`` "Memory accounting"). The
``--diff`` mode is the perf-PR workflow — price on main, price on the
branch, attach the ranked per-op byte delta — mirroring
``telemetry_dump.py --diff`` / ``mfu_report.py --diff``; the peak
regression *gate* lives in ``tools/perf_gate.py`` (memory section).

``--capture`` compiles a named step program (the bench stage programs
or the seconds-fast ``tiny-train``) on the current backend, builds the
liveness ledger, and cross-checks it against XLA's own
``memory_analysis()`` — exit code 1 when the two disagree by more
than 15% (the ledger would be lying about where the bytes go).

Rendering and diffing import only the stdlib side of the profiling
package (no jax); --capture and --census initialize the backend.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_profiling(standalone=True):
    """The profiling package without executing mxnet_tpu/__init__.py
    (which initializes the jax backend) — the mfu_report/telemetry_dump
    pattern. With ``standalone=False`` the real package is imported."""
    if not standalone:
        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        import mxnet_tpu  # noqa: F401 — registers ops for attribution
        from mxnet_tpu import profiling
        return profiling
    import importlib
    name = "_memrep_mxtpu"
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
        print("memory_report: cannot read %s: %s" % (path, e),
              file=sys.stderr)
        raise SystemExit(2)
    if not isinstance(doc, dict) or "peak_live_bytes" not in doc:
        print("memory_report: %s is not a memory-ledger document "
              "(no 'peak_live_bytes' key)" % path, file=sys.stderr)
        raise SystemExit(2)
    return doc


def _fmt_bytes(n):
    for unit, div in (("GB", 1e9), ("MB", 1e6), ("KB", 1e3)):
        if abs(n) >= div:
            return "%.2f%s" % (n / div, unit)
    return "%dB" % n


def format_table(doc, top=25):
    """Peak headline + ranked live-at-peak buffer table."""
    lines = []
    t = doc.get("totals", {})
    lines.append("# memory_ledger: %s  peak live %s at instr #%s (%s)"
                 % (doc.get("module", "?"),
                    _fmt_bytes(doc["peak_live_bytes"]),
                    doc.get("peak_index", "?"),
                    doc.get("peak_instr", "?")))
    lines.append("# args %s · constants %s · outputs %s · "
                 "%s buffers, %s live at peak"
                 % (_fmt_bytes(t.get("arg_bytes", 0)),
                    _fmt_bytes(t.get("constant_bytes", 0)),
                    _fmt_bytes(t.get("output_bytes", 0)),
                    t.get("buffers", "?"), t.get("live_at_peak", "?")))
    xla = doc.get("xla_memory_analysis")
    if xla:
        lines.append(
            "# memory_analysis(): arg %s + out %s + temp %s - alias "
            "%s = %s  (ledger/xla = %.3f)"
            % (_fmt_bytes(xla["argument_bytes"]),
               _fmt_bytes(xla["output_bytes"]),
               _fmt_bytes(xla["temp_bytes"]),
               _fmt_bytes(xla["alias_bytes"]),
               _fmt_bytes(xla["total_bytes"]),
               doc.get("peak_vs_xla", 0.0)))
    lines.append("%-28s %8s %10s %8s %8s %8s" % (
        "op", "buffers", "bytes", "kind", "born", "dies"))
    for g in doc.get("by_op", [])[:top]:
        kinds = g.get("kinds", {})
        kind = max(kinds, key=kinds.get) if kinds else "?"
        # born/dies only meaningful per buffer; show the biggest one
        big = next((b for b in doc.get("buffers", [])
                    if (b.get("op") or b["hlo_op"]) == g["op"]), {})
        lines.append("%-28s %8d %10s %8s %8s %8s" % (
            (g["op"] or "?")[:28], g.get("buffers", 0),
            _fmt_bytes(g["bytes"]), kind,
            big.get("born", "-"), big.get("dies", "-")))
    return "\n".join(lines)


def format_diff(d, top=25):
    lines = ["# peak live bytes: %s -> %s (%+s)"
             % (_fmt_bytes(d["peak_before"]), _fmt_bytes(d["peak_after"]),
                _fmt_bytes(d["peak_delta"])),
             "# per-op live-at-peak delta (ranked by |delta bytes|)",
             "%-28s %12s %12s %12s" % ("op", "before", "after",
                                       "delta")]
    shown = 0
    for r in d["by_op"][:top]:
        if r["delta_bytes"] == 0:
            continue
        lines.append("%-28s %12s %12s %12s" % (
            r["op"][:28], _fmt_bytes(r["before_bytes"]),
            _fmt_bytes(r["after_bytes"]),
            ("+" if r["delta_bytes"] > 0 else "")
            + _fmt_bytes(r["delta_bytes"])))
        shown += 1
    if not shown:
        lines.append("(no per-op change)")
    return "\n".join(lines)


def format_census(doc, top=10):
    lines = ["# live-array census: %d arrays, %s"
             % (doc.get("arrays", 0), _fmt_bytes(doc.get(
                 "total_bytes", 0)))]
    for role, r in sorted(doc.get("by_role", {}).items(),
                          key=lambda kv: -kv[1]["bytes"]):
        lines.append("  %-16s %10s  (%d arrays)"
                     % (role, _fmt_bytes(r["bytes"]), r["arrays"]))
    for dev, d in sorted(doc.get("by_device", {}).items()):
        roles = " ".join("%s=%s" % (role, _fmt_bytes(v))
                         for role, v in sorted(d["by_role"].items()))
        lines.append("  %-16s %10s  %s"
                     % (dev, _fmt_bytes(d["total_bytes"]), roles))
    for a in doc.get("top", [])[:top]:
        lines.append("  %-16s %10s  %s %s"
                     % (a["role"], _fmt_bytes(a["bytes"]),
                        a["dtype"], a["shape"]))
    return "\n".join(lines)


def _capture_program(name, batch, hw):
    """(jitted step fn, args) for --capture (the mfu_report programs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    sys.path.insert(0, REPO)
    import programs
    if name == "tiny-train":
        step, args, _items = programs._tiny_train_step()
        return step, args
    rng = np.random.default_rng(0)
    if name in ("resnet50-infer", "resnet50"):
        fwd, pvals = programs.build_forward(batch, hw=hw)
        data = jnp.asarray(rng.standard_normal(
            (batch, 3, hw, hw), dtype=np.float32), jnp.bfloat16)
        return fwd, (jax.device_put(pvals), data)
    if name == "resnet50-train":
        step, params, moms = programs.build_train(batch)
        data = jnp.asarray(rng.standard_normal(
            (batch, 3, 224, 224), dtype=np.float32), jnp.bfloat16)
        labels = jnp.asarray(
            rng.integers(0, 1000, batch).astype(np.int32))
        return step, (params, moms, data, labels)
    print("memory_report: unknown capture program %r (try "
          "resnet50-infer, resnet50-train, tiny-train)" % name,
          file=sys.stderr)
    raise SystemExit(2)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="memory_report",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="memory-ledger document(s)")
    ap.add_argument("--diff", action="store_true",
                    help="diff two documents (before after)")
    ap.add_argument("--capture", metavar="PROGRAM",
                    help="compile PROGRAM and price its memory "
                         "(resnet50-infer | resnet50-train | "
                         "tiny-train)")
    ap.add_argument("--census", action="store_true",
                    help="census the current process's live arrays")
    ap.add_argument("--hlo", metavar="PATH",
                    help="price a raw optimized-HLO text dump")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--hw", type=int, default=224)
    ap.add_argument("-o", "--out", help="write the document here")
    ap.add_argument("--json", action="store_true",
                    help="emit the document itself instead of a table")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)

    if args.diff:
        if len(args.paths) != 2:
            print("memory_report: --diff takes exactly two documents",
                  file=sys.stderr)
            return 2
        prof = _load_profiling()
        before, after = _read_doc(args.paths[0]), _read_doc(
            args.paths[1])
        d = prof.memory.diff(before, after)
        print(json.dumps(d, indent=1) if args.json
              else format_diff(d, top=args.top))
        return 0

    if args.capture:
        prof = _load_profiling(standalone=False)
        step_fn, fn_args = _capture_program(args.capture, args.batch,
                                            args.hw)
        compiled = step_fn.lower(*fn_args).compile()
        doc = prof.memory.from_compiled(compiled)
        _finish(doc, args, prof, table=format_table)
        ratio = doc.get("peak_vs_xla")
        if ratio is not None and not (0.85 <= ratio <= 1.15):
            print("memory_report: ledger peak disagrees with "
                  "memory_analysis() by >15%% (ratio %.3f)" % ratio,
                  file=sys.stderr)
            return 1
        return 0

    if args.census:
        prof = _load_profiling(standalone=False)
        doc = prof.memory.live_census(top=args.top)
        _finish(doc, args, prof, table=format_census)
        return 0

    if args.hlo:
        prof = _load_profiling()
        with open(args.hlo, "r", encoding="utf-8") as f:
            doc = prof.memory.build_memory_ledger(f.read())
        _finish(doc, args, prof, table=format_table)
        return 0

    if len(args.paths) != 1:
        print("memory_report: exactly one document unless --diff/"
              "--capture/--census/--hlo", file=sys.stderr)
        return 2
    prof = _load_profiling()
    doc = _read_doc(args.paths[0])
    _finish(doc, args, prof, table=format_table)
    return 0


def _finish(doc, args, prof, table):
    if args.out:
        prof.memory.dump(doc, args.out)
    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        print(table(doc, top=args.top))


if __name__ == "__main__":
    sys.exit(main())
