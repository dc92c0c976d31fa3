"""Trace-file export/load + chrome-trace conversion.

One process writes ONE trace file; ``tools/trace_merge.py`` stitches
the per-rank files of a distributed job into a single Perfetto-loadable
timeline with clock alignment and a straggler report.

File format (versioned, plain JSON)::

    {"version": 1,
     "clock": "monotonic_ns",           # absolute CLOCK_MONOTONIC
     "meta": {"pid": ..., "role": "worker", "rank": 0,
              "epoch_ns": <process epoch for profiler-relative ts>},
     "spans": [{"name", "cat", "trace", "span", "parent",
                "start_ns", "dur_ns", "cpu_ns", "tid", "thread",
                "attrs"}, ...]}

Wire-propagation format this pairs with (comm.cc wire v2): every
kvstore request header carries ``u64 trace_id | u64 span_id`` after the
fixed fields; 0 = untraced. The format is versioned by the transport's
source — both sides build from one comm.cc, and the v2 header growth
bumped the rendezvous magic ("MXTW" -> "MXT2") so a mixed v1/v2
pair fails fast at handshake; a future header change must bump it
again.
"""
from __future__ import annotations

import json
import os

from ..base import MXNetError
from . import clock

TRACE_VERSION = 1


def default_path():
    """MXTPU_TRACE_FILE, or trace.<role><rank>.json inside a launch.py
    job (processes share a cwd), else trace.json."""
    path = os.environ.get("MXTPU_TRACE_FILE")
    if path:
        return path
    role = os.environ.get("DMLC_ROLE")
    if role is None:
        return "trace.json"
    idx = os.environ.get("DMLC_SERVER_ID" if role == "server"
                         else "DMLC_WORKER_ID", "0")
    return "trace.%s%s.json" % (role, idx)


def _proc_meta():
    meta = {"pid": os.getpid(), "epoch_ns": clock.EPOCH_NS}
    role = os.environ.get("DMLC_ROLE")
    if role is not None:
        meta["role"] = role
        meta["rank"] = int(os.environ.get(
            "DMLC_SERVER_ID" if role == "server" else "DMLC_WORKER_ID",
            "0"))
    return meta


def trace_doc(spans=None, meta=None):
    from . import spans_snapshot
    doc = {"version": TRACE_VERSION, "clock": "monotonic_ns",
           "meta": _proc_meta(),
           "spans": spans if spans is not None else spans_snapshot()}
    if meta:
        doc["meta"].update(meta)
    return doc


def write_trace(path=None, spans=None, meta=None):
    """Write the process's recorded spans to ``path`` (atomically:
    tmp+rename, like telemetry exports). Returns the document."""
    path = path or default_path()
    doc = trace_doc(spans, meta)
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return doc


def load_trace(path):
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "spans" not in doc:
        raise MXNetError("%s is not a trace file (no 'spans' key)" % path)
    if doc.get("version", 0) > TRACE_VERSION:
        raise MXNetError(
            "trace file %s is version %s, this build reads <= %d"
            % (path, doc.get("version"), TRACE_VERSION))
    return doc


_pull_nonce = [0]


def pull_server_trace(kv, path, timeout=10.0, poll=0.05):
    """Fetch a kvstore SERVER process's trace file through the profiler
    directive channel ({"cmd": "trace_dump"} — the tracing analogue of
    telemetry.export.pull_server_metrics; same shared-filesystem
    contract). Returns the loaded trace document."""
    import time
    conn = getattr(kv, "_conn", None) or kv
    send = getattr(conn, "send_profiler_command", None)
    if send is None:
        raise MXNetError(
            "pull_server_trace needs a connected dist kvstore "
            "(create mx.kv.create('dist_sync') first)")
    _pull_nonce[0] += 1
    nonce_path = "%s.req%d.%d" % (path, os.getpid(), _pull_nonce[0])
    send({"cmd": "trace_dump", "path": nonce_path})
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            doc = load_trace(nonce_path)
        except (OSError, ValueError, MXNetError):
            time.sleep(poll)
            continue
        os.replace(nonce_path, path)
        return doc
    raise MXNetError(
        "server trace dump did not appear at %s within %.1fs (server "
        "down, tracing disabled there, or path not shared?)"
        % (nonce_path, timeout))


def memory_counter_events(census_doc, pid=91, ts=0.0):
    """A live-array census (``profiling.memory.live_census`` document)
    rendered as Perfetto counter tracks: one stacked 'C' counter of
    live bytes by role, plus one counter per device with its total.
    ``ts`` places the sample on the shared clock (callers pass the
    profiler's now)."""
    events = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
               "args": {"name": "HBM live bytes (census)"}}]
    by_role = census_doc.get("by_role", {})
    if by_role:
        events.append({
            "name": "mx_memory_live_bytes", "ph": "C", "ts": ts,
            "pid": pid,
            "args": {role: r.get("bytes", 0)
                     for role, r in sorted(by_role.items())}})
    for dev, d in sorted(census_doc.get("by_device", {}).items()):
        events.append({
            "name": "mx_memory_live_bytes[%s]" % dev, "ph": "C",
            "ts": ts, "pid": pid,
            "args": {"bytes": d.get("total_bytes", 0)}})
    return events


def health_counter_events(health_doc, pid=92, ts=0.0):
    """A model-health summary (``profiling.health.snapshot_doc``
    document) rendered as Perfetto counter tracks beside the PR 7
    memory track: loss + loss EWMA, global grad norm, and cumulative
    nonfinite count (stacked by seam) on the shared clock."""
    def _finite(v):
        # a NaN/Inf loss is exactly what an unhealthy run carries, and
        # json.dumps would emit bare NaN/Infinity literals that make
        # Perfetto reject the whole trace — drop the sample, keep the
        # nonfinite-count track as the signal. (Local copy by design:
        # tracing/ must import standalone, without telemetry; the
        # sibling guard lives in telemetry/export._json_safe.)
        return isinstance(v, (int, float)) and not isinstance(v, bool) \
            and v == v and v not in (float("inf"), float("-inf"))

    events = [{"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
               "args": {"name": "model health (sentry/loss/norms)"}}]
    loss = health_doc.get("loss", {})
    args = {}
    if _finite(loss.get("last")):
        args["loss"] = loss["last"]
    if _finite(loss.get("ewma")):
        args["ewma"] = loss["ewma"]
    if args:
        events.append({"name": "mx_health_loss", "ph": "C", "ts": ts,
                       "pid": pid, "args": args})
    norms = health_doc.get("norms", {})
    if _finite(norms.get("grad_norm")):
        events.append({"name": "mx_health_grad_norm", "ph": "C",
                       "ts": ts, "pid": pid,
                       "args": {"l2": norms["grad_norm"]}})
    sentry = health_doc.get("sentry", {})
    by_source = sentry.get("by_source") or {}
    events.append({
        "name": "mx_health_nonfinite_total", "ph": "C", "ts": ts,
        "pid": pid,
        "args": ({src: n for src, n in sorted(by_source.items())}
                 if by_source
                 else {"total": sentry.get("nonfinite_total", 0)})})
    return events


def chrome_events(spans, pid=0, offset_ns=0, base_ns=None):
    """Span dicts -> chrome-trace 'X' events. ``offset_ns`` is added to
    every timestamp (clock alignment); ``base_ns`` is the zero point
    (defaults to the process epoch so profiler events and spans share
    one axis)."""
    if base_ns is None:
        base_ns = clock.EPOCH_NS
    out = []
    for s in spans:
        args = {"trace": "%016x" % (s.get("trace") or 0),
                "span": "%016x" % (s.get("span") or 0)}
        if s.get("parent"):
            args["parent"] = "%016x" % s["parent"]
        if s.get("attrs"):
            args.update(s["attrs"])
        out.append({
            "name": s["name"], "cat": s.get("cat") or "span", "ph": "X",
            "ts": (s["start_ns"] + offset_ns - base_ns) / 1e3,
            "dur": s["dur_ns"] / 1e3,
            "pid": pid, "tid": s.get("tid", 0) % 100000,
            "args": args,
        })
    return out
