"""From a ``jax.profiler`` capture to device busy time, per-operation
time and attributed idle gaps.

The capture is read with ``jax.profiler.ProfileData`` (JAX's own reader
of ``*.xplane.pb``). The interval arithmetic (``union_ns``) is copied
from ``mxnet_tpu/profiling/xplane.py``; its hand-written protobuf decode
and its join against an analytic cost ledger are not.

Layout, as seen in this benchmark's chip traces (TPU v5 lite, one
chip): plane ``/device:TPU:0`` carries the lines ``XLA Ops`` (one event
per executed HLO operation or fusion, named after the instruction, with
the stat ``hlo_module``), ``XLA Modules`` and ``Steps``; plane
``/host:CPU`` carries one line per host thread with the TraceMe events
(``PjitFunction(...)``, the benchmark's own ``bench.*`` annotations).
On the CPU backend, used by the tests only, operations sit on
``tf_XLA*`` lines of ``/host:CPU`` and carry the stat ``hlo_op``.
"""
import glob
import os
import re

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def find_capture(profile_dir):
    found = glob.glob(os.path.join(profile_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError("no .xplane.pb under %s" % profile_dir)
    return max(found, key=os.path.getmtime)


def short_name(name):
    """A chip capture names an operation by its whole HLO line, ``%fusion.7
    = (f32[64]...) fusion(...)``: keep the instruction's name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def load(profile_dir):
    """[{"name", "lines": [{"name", "events": [(name, start_ns, end_ns,
    text)]}]}] of the newest capture under ``profile_dir``. ``name`` is
    the instruction's short name; ``text`` of a device operation is the
    whole HLO line the capture names it by (on the CPU backend, a marker
    that the event is an operation)."""
    import warnings

    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_capture(profile_dir))
    with warnings.catch_warnings():
        # jaxlib's event_stats type trips a DeprecationWarning per event
        warnings.simplefilter("ignore", DeprecationWarning)
        return _planes(data)


def _planes(data):
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            on_device = plane.name.startswith("/device:")
            on_executor = line.name.startswith("tf_XLA")
            events = []
            for ev in line.events:
                text = None
                if on_device:
                    text = ev.name
                elif on_executor and any(k == "hlo_op" for k, _ in ev.stats):
                    text = "cpu"
                start = int(ev.start_ns)
                events.append((short_name(ev.name), start,
                               start + int(ev.duration_ns), text))
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def union_ns(intervals):
    """Total length of the union of (start, end) intervals."""
    total = 0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_of(planes):
    """(start_ns, end_ns) of the benchmark's own window annotation."""
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, s, e, _ in line["events"]:
                if name == WINDOW_SPAN:
                    return s, e
    raise ValueError("capture holds no %r annotation" % WINDOW_SPAN)


def device_ops(planes):
    """{device: [(name, start_ns, end_ns, text)]}: the executed
    operations of each device."""
    out = {}
    for plane in planes:
        on_tpu = plane["name"].startswith(DEVICE_PLANE)
        for line in plane["lines"]:
            if on_tpu and line["name"] == OPS_LINE and line["events"]:
                out.setdefault(plane["name"], []).extend(line["events"])
            elif not on_tpu and line["name"].startswith("tf_XLA"):
                ops = [e for e in line["events"] if e[3] is not None]
                if ops:
                    out.setdefault("cpu", []).extend(ops)
    return out


def _clip(events, w0, w1):
    return [(max(s, w0), min(e, w1)) for _, s, e, _ in events
            if e > w0 and s < w1]


def busy(planes):
    """Seconds in which an operation ran on the device, averaged over
    the devices seen, and the seconds of the traced window."""
    w0, w1 = window_of(planes)
    per_dev = device_ops(planes)
    if not per_dev:
        raise ValueError("capture holds no device operation")
    busy_ns = [union_ns(_clip(evs, w0, w1)) for evs in per_dev.values()]
    return {"busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "window_s": (w1 - w0) / 1e9}


def op_seconds(planes, match=None):
    """{operation name: seconds} inside the window, summed over
    devices; ``match(name, module)`` filters."""
    w0, w1 = window_of(planes)
    out = {}
    for evs in device_ops(planes).values():
        for name, s, e, module in evs:
            if e <= w0 or s >= w1:
                continue
            if match is not None and not match(name, module):
                continue
            out[name] = out.get(name, 0.0) + (min(e, w1) - max(s, w0)) / 1e9
    return out


_SUFFIX = re.compile(r"(\.(remat\d*|clone|\d+))+$")


def stem(name):
    """``fusion.12.remat2`` -> ``fusion``: one unrolled layer's copy of
    an operation differs from the next layer's by its number alone."""
    return _SUFFIX.sub("", name)


def top_ops(planes, k=10):
    """The ``k`` operations that took most device time inside the
    window, the numbered copies of one operation summed."""
    secs = {}
    for name, s in op_seconds(planes).items():
        secs[stem(name)] = secs.get(stem(name), 0.0) + s
    return [[n, s] for n, s in sorted(secs.items(),
                                      key=lambda kv: -kv[1])[:k]]


def _python_events(planes, w0, w1):
    """Host events of the threads that run Python: the lines that hold a
    ``PjitFunction(...)`` or one of the benchmark's ``bench.*``
    annotations. The runtime's own worker threads say how a call was
    carried out, not what the host was doing."""
    out = []
    for plane in planes:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            names = {n for n, _, _, _ in line["events"]}
            if not any(n.startswith(("PjitFunction(", "bench."))
                       for n in names):
                continue
            out.extend((s, e, n) for n, s, e, _ in line["events"]
                       if n != WINDOW_SPAN and e > w0 and s < w1 and e > s)
    return sorted(out)


def idle_gaps(planes, k=10, reach=256):
    """The first device's idle time inside the window by what the host
    was doing: every gap between device operations goes to the Python-
    level host event that overlaps it most (of several that cover it
    alike, the innermost); ``reach`` bounds how many events starting
    before a gap's end are looked at."""
    import bisect

    w0, w1 = window_of(planes)
    per_dev = device_ops(planes)
    if not per_dev:
        return []
    evs = sorted(_clip(per_dev[sorted(per_dev)[0]], w0, w1))
    gaps, cur = [], w0
    for s, e in evs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    host = _python_events(planes, w0, w1)
    starts = [h[0] for h in host]
    out = {}
    for g0, g1 in gaps:
        best, best_ov, best_len = "unattributed", 0, 0
        hi = bisect.bisect_left(starts, g1)
        for s, e, n in host[max(hi - reach, 0):hi]:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov or (ov == best_ov and ov > 0
                                and e - s < best_len):
                best, best_ov, best_len = n, ov, e - s
        out[best] = out.get(best, 0.0) + (g1 - g0) / 1e9
    return [[n, s] for n, s in sorted(out.items(),
                                      key=lambda kv: -kv[1])[:k]]
