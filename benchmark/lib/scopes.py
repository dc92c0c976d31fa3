"""Device time by ``jax.named_scope``, out of a capture's ``.xplane.pb``.

``jax.profiler.ProfileData`` gives an event's own stats (offset,
duration) and not its metadata's, and on a TPU the scope lives there: the
event metadata of an ``XLA Ops`` event carries the stat ``tf_op``, the
operation's ``op_name`` (``jit(mx_lfm2moe_train)/lfm2.conv/mul``, or
``transpose(jvp(lfm2.conv))/...`` in the backward program). So this reads
the protobuf's wire format directly, the few fields it needs:

- XSpace.planes = 1; XPlane: name = 2, lines = 3, event_metadata = 4
  (map: key 1, value 2), stat_metadata = 5 (map)
- XEventMetadata: id = 1, name = 2, stats = 5; XStatMetadata: id = 1,
  name = 2; XStat: metadata_id = 1, str_value = 5, ref_value = 7
- XLine: name = 2, timestamp_ns = 3, events = 4; XEvent: metadata_id = 1,
  offset_ps = 2, duration_ps = 3

Operations that the compiler makes itself (its grouped ``ragged-dot``
kernels) carry no scope; a reader finds those by the instruction's name
(``lib/xplane.op_seconds``).
"""
from benchmark.lib import spans, xplane

OPS_LINE = "XLA Ops"


def _varint(buf, i):
    r = s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        if not b & 0x80:
            return r, i
        s += 7


def _fields(buf):
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        fn, wt = tag >> 3, tag & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        else:
            raise ValueError("unsupported protobuf wire type %d" % wt)
        yield fn, wt, v


def _map_entry(buf):
    key = val = None
    for fn, _, v in _fields(buf):
        if fn == 1:
            key = v
        elif fn == 2:
            val = v
    return key, val


def _plane(buf):
    """(name, {stat id: stat name}, {metadata id: (name, [(stat id, str,
    ref)])}, [line bytes]) of one XPlane."""
    name, stat_names, metas, lines = "", {}, {}, []
    for fn, _, v in _fields(buf):
        if fn == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fn == 3:
            lines.append(v)
        elif fn == 5:
            key, val = _map_entry(v)
            for f2, _, v2 in _fields(val or b""):
                if f2 == 2:
                    stat_names[key] = bytes(v2).decode("utf-8", "replace")
        elif fn == 4:
            key, val = _map_entry(v)
            mname, stats = "", []
            for f2, _, v2 in _fields(val or b""):
                if f2 == 2:
                    mname = bytes(v2).decode("utf-8", "replace")
                elif f2 == 5:
                    sid = text = ref = None
                    for f3, _, v3 in _fields(v2):
                        if f3 == 1:
                            sid = v3
                        elif f3 == 5:
                            text = bytes(v3).decode("utf-8", "replace")
                        elif f3 == 7:
                            ref = v3
                    stats.append((sid, text, ref))
            metas[key] = (mname, stats)
    return name, stat_names, metas, lines


def _tf_op(stats, stat_names):
    for sid, text, ref in stats:
        if stat_names.get(sid) == "tf_op":
            return text if text is not None else stat_names.get(ref, "")
    return ""


def read(profile_dir, keys):
    """{key: [(start_ns, end_ns)]} of the first TPU device's executed
    operations whose scope path (``tf_op``) contains ``key``, on the
    clock ``lib/xplane.load`` gives. A capture with no such device or
    stat gives empty lists."""
    with open(xplane.find_capture(profile_dir), "rb") as f:
        space = memoryview(f.read())
    out = {k: [] for k in keys}
    planes = sorted((_plane(v) for fn, _, v in _fields(space) if fn == 1),
                    key=lambda p: p[0])
    for name, stat_names, metas, lines in planes:
        if not name.startswith(xplane.DEVICE_PLANE):
            continue
        scope_of = {}
        for mid, (_, stats) in metas.items():
            path = _tf_op(stats, stat_names)
            hit = [k for k in keys if k in path]
            if hit:
                scope_of[mid] = hit
        for line in lines:
            lname, t0, events = "", 0, []
            for fn, _, v in _fields(line):
                if fn == 2:
                    lname = bytes(v).decode("utf-8", "replace")
                elif fn == 3:
                    t0 = v
                elif fn == 4:
                    events.append(v)
            if lname != OPS_LINE:
                continue
            for ev in events:
                mid = off = dur = 0
                for fn, _, v in _fields(ev):
                    if fn == 1:
                        mid = v
                    elif fn == 2:
                        off = v
                    elif fn == 3:
                        dur = v
                for k in scope_of.get(mid, ()):
                    start = t0 + off // 1000
                    out[k].append((start, start + dur // 1000))
        break                                   # the first device only
    return out


def scope_ms(ctx, keys, names=()):
    """Device time per step of the traced slice, in ms, of the operations
    under any of the scopes ``keys`` (from ``run["scope_events"]``, the
    driver's digest of ``read``) plus those whose instruction name starts
    with one of ``names``; the union, so nothing counts twice. None where
    the run has no digest or no step."""
    events = ctx["run"].get("scope_events")
    planes = ctx["planes"]
    steps = len(spans.named(spans.host_lines(planes), spans.STEP_SPAN))
    if events is None or not steps:
        return None
    w0, w1 = xplane.window_of(planes)
    spans_ns = [(s, e) for k in keys for s, e in events.get(k, ())]
    if names:
        ops = xplane.device_ops(planes)
        first = ops.get(sorted(ops)[0], []) if ops else []
        spans_ns += [(s, e) for n, s, e, _ in first
                     if n.startswith(tuple(names))]
    clipped = [(max(s, w0), min(e, w1)) for s, e in spans_ns
               if e > w0 and s < w1]
    if not clipped:
        return None
    return xplane.union_ns(clipped) / steps / 1e6
