"""HBM memory attribution: static liveness ledger, live-array census,
and the OOM postmortem.

PRs 4-6 finished the *time* axis of observability; this module is the
*memory* axis. The reference framework answers "where do the bytes go"
statically, with NNVM's PlanMemory pass over the graph (ref:
src/nnvm/plan_memory.cc — liveness intervals + an inplace pass); the
TPU-native counterpart has XLA do the planning, so the same question
is answered in three cooperating layers:

1. **Static liveness ledger** (stdlib-only, chip-free): reuse the PR 6
   HLO text parser to compute def-use buffer intervals over the entry
   computation of a compiled executable, sweep them into peak live
   bytes + the instruction executing at the peak, and rank the buffers
   live at that point — each keyed back to a framework op through the
   same named-scope / ``jit(<fn>)`` / fusion-rule attribution channels
   the cost ledger uses. Cross-checked against XLA's own
   ``compiled.memory_analysis()`` (argument+output+temp), which is
   CPU/TPU-identical in shape, so the whole ledger is tier-1 testable.

2. **Runtime census**: classify ``jax.live_arrays()`` into parameter /
   gradient / optimizer_state / io_buffer / kv_cache / activation roles via
   NDArray-layer tagging (weakref side table — ``jax.Array`` objects
   are immutable, the tag lives next to them, never on them), reported
   **per device shard** via ``addressable_shards`` so a ZeRO-3 run
   shows 1/dp bytes per device where a replicated run shows the full
   footprint. Exported as ``mx_memory_*`` telemetry gauges and a
   Perfetto counter track in the merged chrome trace.

3. **OOM postmortem**: :func:`maybe_oom_postmortem` at the executor /
   trainer / sharded-step seams catches XLA ``RESOURCE_EXHAUSTED``
   and writes one atomic artifact combining the ranked peak-liveness
   table, the live-array census, per-device allocator stats and a
   PR 5 flight-recorder dump — the memory analogue of the hang
   flight recorder.

Env: ``MXTPU_MEMORY_CENSUS`` (0 disables tagging + the census
collector), ``MXTPU_OOM_DUMP_PATH`` (postmortem destination).
CLI: ``tools/memory_report.py`` (table / --diff / --capture / --hlo).
"""
from __future__ import annotations

import json
import os
import sys
import time
import weakref

from . import hlo
from . import ledger as _ledger

MEMORY_LEDGER_VERSION = 1
CENSUS_VERSION = 1
POSTMORTEM_VERSION = 1

# the role taxonomy (docs/observability.md "Memory accounting").
# "activation" is the default for any live array nothing tagged —
# intermediates, eval results, user temporaries. "kv_cache" is the
# serving decode plane's paged block pool (serving/generate/kvcache.py
# tags both pool arrays and re-tags them after every donated step), so
# the census, per-device gauges and the OOM postmortem name the cache
# that dominates generative-serving HBM by its actual bytes.
ROLES = ("parameter", "gradient", "optimizer_state", "io_buffer",
         "kv_cache", "activation")

# ---------------------------------------------------------------------------
# static liveness ledger
# ---------------------------------------------------------------------------

# opcodes that alias/forward their operand buffers instead of defining
# storage of their own (XLA buffer assignment gives them no allocation)
_FORWARDING = {"tuple", "get-tuple-element", "bitcast",
               "bitcast-convert", "opt-barrier", "after-all"}

import re as _re

_ALIAS_PAIR_RE = _re.compile(r"\{\s*(\d*)\s*\}\s*:\s*\(\s*(\d+)\s*[,)]")


def parse_input_output_aliases(hlo_text):
    """{output tuple index: parameter number} donation pairs from the
    HloModule header, e.g.
    ``input_output_alias={ {0}: (0, {}, may-alias), {1}: (1, {}) }``.
    The value is brace-nested, so the span is found by depth scan, not
    regex. Nested output indices ({0,1}) are rare at the entry and
    skipped."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return {}
    i = hlo_text.index("{", start)
    depth = 0
    for j in range(i, min(len(hlo_text), i + 100000)):
        c = hlo_text[j]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                body = hlo_text[i + 1:j]
                break
    else:
        return {}
    out = {}
    for oidx, pnum in _ALIAS_PAIR_RE.findall(body):
        out[int(oidx) if oidx else 0] = int(pnum)
    return out


def buffer_intervals(mod, aliases=None):
    """Def-use liveness intervals over the entry computation.

    Returns ``{buffer name: {"def": i, "last_use": j, "bytes": b,
    "instr": Instr, "aliased": bool}}`` where indices are positions in
    the entry instruction list. Forwarding opcodes (tuple / gte /
    bitcast / opt-barrier) resolve through to the defining buffer —
    they own no storage. A fusion's internal producer/consumer buffers
    never appear at all: only entry instructions allocate (the fused
    temporaries live in registers/scratch, which is exactly the HBM
    accounting the cost ledger's bytes column already uses). Donated
    parameters (``aliases``: output index -> parameter number) keep
    the donor parameter live through the aliased output's definition
    and zero the output's own footprint — the output writes into the
    donor's buffer (the reference's inplace pass, compiler-decided).
    """
    instrs = mod.entry_instructions
    index = {ins.name: i for i, ins in enumerate(instrs)}
    resolve_cache = {}

    def resolve(name):
        """Underlying storage-owning buffer names for ``name``."""
        got = resolve_cache.get(name)
        if got is not None:
            return got
        i = index.get(name)
        if i is None:
            out = ()
        else:
            ins = instrs[i]
            if ins.opcode in _FORWARDING:
                resolve_cache[name] = ()   # cycle guard
                out = []
                for op in ins.operands:
                    out.extend(resolve(op))
                out = tuple(dict.fromkeys(out))
            else:
                out = (name,)
        resolve_cache[name] = out
        return out

    bufs = {}
    end = len(instrs) - 1
    for i, ins in enumerate(instrs):
        if ins.opcode in _FORWARDING:
            continue
        # non-donated argument buffers are owned by the caller and
        # stay resident for the WHOLE execution — live [0, end]
        # regardless of where the parameter instruction sits in the
        # text. They can never be reused for temporaries, which is why
        # memory_analysis() sums argument bytes wholesale. Donation is
        # the exception; the aliased-output bookkeeping below accounts
        # for it.
        first, last = (0, end) if ins.opcode == "parameter" else (i, i)
        bufs[ins.name] = {"def": first, "last_use": last,
                          "bytes": hlo.shape_bytes(ins.shape),
                          "instr": ins, "aliased": False}
    for i, ins in enumerate(instrs):
        for op in ins.operands:
            for name in resolve(op):
                b = bufs.get(name)
                if b is not None and i > b["last_use"]:
                    b["last_use"] = i
    # outputs stay live to the end of the program. is_output marks
    # reachability from the root — a temp merely CONSUMED by the last
    # instruction shares its last_use index but is not an output
    root = next((ins for ins in instrs if ins.is_root), None)
    root_bufs = resolve(root.name) if root is not None else ()
    for name in root_bufs:
        if name in bufs:
            bufs[name]["last_use"] = end
            bufs[name]["is_output"] = True
    # donated params: the aliased output reuses the donor's storage
    if aliases and root is not None:
        # output tuple component k = root's k-th operand when the root
        # is a forwarding tuple, else the root itself for index 0;
        # parameter numbers follow textual order in XLA dumps
        comps = (root.operands if root.opcode == "tuple"
                 else [root.name])
        pnum_order = [ins.name for ins in instrs
                      if ins.opcode == "parameter"]
        for oidx, pnum in aliases.items():
            if oidx >= len(comps) or pnum >= len(pnum_order):
                continue
            donor = pnum_order[pnum]
            for name in resolve(comps[oidx]):
                b = bufs.get(name)
                if b is None or name == donor:
                    continue
                b["aliased"] = True
                b["bytes"] = 0
                d = bufs.get(donor)
                if d is not None and b["def"] > d["last_use"]:
                    d["last_use"] = b["def"]
    return bufs


def _sweep_peak(bufs, n):
    """(peak_bytes, peak_index) from interval deltas."""
    if n <= 0:
        return 0, 0
    delta = [0] * (n + 1)
    for b in bufs.values():
        delta[b["def"]] += b["bytes"]
        delta[b["last_use"] + 1] -= b["bytes"]
    live = peak = 0
    peak_i = 0
    for i in range(n):
        live += delta[i]
        if live > peak:
            peak = live
            peak_i = i
    return peak, peak_i


def build_memory_ledger(hlo_text, fn_map=None, rule_map=None,
                        module=None, top=None):
    """Price an optimized-HLO module into a memory-ledger document:
    peak live bytes, the instruction at the peak, and the ranked table
    of buffers live at that point, attributed to framework ops (and,
    for fused clusters, the subgraph rule that made them — the same
    channels as the cost ledger)."""
    mod = module if module is not None else hlo.parse_module(hlo_text)
    if fn_map is None:
        fn_map = _ledger.framework_fn_map()
    if rule_map is None:
        rule_map = _ledger.fusion_rule_map()
    aliases = parse_input_output_aliases(hlo_text) if hlo_text else {}
    bufs = buffer_intervals(mod, aliases=aliases)
    instrs = mod.entry_instructions
    n = len(instrs)
    peak, peak_i = _sweep_peak(bufs, n)
    rows = []
    arg_bytes = const_bytes = 0
    out_bytes = 0
    for name, b in bufs.items():
        ins = b["instr"]
        if ins.opcode == "parameter":
            arg_bytes += b["bytes"]
        elif ins.opcode == "constant":
            const_bytes += b["bytes"]
        is_out = b.get("is_output") and not b["aliased"] \
            and ins.opcode != "parameter"
        if is_out:
            out_bytes += b["bytes"]
        if not (b["def"] <= peak_i <= b["last_use"]) or b["bytes"] == 0:
            continue
        kind = ("argument" if ins.opcode == "parameter" else
                "constant" if ins.opcode == "constant" else
                "output" if is_out else "temp")
        op = _ledger.attribute_op_name(ins.op_name, fn_map)
        row = {
            "buffer": name,
            "hlo_op": ins.opcode,
            "op": op,
            "bytes": b["bytes"],
            "kind": kind,
            "born": b["def"],
            "dies": b["last_use"],
        }
        rule = rule_map.get(op)
        if rule:
            row["rule"] = rule
        rows.append(row)
    rows.sort(key=lambda r: (-r["bytes"], r["buffer"]))
    # aggregates are computed over the FULL live-at-peak set; `top`
    # bounds only the stored per-buffer table
    by_op = group_buffers_by_op(rows)
    live_at_peak = len(rows)
    if top is not None:
        rows = rows[:top]
    return {
        "version": MEMORY_LEDGER_VERSION,
        "kind": "memory_ledger",
        "module": mod.name,
        "peak_live_bytes": peak,
        "peak_index": peak_i,
        "peak_instr": instrs[peak_i].name if 0 <= peak_i < n else None,
        "totals": {
            "instructions": n,
            "buffers": len(bufs),
            "live_at_peak": live_at_peak,
            "arg_bytes": arg_bytes,
            "constant_bytes": const_bytes,
            "output_bytes": out_bytes,
        },
        "buffers": rows,
        "by_op": by_op,
    }


def group_buffers_by_op(rows):
    """Live-at-peak bytes re-aggregated on the framework-op
    attribution (the ranked answer to "which op's buffers hold the
    HBM at the worst moment")."""
    agg = {}
    for r in rows:
        key = r.get("op") or r["hlo_op"]
        a = agg.setdefault(key, {"op": key, "buffers": 0, "bytes": 0})
        a["buffers"] += 1
        a["bytes"] += r["bytes"]
        if r.get("rule"):
            a["rule"] = r["rule"]
        kinds = a.setdefault("kinds", {})
        kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
    return sorted(agg.values(), key=lambda a: -a["bytes"])


def from_compiled(compiled, hlo_text=None, **kwargs):
    """Memory ledger from a ``jax.stages.Compiled``, cross-checked
    against XLA's own ``memory_analysis()`` buffer-assignment totals
    (argument + output + temp = what the arena must hold at peak).
    Pass ``hlo_text``/``module=`` to share one serialization/parse
    with a cost-ledger pass over the same executable."""
    if hlo_text is None:
        hlo_text = compiled.as_text()
    doc = build_memory_ledger(hlo_text, **kwargs)
    try:
        ma = compiled.memory_analysis()
        xla = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "generated_code_bytes": int(
                ma.generated_code_size_in_bytes),
        }
        # aliased output bytes live in the donor argument's buffer;
        # XLA reports them in BOTH argument and alias columns, so the
        # resident total counts them once
        xla["total_bytes"] = (xla["argument_bytes"]
                              + xla["output_bytes"]
                              + xla["temp_bytes"]
                              - xla["alias_bytes"])
        doc["xla_memory_analysis"] = xla
        if xla["total_bytes"] > 0:
            doc["peak_vs_xla"] = round(
                doc["peak_live_bytes"] / xla["total_bytes"], 4)
    except Exception:  # noqa: BLE001 — memory_analysis is best-effort
        pass
    return doc


def from_fn(fn, *args, **kwargs):
    """Lower+compile ``fn`` on the current backend and build its
    memory ledger (plain callables are jitted here)."""
    import jax
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return from_compiled(jitted.lower(*args).compile(), **kwargs)


def diff(before, after):
    """Ranked per-op delta of live-at-peak bytes between two memory
    ledgers — the ``memory_report --diff`` payload, mirroring
    ``telemetry_dump --diff`` / ``mfu_report --diff``."""
    def index(doc):
        return {g["op"]: g for g in doc.get("by_op", [])}

    ia, ib = index(before), index(after)
    out = []
    for op in sorted(set(ia) | set(ib)):
        a, b = ia.get(op, {}), ib.get(op, {})
        out.append({
            "op": op,
            "before_bytes": a.get("bytes", 0),
            "after_bytes": b.get("bytes", 0),
            "delta_bytes": b.get("bytes", 0) - a.get("bytes", 0),
        })
    out.sort(key=lambda r: -abs(r["delta_bytes"]))
    return {
        "peak_before": before.get("peak_live_bytes", 0),
        "peak_after": after.get("peak_live_bytes", 0),
        "peak_delta": (after.get("peak_live_bytes", 0)
                       - before.get("peak_live_bytes", 0)),
        "by_op": out,
    }


def load(path):
    with open(path, "r", encoding="utf-8") as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "peak_live_bytes" not in doc:
        raise ValueError("%s is not a memory-ledger document" % path)
    return doc


def dump(doc, path):
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, path)
    return doc


# ---------------------------------------------------------------------------
# runtime census
# ---------------------------------------------------------------------------

_census = [os.environ.get("MXTPU_MEMORY_CENSUS", "1") not in (
    "0", "off", "false")]


def census_enabled():
    """MXTPU_MEMORY_CENSUS gate (default on) for tagging + collector.
    Cached at import — the tag seams run per parameter per step, so
    the gate must be one list read, not an environ lookup."""
    return _census[0]


def set_census_enabled(on):
    _census[0] = bool(on)


# id(jax.Array) -> (weakref, role). A weakref (ArrayImpl supports it)
# with a delete callback keeps the table from pinning arrays or
# serving a recycled id; tag writes are one dict store — hot-path safe
_TAGS = {}


def tag_role(x, role):
    """Tag a device array (jax.Array, NDArray, or anything exposing
    ``._data``) with a census role. No-op for non-array leaves and
    when MXTPU_MEMORY_CENSUS=0."""
    if not census_enabled():
        return x
    data = getattr(x, "_data", x)
    try:
        key = id(data)
        ref = weakref.ref(data, lambda _r, _k=key: _TAGS.pop(_k, None))
    except TypeError:
        return x  # numpy scalar / tracer / non-weakref-able
    _TAGS[key] = (ref, str(role))
    return x


def tag_tree(tree, role):
    """Tag every array leaf of a pytree (params dict, optimizer state
    tuple, batch list). Safe without jax imported: falls back to a
    shallow walk over lists/tuples/dicts."""
    if not census_enabled():
        return tree
    if "jax" in sys.modules:
        import jax
        jax.tree_util.tree_map(lambda leaf: tag_role(leaf, role), tree)
        return tree
    if isinstance(tree, dict):
        for v in tree.values():
            tag_tree(v, role)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            tag_tree(v, role)
    else:
        tag_role(tree, role)
    return tree


def role_of(x):
    """The tagged census role of an array, or None."""
    data = getattr(x, "_data", x)
    got = _TAGS.get(id(data))
    if got is None:
        return None
    ref, role = got
    return role if ref() is data else None


def live_census(arrays=None, top=0):
    """Classify live device arrays into roles, per device shard.

    ``arrays`` defaults to ``jax.live_arrays()`` (the whole process);
    pass an explicit list/tree to census just those (the ZeRO tests
    do, to isolate from unrelated suite state). Shard bytes come from
    ``addressable_shards`` metadata — no device sync, no transfer. A
    replicated array contributes its full size on EVERY device; a
    1/dp-sharded array contributes 1/dp per device — which is exactly
    the per-device proof ROADMAP item 2 asks for."""
    doc = {"version": CENSUS_VERSION, "kind": "memory_census",
           "ts": time.time(), "arrays": 0, "total_bytes": 0,
           "by_role": {}, "by_device": {}}
    if arrays is None:
        if not census_enabled():
            # tagging was off, so a whole-process walk would classify
            # every parameter/gradient as "activation" — confidently
            # wrong role totals are worse than an empty, marked doc.
            # An EXPLICIT arrays= request is still honored.
            doc["disabled"] = True
            return doc
        if "jax" not in sys.modules:
            return doc
        import jax
        try:
            arrays = jax.live_arrays()
        except Exception:  # noqa: BLE001 — backend init can fail headless
            return doc
    else:
        if "jax" in sys.modules:
            import jax
            arrays = jax.tree_util.tree_leaves(arrays)
        arrays = [getattr(a, "_data", a) for a in arrays]
    tops = []
    for arr in arrays:
        role = role_of(arr) or "activation"
        try:
            shards = arr.addressable_shards
        except Exception:  # noqa: BLE001 — deleted/donated buffers
            continue
        total = 0
        for sh in shards:
            try:
                nb = int(sh.data.nbytes)
                dev = sh.device
            except Exception:  # noqa: BLE001 — shard without data
                continue
            dkey = "%s:%d" % (getattr(dev, "platform", "dev"),
                              getattr(dev, "id", 0))
            d = doc["by_device"].setdefault(
                dkey, {"total_bytes": 0, "by_role": {}})
            d["total_bytes"] += nb
            d["by_role"][role] = d["by_role"].get(role, 0) + nb
            total += nb
        r = doc["by_role"].setdefault(role, {"bytes": 0, "arrays": 0})
        r["bytes"] += total
        r["arrays"] += 1
        doc["arrays"] += 1
        doc["total_bytes"] += total
        if top:
            tops.append((total, {
                "shape": list(getattr(arr, "shape", ())),
                "dtype": str(getattr(arr, "dtype", "?")),
                "role": role, "bytes": total}))
    if top:
        tops.sort(key=lambda t: -t[0])
        doc["top"] = [t[1] for t in tops[:top]]
    return doc


# ---------------------------------------------------------------------------
# OOM postmortem
# ---------------------------------------------------------------------------

_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "Allocation failure", "failed to allocate")
# the short marker only as a standalone word: '/models/BLOOM-7b' in an
# unrelated error message must not read as an allocation failure
_OOM_WORD_RE = _re.compile(r"\bOOM\b")


def is_oom_error(e):
    """Whether an exception is an XLA/PJRT allocation failure. Checked
    on the message, not the type: the backend raises XlaRuntimeError,
    RuntimeError, or jaxlib-versioned subclasses depending on where
    allocation fails."""
    s = str(e)
    return any(m in s for m in _OOM_MARKERS) or \
        _OOM_WORD_RE.search(s) is not None


def oom_dump_path():
    return os.environ.get("MXTPU_OOM_DUMP_PATH") or "oom_postmortem.json"


def _device_stats():
    out = {}
    if "jax" not in sys.modules:
        return out
    import jax
    try:
        devs = jax.local_devices()
    except Exception:  # noqa: BLE001
        return out
    for d in devs:
        fn = getattr(d, "memory_stats", None)
        try:
            stats = fn() if fn is not None else None
        except Exception:  # noqa: BLE001 — per-device support varies
            stats = None
        if stats:
            out["%s:%d" % (d.platform, d.id)] = {
                k: stats[k] for k in ("bytes_in_use",
                                      "peak_bytes_in_use",
                                      "bytes_limit") if k in stats}
    return out


def oom_postmortem(error=None, hlo_text=None, compiled=None,
                   source=None, path=None, extra=None):
    """Write the combined memory artifact: ranked peak-liveness table
    (when the failing program's HLO is reachable), live-array census,
    per-device allocator stats, and a flight-recorder dump. Atomic
    write; every section is individually guarded — a postmortem must
    never raise over the OOM it documents."""
    doc = {"version": POSTMORTEM_VERSION, "kind": "oom_postmortem",
           "ts": time.time()}
    if source:
        doc["source"] = str(source)[:120]
    if error is not None:
        doc["error"] = str(error)[:800]
        doc["error_type"] = type(error).__name__
    if compiled is not None and hlo_text is None:
        try:
            hlo_text = compiled.as_text()
        except Exception:  # noqa: BLE001
            pass
    if callable(hlo_text):
        try:
            hlo_text = hlo_text()
        except Exception:  # noqa: BLE001 — re-lowering can itself fail
            hlo_text = None
    if hlo_text:
        try:
            led = build_memory_ledger(hlo_text)
            led["buffers"] = led["buffers"][:25]
            doc["memory_ledger"] = led
        except Exception as e:  # noqa: BLE001
            doc["memory_ledger_error"] = repr(e)[:200]
    try:
        doc["census"] = live_census(top=10)
    except Exception as e:  # noqa: BLE001
        doc["census_error"] = repr(e)[:200]
    doc["device_stats"] = _device_stats()
    try:
        from ..tracing import flight as _flight
        doc["flight"] = _flight.snapshot(max_spans=10)
    except Exception as e:  # noqa: BLE001
        doc["flight_error"] = repr(e)[:200]
    if extra:
        doc.update(extra)
    path = path or oom_dump_path()
    try:
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        doc["path"] = path
    except OSError as e:
        doc["write_error"] = repr(e)[:200]
        print("[mxtpu] OOM postmortem write failed: %r" % (e,),
              file=sys.stderr, flush=True)
    return doc


def maybe_oom_postmortem(error, source=None, hlo_text=None,
                         compiled=None):
    """Seam helper: write a postmortem iff ``error`` is an allocation
    failure; always returns None so callers just re-raise. One
    artifact per process per failure burst: repeated OOMs inside one
    second coalesce (retry loops must not grind the disk)."""
    if error is None or not is_oom_error(error):
        return None
    now = time.monotonic()
    if now - _LAST_POSTMORTEM[0] < 1.0:
        return None
    _LAST_POSTMORTEM[0] = now
    try:
        return oom_postmortem(error=error, source=source,
                              hlo_text=hlo_text, compiled=compiled)
    except Exception as e:  # noqa: BLE001 — never mask the real OOM
        print("[mxtpu] OOM postmortem failed: %r" % (e,),
              file=sys.stderr, flush=True)
        return None


_LAST_POSTMORTEM = [-10.0]
