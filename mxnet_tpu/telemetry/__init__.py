"""Runtime telemetry: the metrics half of the observability spine.

``profiler.py`` answers *when* (chrome-trace events on a timeline);
this package answers *how much* (thread-safe Counter/Gauge/Histogram
families in a process-global registry), with exports that tie the two
back together:

    import mxnet_tpu as mx
    mx.telemetry.snapshot()                  # dict of every metric
    mx.telemetry.export.dump("telemetry.json")
    mx.telemetry.export.to_prometheus()      # scrape format
    mx.telemetry.export.dump_chrome_trace("merged.json")  # + profiler
    mx.telemetry.step.last_breakdown()       # step/data/comm/compile

Instrumented seams (all gated on ``MXTPU_TELEMETRY``, all sync-free —
mxlint MXL002 covers them): op dispatch + XLA compile/retrace
(ops/registry.py + the jax monitoring listener below), host engine
queue depth (engine.py), io data-wait (io/io.py), kvstore push/pull
bytes/latency/retries worker- and server-side (kvstore/), checkpoint
save/restore (checkpoint.py), per-step breakdown (gluon/trainer.py,
module/base_module.py). Env knobs: ``MXTPU_TELEMETRY``,
``MXTPU_TELEMETRY_FLUSH_SEC``, ``MXTPU_TELEMETRY_FILE``,
``MXTPU_TELEMETRY_VERBOSE`` (libinfo._ENV_VARS; docs/observability.md
is the catalogue).
"""
from __future__ import annotations

import sys
import threading

from ..base import get_env
from . import metrics
from . import step
from . import export
from . import timeline
from . import slo
from .metrics import enabled, registry

__all__ = ["metrics", "step", "export", "timeline", "slo", "enabled",
           "set_enabled", "registry", "snapshot", "compile_scope"]


def set_enabled(on):
    """Flip hot-path collection at runtime. Enabling also installs the
    jax compile listener and honors MXTPU_TELEMETRY_FLUSH_SEC if the
    process started with MXTPU_TELEMETRY=0 and skipped both at import
    (the listener import pulls in jax, which a disabled start avoids)."""
    metrics.set_enabled(on)
    if on:
        _install_compile_listener()
        if _flusher[0] is None and \
                get_env("MXTPU_TELEMETRY_FLUSH_SEC", 0.0, float) > 0:
            start_flusher()


def snapshot():
    return export.snapshot()


# -- XLA compile attribution ------------------------------------------------
# jax's monitoring bus reports every backend compile + jaxpr trace with
# its duration; listening there costs the hot path NOTHING per cached
# dispatch (vs ~1.3us/call for probing the jit cache size). The op name
# a compile is charged to rides this thread-local, set by
# ops/registry.OpDef.__call__ and executor builds via compile_scope().
_current_op = threading.local()


class compile_scope:
    """Attribute XLA compiles triggered inside the block to ``name``."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.prev = getattr(_current_op, "name", None)
        _current_op.name = self.name
        return self

    def __exit__(self, *exc):
        _current_op.name = self.prev
        return False


_met = metrics.lazy_metrics(lambda reg: {
    "compiles": reg.counter(
        "mx_jit_compiles_total",
        "XLA backend compiles, attributed to the op/executor that "
        "triggered them", labelnames=("op",)),
    "compile_s": reg.counter(
        "mx_jit_compile_seconds_total",
        "wall-clock spent in XLA backend compilation").labels(),
    "traces": reg.counter(
        "mx_jit_traces_total",
        "jaxpr trace events (>= compiles: nested traces count)"
        ).labels(),
    "trace_s": reg.counter(
        "mx_jit_trace_seconds_total",
        "wall-clock spent tracing python -> jaxpr").labels(),
})


def _on_event_duration(event, duration, **kwargs):
    if not enabled():
        return
    if event == "/jax/core/compile/backend_compile_duration":
        op = getattr(_current_op, "name", None) or "_unattributed"
        m = _met()
        m["compiles"].labels(op=op).inc()
        m["compile_s"].inc(duration)
        step.add_compile(duration)
    elif event == "/jax/core/compile/jaxpr_trace_duration":
        m = _met()
        m["traces"].inc()
        m["trace_s"].inc(duration)


_listener_installed = [False]


def _install_compile_listener():
    if _listener_installed[0]:
        return True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(
        _on_event_duration)
    _listener_installed[0] = True
    return True


# -- device memory high-water ----------------------------------------------
# devices whose allocator reported stats in the last collection pass;
# the census collector below backfills the others (CPU meshes report
# memory_stats()=None on every device — PR 7 regression fix: those
# meshes used to report NOTHING, and a multi-process job iterating
# jax.devices() would try non-addressable remote devices). One-element
# list rebound atomically: snapshots can run concurrently (flusher
# daemon + a user dump), and a clear()+add() window would let the
# census pass overwrite an allocator-reported gauge
_devices_with_stats = [frozenset()]


def _device_memory_collector(reg):
    """Snapshot-time pull of per-device allocator stats. Never triggers
    backend init: only reads when jax is already imported. Only
    ADDRESSABLE devices are polled — on a multi-host mesh the remote
    devices' stats belong to their own process's telemetry, and
    querying them raises."""
    if "jax" not in sys.modules:
        return
    import jax
    try:
        devs = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend init can fail headless
        return
    peak = reg.gauge("mx_device_mem_peak_bytes",
                     "allocator high-water mark per device",
                     labelnames=("device",))
    used = reg.gauge("mx_device_mem_bytes_in_use",
                     "allocator bytes currently live per device",
                     labelnames=("device",))
    found = set()
    for d in devs:
        stats_fn = getattr(d, "memory_stats", None)
        try:
            stats = stats_fn() if stats_fn is not None else None
        except Exception:  # noqa: BLE001 — per-device stat support varies
            stats = None
        if not stats:
            continue
        dev = "%s:%d" % (d.platform, d.id)
        found.add(dev)
        peak.labels(device=dev).set_max(
            stats.get("peak_bytes_in_use", 0))
        used.labels(device=dev).set(stats.get("bytes_in_use", 0))
    _devices_with_stats[0] = frozenset(found)


# -- live-array census ------------------------------------------------------
def _memory_census_collector(reg):
    """Snapshot-time live-array census: per-device, per-role live
    bytes from ``profiling.memory.live_census`` (shard metadata only —
    no device sync). Devices whose allocator exposes no stats (every
    CPU-mesh device) additionally get their ``mx_device_mem_*`` gauges
    backfilled from the census, so a multi-device mesh reports true
    per-device values instead of nothing or a process aggregate."""
    if "jax" not in sys.modules:
        return
    from ..profiling import memory as _mem
    stats_devs = _devices_with_stats[0]
    # zero existing census-fed series FIRST, before the enabled gate:
    # a role/device that emptied since the last snapshot — or a gate
    # flipped off mid-run — must read 0, not its stale value. find()
    # (not gauge()) so a disabled process never creates the families
    for name in ("mx_memory_live_bytes", "mx_memory_live_arrays"):
        fam = reg.find(name)
        if fam is not None:
            for s in fam.series():
                s.set(0)
    fam = reg.find("mx_device_mem_bytes_in_use")
    if fam is not None:
        for s in fam.series():
            if s.labels.get("device") not in stats_devs:
                s.set(0)  # backfilled device: same staleness rule
    if not _mem.census_enabled():
        return
    doc = _mem.live_census()
    live = reg.gauge("mx_memory_live_bytes",
                     "live device-array bytes per device and census "
                     "role", labelnames=("device", "role"))
    cnt = reg.gauge("mx_memory_live_arrays",
                    "live device arrays per census role",
                    labelnames=("role",))
    for role, r in doc["by_role"].items():
        cnt.labels(role=role).set(r["arrays"])
    peak = reg.gauge("mx_device_mem_peak_bytes",
                     "allocator high-water mark per device",
                     labelnames=("device",))
    used = reg.gauge("mx_device_mem_bytes_in_use",
                     "allocator bytes currently live per device",
                     labelnames=("device",))
    for dev, d in doc["by_device"].items():
        for role, nb in d["by_role"].items():
            live.labels(device=dev, role=role).set(nb)
        if dev not in stats_devs:
            used.labels(device=dev).set(d["total_bytes"])
            peak.labels(device=dev).set_max(d["total_bytes"])


# -- periodic flush ---------------------------------------------------------
class _Flusher(threading.Thread):
    def __init__(self, period, path, verbose):
        super().__init__(name="mxtpu-telemetry-flush", daemon=True)
        self.period = period
        self.path = path
        self.verbose = verbose
        self._stop = threading.Event()

    def run(self):
        while not self._stop.wait(self.period):
            try:
                snap = export.dump(self.path)
                if self.verbose:
                    n = sum(len(f["series"])
                            for f in snap["metrics"].values())
                    print("[telemetry] flushed %d series to %s"
                          % (n, self.path), file=sys.stderr, flush=True)
            except Exception as e:  # noqa: BLE001 — a full disk must not
                if self.verbose:     # kill the training process
                    print("[telemetry] flush failed: %r" % (e,),
                          file=sys.stderr, flush=True)

    def stop(self):
        self._stop.set()


_flusher = [None]


def _default_flush_path():
    """Per-process default: in a launch.py job every role shares cwd
    and inherited env, so worker and server flushers writing one
    'telemetry.json' would silently replace each other's snapshots —
    the role/rank lands in the filename instead."""
    path = get_env("MXTPU_TELEMETRY_FILE", None)
    if path is not None:
        return path
    import os
    role = os.environ.get("DMLC_ROLE")
    if role is None:
        return "telemetry.json"
    idx = os.environ.get("DMLC_SERVER_ID" if role == "server"
                         else "DMLC_WORKER_ID", "0")
    return "telemetry.%s%s.json" % (role, idx)


def start_flusher(period=None, path=None, verbose=None):
    """Start (or restart) the periodic snapshot writer; args default to
    the MXTPU_TELEMETRY_* env vars."""
    stop_flusher()
    if period is None:
        period = get_env("MXTPU_TELEMETRY_FLUSH_SEC", 0.0, float)
    if period <= 0:
        return None
    if path is None:
        path = _default_flush_path()
    if verbose is None:
        verbose = get_env("MXTPU_TELEMETRY_VERBOSE", False, bool)
    fl = _Flusher(period, path, verbose)
    fl.start()
    _flusher[0] = fl
    return fl


def stop_flusher():
    fl, _flusher[0] = _flusher[0], None
    if fl is not None:
        fl.stop()


# the collectors are pull-only and jax-free until devices exist —
# always registered so a late set_enabled(True) still reports memory.
# Order matters: the allocator pass records which devices have real
# stats, then the census pass backfills the rest
registry().register_collector(_device_memory_collector)
registry().register_collector(_memory_census_collector)
if enabled():
    # listener import touches jax; a disabled start (MXTPU_TELEMETRY=0,
    # e.g. tools/telemetry_dump.py's standalone load) must stay light
    _install_compile_listener()
    if get_env("MXTPU_TELEMETRY_FLUSH_SEC", 0.0, float) > 0:
        start_flusher()
    if get_env("MXTPU_TIMELINE_SEC", 0.0, float) > 0:
        timeline.start_ticker()
