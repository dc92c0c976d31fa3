"""Multi-process KVStore worker/server glue over the native transport.

Role assignment follows the reference's launcher contract (ref:
tools/launch.py + dmlc-core tracker env): ``DMLC_ROLE`` is ``worker`` /
``server`` / ``scheduler``, the server address comes from
``DMLC_PS_ROOT_URI``/``DMLC_PS_ROOT_PORT``, worker count from
``DMLC_NUM_WORKER``. The data plane is _native/comm.cc (the ps-lite
equivalent): rank assignment at connect, BSP merge rounds, barriers,
and an optional server-side optimizer shipped as a pickled blob
(ref: python/mxnet/kvstore.py:450-495 set_optimizer).

Recovery layer (ref: kvstore_dist.h:118-123 dead-node detection +
ps-lite resend-on-timeout): every request carries a monotonically
increasing id, so when a request dies with the connection the worker
reconnects (reclaiming its rank), pins the failed id, and RESENDS —
idempotent at the server. Retries run under exponential backoff with
jitter until ``MXNET_KVSTORE_RECOVERY_BUDGET_MS`` is spent, then raise
one clean ``MXNetError``. With the budget unset (0) the transport keeps
its legacy fail-fast behavior. The server side snapshots its whole
state on SIGTERM and restores it on start (``MXNET_KVSTORE_SNAPSHOT_
PATH``), so a restarted server — ``tools/launch.py
--restart-policy=server`` — rejoins with state intact. Deterministic
fault injection for all of this lives in ``MXNET_KVSTORE_FAULT_PLAN``
(kvstore/fault.py).
"""
from __future__ import annotations

import ctypes
import os
import pickle
import time

import numpy as np

from ..base import MXNetError
from .. import _native
# imported at module scope ON PURPOSE: the server updater runs as a
# ctypes callback on a C++ connection thread while the main thread may
# still be mid-import of the mxnet_tpu package (the kvstore_server
# import-time entry) — a lazy `from .. import profiler` inside the
# callback would deadlock on the package's import lock
from .. import profiler
from .. import tracing as _tracing
from ..telemetry import metrics as _tm_metrics
from . import fault as fault_mod

# server-process registry families (pulled into worker dumps via the
# metrics_snapshot directive); update_s caches its series, per-key
# update counters are cached in the updater closure
_server_met = _tm_metrics.lazy_metrics(lambda reg: {
    "updates": reg.counter(
        "mx_server_updates_total",
        "merge-round optimizer updates applied",
        labelnames=("key",)),
    "update_s": reg.histogram(
        "mx_server_update_seconds",
        "server-side optimizer update latency").labels(),
})

CMD_SYNC_MODE = 1
CMD_STOP = 2
CMD_SERVER_PROFILER = 3
# profiler directives ride the same server-side blob FIFO as pickled
# optimizers; pickles start with b"\x80", so this prefix is unambiguous
PROF_MAGIC = b"PROF\x00"
CMD_SET_OPTIMIZER = 4
# exit code of a SIGTERM'd server that snapshotted its state: tells the
# launcher "restartable death with state on disk" apart from a clean
# stop (0, never restarted) and a crash (anything else)
SERVER_RESTART_EXITCODE = 17


def role():
    return os.environ.get("DMLC_ROLE", "worker")


def server_address():
    uri = os.environ.get("DMLC_PS_ROOT_URI", "127.0.0.1")
    port = int(os.environ.get("DMLC_PS_ROOT_PORT", "9091"))
    return uri, port


def num_workers_env():
    return int(os.environ.get("DMLC_NUM_WORKER", "1"))


def num_servers_env():
    return int(os.environ.get("DMLC_NUM_SERVER", "1"))


def server_ports():
    """Every server's port: root_port + server_index (the launcher's
    contract; multi-server key sharding dials them all)."""
    uri, root = server_address()
    return uri, [root + i for i in range(num_servers_env())]


def request_timeout_ms():
    return int(os.environ.get("MXNET_KVSTORE_REQUEST_TIMEOUT_MS",
                              "120000"))


def recovery_budget_ms():
    """Total wall-clock the client may spend recovering ONE request
    (reconnect + resend loop). 0 disables recovery: the legacy
    fail-fast transport."""
    return int(os.environ.get("MXNET_KVSTORE_RECOVERY_BUDGET_MS", "0"))


def recovery_backoff_ms():
    return int(os.environ.get("MXNET_KVSTORE_RECOVERY_BACKOFF_MS", "50"))


def recovery_backoff_max_ms():
    return int(os.environ.get("MXNET_KVSTORE_RECOVERY_BACKOFF_MAX_MS",
                              "2000"))


_client_faults_installed = False


def _ensure_client_faults(lib):
    """Install the worker-side rules of MXNET_KVSTORE_FAULT_PLAN into
    the native client seams, once per process."""
    global _client_faults_installed
    if _client_faults_installed:
        return
    _client_faults_installed = True
    rules = fault_mod.plan_from_env()
    if rules:
        fault_mod.install_client_rules(lib, rules)


class WorkerConnection:
    """One worker's connection to the parameter server, with transparent
    reconnect/resend recovery when a budget is armed."""

    def __init__(self, host=None, port=None, timeout=30.0):
        self._lib = _native.load_comm()
        if host is None:
            host, port = server_address()
        self._host, self._port = host, int(port)
        _ensure_client_faults(self._lib)
        self._budget_ms = recovery_budget_ms()
        self.telemetry = fault_mod.RecoveryTelemetry()
        t0 = time.monotonic()
        deadline = t0 + timeout
        handle = None
        while time.monotonic() < deadline:
            handle = self._lib.mxtpu_client_connect(
                host.encode(), self._port)
            if handle:
                break
            time.sleep(0.1)
        if not handle:
            elapsed = time.monotonic() - t0
            raise MXNetError(
                f"kvstore rendezvous with server at {host}:{self._port} "
                f"failed: no connection after {elapsed:.1f}s (deadline "
                f"{timeout:.0f}s) — server process not up, wrong "
                "DMLC_PS_ROOT_URI/PORT, or the server died during startup")
        self._h = ctypes.c_void_p(handle)
        self.rank = self._lib.mxtpu_client_rank(self._h)
        self.num_workers = self._lib.mxtpu_client_num_workers(self._h)
        # bounded requests: a dead server/worker set fails the job
        # instead of hanging it (ref: kvstore_dist.h:118-123)
        self._lib.mxtpu_client_set_timeout(self._h, request_timeout_ms())

    def _fptr(self, arr):
        return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_float))

    @staticmethod
    def _explain(rc):
        if rc == -1:
            return ("request timed out or connection lost — server or a "
                    "peer worker may have died")
        if rc == -3:
            return ("server rejected the request (degraded: a worker "
                    "died mid-round, or the command was refused)")
        return f"rc={rc}"

    # -- recovery core -----------------------------------------------------
    def _call(self, op, invoke):
        """Span + wire-context wrapper around :meth:`_call_impl`: every
        dist request runs inside a ``kv.<op>`` span whose (trace_id,
        span_id) is stamped into the request header (wire v2) — the
        server opens the matching ``server_recv:<op>`` child span, the
        cross-process edge tools/trace_merge.py aligns clocks with."""
        if not _tracing.enabled():
            return self._call_impl(op, invoke)
        # optional on the transport: a stub/legacy lib without the
        # wire-v2 entry point simply sends untraced requests
        set_trace = getattr(self._lib, "mxtpu_client_set_trace", None)
        with _tracing.span("kv.%s" % op, cat="comm",
                           rank=self.rank) as sp:
            def stamped(h, _invoke=invoke, _sp=sp):
                # re-stamped per attempt: a recovery resend on a fresh
                # connection must carry the same span context
                if set_trace is not None:
                    set_trace(h, _sp.trace_id, _sp.span_id)
                return _invoke(h)
            rc = self._call_impl(op, stamped)
            if rc < 0:   # pull-style calls return positive sizes
                sp.set_attr("rc", int(rc))
            return rc

    def _call_impl(self, op, invoke):
        """Run ``invoke(handle) -> rc``; on a transport failure (rc -1)
        with a recovery budget armed, reconnect with the reclaimed rank,
        pin the failed request id, and resend until the budget is spent.
        Non-transport errors (-2 size, -3 rejected) pass through — the
        server answered, so resending cannot help.

        Not safe for CONCURRENT calls on one connection: the resend id
        is derived from the connection's request counter, which assumes
        requests are serialized per connection (ShardedConnection issues
        at most one in-flight request per underlying connection)."""
        rc = invoke(self._h)
        if rc != -1 or self._budget_ms <= 0:
            return rc
        failed_id = int(self._lib.mxtpu_client_get_next_req_id(self._h)) - 1
        tel = self.telemetry
        tel.last_op = op
        tel.last_req_id = failed_id
        reconnects_before = tel.reconnects
        sched = fault_mod.BackoffSchedule(
            self._budget_ms, base_ms=recovery_backoff_ms(),
            max_ms=recovery_backoff_max_ms())
        last_err = "request timed out or connection lost"
        while True:
            wait = sched.next_wait()
            if wait is None:
                break
            time.sleep(wait)
            tel.attempts += 1
            newh = self._lib.mxtpu_client_connect_as(
                self._host.encode(), self._port, self.rank)
            if not newh:
                last_err = ("reconnect refused (server down or "
                            "restarting)")
                continue
            old, self._h = self._h, ctypes.c_void_p(newh)
            # the resend must carry the SAME id the failed request
            # consumed — that is what makes it idempotent at the server
            self._lib.mxtpu_client_set_next_req_id(self._h, failed_id)
            # clamp the resend's deadline to the REMAINING budget: the
            # budget bounds the whole recovery, and one resend hanging
            # for the full request timeout would blow through it
            self._lib.mxtpu_client_set_timeout(
                self._h, max(1, min(request_timeout_ms(),
                                    int(sched.remaining_ms()))))
            self._lib.mxtpu_client_close(old)
            tel.reconnects += 1
            rc = invoke(self._h)
            if rc != -1:
                # recovered: lift the budget clamp — the next request is
                # a normal one and may legitimately park on the server
                # (BSP straggler wait) for the full request timeout
                self._lib.mxtpu_client_set_timeout(self._h,
                                                   request_timeout_ms())
                tel.recovered += 1
                tel.backoff_wait_ms += sched.total_wait_ms
                self._note(op, failed_id, "recovered", sched,
                           tel.reconnects - reconnects_before)
                return rc
            last_err = "resent request timed out or connection lost again"
        tel.exhausted += 1
        tel.backoff_wait_ms += sched.total_wait_ms
        tel.last_error = last_err
        self._note(op, failed_id, "exhausted", sched,
                   tel.reconnects - reconnects_before, last_err)
        raise MXNetError(
            f"kvstore recovery budget exhausted for {op} (request id "
            f"{failed_id}) against {self._host}:{self._port}: "
            f"{sched.attempts} attempts, {tel.reconnects} reconnects, "
            f"{sched.elapsed_ms():.0f}ms elapsed of "
            f"{self._budget_ms}ms budget; last error: {last_err}. "
            "Raise MXNET_KVSTORE_RECOVERY_BUDGET_MS or restart the "
            "server (tools/launch.py --restart-policy=server)")

    def _note(self, op, req_id, outcome, sched, reconnects, error=""):
        """One per-incident telemetry record (values for THIS recovery,
        not cumulative — the profiler summary sums across incidents)."""
        self.telemetry.events.append((op, req_id, outcome))
        profiler.note_recovery({
            "op": op, "req_id": req_id, "outcome": outcome,
            "rank": self.rank, "attempts": sched.attempts,
            "reconnects": reconnects,
            "backoff_wait_ms": round(sched.total_wait_ms, 3),
            "elapsed_ms": round(sched.elapsed_ms(), 1),
            "budget_ms": self._budget_ms, "error": error,
        })

    # -- data plane --------------------------------------------------------
    def init(self, key, value):
        arr = np.ascontiguousarray(value, dtype=np.float32)
        rc = self._call("init", lambda h: self._lib.mxtpu_client_init(
            h, key, self._fptr(arr), arr.size))
        if rc != 0:
            raise MXNetError(f"dist init failed for key {key}: "
                             f"{self._explain(rc)}")

    def push(self, key, value):
        arr = np.ascontiguousarray(value, dtype=np.float32)
        rc = self._call("push", lambda h: self._lib.mxtpu_client_push(
            h, key, self._fptr(arr), arr.size))
        if rc != 0:
            raise MXNetError(f"dist push failed for key {key}: "
                             f"{self._explain(rc)}")

    def push_compressed(self, key, payload):
        rc = self._call(
            "push_2bit", lambda h: self._lib.mxtpu_client_push_2bit(
                h, key, payload, len(payload)))
        if rc != 0:
            raise MXNetError(f"dist compressed push failed for key "
                             f"{key}: {self._explain(rc)}")

    def pull(self, key, shape):
        n = int(np.prod(shape)) if shape else 1
        out = np.empty(n, dtype=np.float32)
        got = self._call("pull", lambda h: self._lib.mxtpu_client_pull(
            h, key, self._fptr(out), n))
        if got < 0:
            raise MXNetError(f"dist pull failed for key {key}: "
                             f"{self._explain(got)}")
        if got != n:
            raise MXNetError(
                f"dist pull size mismatch for key {key}: got {got}, "
                f"want {n} (was the key initialized?)")
        return out.reshape(shape)

    def pull_rows(self, key, row_ids, row_len, total_elems=None):
        """Row-granular sparse pull: only the requested rows cross the
        wire (ref: kvstore_dist.h:470 PullRowSparse). ``total_elems``
        is accepted for signature parity with ShardedConnection."""
        ids = np.ascontiguousarray(row_ids, dtype=np.int32)
        out = np.empty((ids.size, int(row_len)), np.float32)
        got = self._call(
            "pull_rows", lambda h: self._lib.mxtpu_client_pull_rows(
                h, key,
                ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                ids.size, int(row_len), self._fptr(out)))
        if got < 0:
            raise MXNetError(f"dist pull_rows failed for key {key}: "
                             f"{self._explain(got)}")
        if got != out.size:
            raise MXNetError(
                f"dist pull_rows size mismatch for key {key}")
        return out

    def barrier(self):
        rc = self._call("barrier",
                        lambda h: self._lib.mxtpu_client_barrier(h))
        if rc != 0:
            raise MXNetError(f"dist barrier failed: {self._explain(rc)}")

    def command(self, cmd, body=b""):
        rc = self._call("command", lambda h: self._lib.mxtpu_client_command(
            h, cmd, body, len(body)))
        if rc != 0:
            raise MXNetError(f"dist command {cmd} failed: "
                             f"{self._explain(rc)}")

    def set_sync_mode(self, sync):
        self.command(CMD_SYNC_MODE, b"\x01" if sync else b"\x00")

    def send_optimizer(self, optimizer):
        self.command(CMD_SET_OPTIMIZER, pickle.dumps(optimizer))

    def send_profiler_command(self, directive):
        """Remote-control the SERVER process's profiler (ref:
        include/mxnet/kvstore.h:43-49 kSetConfig/kState/kPause/kDump;
        kvstore_dist_server.h:199 Controller profiler branch).
        `directive` is a dict like {"cmd": "set_state", "state": "run"}
        handled by run_server's poll loop."""
        self.command(CMD_SERVER_PROFILER,
                     PROF_MAGIC + pickle.dumps(directive))

    def trace_clock_sync(self, rounds=5):
        """Emit ``rounds`` traced no-op directives over the existing
        directive channel. Each one is a worker-side ``kv.clock_sync``
        span whose server-side ``server_recv:command`` child carries the
        SERVER clock's recv timestamp — the (send, recv, ack) triples
        tools/trace_merge.py estimates per-rank clock offsets from.
        Cheap (an empty blob the server's poll loop discards); a no-op
        when tracing is disabled."""
        if not _tracing.enabled():
            return
        body = PROF_MAGIC + pickle.dumps({"cmd": "noop"})
        for _ in range(max(int(rounds), 1)):
            self._call("clock_sync",
                       lambda h: self._lib.mxtpu_client_command(
                           h, CMD_SERVER_PROFILER, body, len(body)))

    def stop_server(self):
        self.command(CMD_STOP)

    def close(self):
        if self._h:
            self._lib.mxtpu_client_close(self._h)
            self._h = None


class ShardedConnection:
    """Worker connections to S servers with key sharding
    (ref: kvstore_dist.h:532 EncodeDefaultKey — small keys round-robin
    across servers; arrays above MXNET_KVSTORE_BIGARRAY_BOUND bytes are
    split into S contiguous slices, one per server, so the push/pull
    bandwidth of a big tensor rides every server at once).

    Derived slice keys live at 1_000_000 + key * 64 + slice; user keys
    must stay below 1e6 (the reference packs keys similarly).

    Recovery composes per shard: each WorkerConnection reconnects and
    resends against its own server independently.
    """

    _SHARD_BASE = 1_000_000

    def __init__(self):
        from concurrent.futures import ThreadPoolExecutor

        host, ports = server_ports()
        self._conns = [WorkerConnection(host, p) for p in ports]
        self.rank = self._conns[0].rank
        self.num_workers = self._conns[0].num_workers
        # element count, matching the reference's semantics
        # (kvstore_dist.h bigarray_bound_, default 1e6 elements)
        self._big = int(float(os.environ.get(
            "MXNET_KVSTORE_BIGARRAY_BOUND", "1000000")))
        self._sizes = {}
        # per-server socket IO releases the GIL inside ctypes — slice
        # requests genuinely overlap across servers
        self._pool = ThreadPoolExecutor(max_workers=len(self._conns))

    @property
    def num_servers(self):
        return len(self._conns)

    @property
    def telemetry(self):
        return [c.telemetry for c in self._conns]

    def _srv(self, key):
        return self._conns[key % len(self._conns)]

    def _slices(self, key, n):
        """[(server, derived_key, start, stop)] covering [0, n)."""
        S = len(self._conns)
        if key >= self._SHARD_BASE:
            raise MXNetError(f"kvstore key {key} out of range (<1e6)")
        if n < self._big or S == 1:
            return None
        per = (n + S - 1) // S
        out = []
        for i in range(S):
            start, stop = i * per, min((i + 1) * per, n)
            if start >= stop:
                break
            out.append((self._conns[i],
                        self._SHARD_BASE + key * 64 + i, start, stop))
        return out

    def init(self, key, value):
        flat = np.ascontiguousarray(value, dtype=np.float32).ravel()
        self._sizes[key] = flat.size
        sl = self._slices(key, flat.size)
        if sl is None:
            self._srv(key).init(key, flat)
            return
        for conn, dk, start, stop in sl:
            conn.init(dk, flat[start:stop])

    def push(self, key, value):
        flat = np.ascontiguousarray(value, dtype=np.float32).ravel()
        sl = self._slices(key, flat.size)
        if sl is None:
            self._srv(key).push(key, flat)
            return
        futs = [self._pool.submit(conn.push, dk, flat[start:stop])
                for conn, dk, start, stop in sl]
        for f in futs:
            f.result()

    def push_compressed(self, key, payload):
        if self._slices(key, self._sizes.get(key, 0)) is not None:
            raise MXNetError(
                "gradient compression cannot be combined with "
                f"multi-server big-array sharding (key {key}, "
                f"{self._sizes[key]} elements >= bound {self._big}); "
                "raise MXNET_KVSTORE_BIGARRAY_BOUND or use one server")
        self._srv(key).push_compressed(key, payload)

    def pull(self, key, shape):
        n = int(np.prod(shape)) if shape else 1
        sl = self._slices(key, n)
        if sl is None:
            return self._srv(key).pull(key, shape)
        out = np.empty(n, np.float32)

        def one(conn, dk, start, stop):
            out[start:stop] = conn.pull(dk, (stop - start,))

        futs = [self._pool.submit(one, *args) for args in sl]
        for f in futs:
            f.result()
        return out.reshape(shape)

    def pull_rows(self, key, row_ids, row_len, total_elems=None):
        # decide sharding from the caller-supplied size — _sizes is
        # only populated on the rank that called init()
        n = total_elems if total_elems is not None \
            else self._sizes.get(key, 0)
        if self._slices(key, n) is not None:
            # sliced keys: rows straddle server boundaries — pull full
            # and select (row-granularity is a single-server feature)
            full = self.pull(key, (n // int(row_len), int(row_len)))
            return full[np.asarray(row_ids, np.int32)]
        return self._srv(key).pull_rows(key, row_ids, row_len)

    def barrier(self):
        self._conns[0].barrier()

    def command(self, cmd, body=b""):
        for c in self._conns:
            c.command(cmd, body)

    def set_sync_mode(self, sync):
        self.command(CMD_SYNC_MODE, b"\x01" if sync else b"\x00")

    def send_optimizer(self, optimizer):
        self.command(CMD_SET_OPTIMIZER, pickle.dumps(optimizer))

    def send_profiler_command(self, directive):
        # every shard server gets the directive, like set_optimizer
        self.command(CMD_SERVER_PROFILER,
                     PROF_MAGIC + pickle.dumps(directive))

    def trace_clock_sync(self, rounds=5):
        for c in self._conns:
            c.trace_clock_sync(rounds)

    def stop_server(self):
        self.command(CMD_STOP)

    def close(self):
        for c in self._conns:
            c.close()
        self._conns = []
        self._pool.shutdown(wait=False)


def connect_workers():
    """Factory: one server -> plain connection; several -> sharded."""
    if num_servers_env() > 1:
        return ShardedConnection()
    return WorkerConnection()


def _apply_profiler_directive(body):
    """Run a worker-sent profiler command in THIS (server) process
    (ref: src/kvstore/kvstore_dist_server.h:199 — the reference's
    server Controller handles kSetConfig/kState/kPause/kDump by calling
    its own profiler; integration-tested 3-way by
    tests/nightly/test_server_profiling.py). ``metrics_snapshot``
    extends the same channel to the telemetry registry: the server
    writes its metric snapshot to the requested path, which the worker
    side polls into its own dump (telemetry.export.pull_server_metrics
    — the 'server metrics in the worker artifact' half of
    docs/observability.md)."""
    cmd = "?"
    try:
        d = pickle.loads(body)
        cmd = d.get("cmd")
        if cmd == "set_config":
            profiler.set_config(**d.get("kwargs", {}))
        elif cmd == "set_state":
            profiler.set_state(d.get("state", "stop"))
        elif cmd == "pause":
            profiler.pause()
        elif cmd == "resume":
            profiler.resume()
        elif cmd == "dump":
            profiler.dump()
        elif cmd == "metrics_snapshot":
            from ..telemetry import export as _tm_export
            _tm_export.dump(d["path"])
        elif cmd == "trace_dump":
            # worker-requested server trace file (the tracing analogue
            # of metrics_snapshot: trace_merge wants one file per rank)
            _tracing.export.write_trace(d["path"])
        elif cmd == "noop":
            pass   # clock-sync probe: the traced request IS the payload
    except Exception as e:  # noqa: BLE001 — the worker already got its
        # ACK (the command is async by design); a malformed directive
        # must not take down the poll loop the whole job depends on
        # (the reference also logs-and-continues, kvstore.h:387)
        import sys
        print("kvstore server: profiler command %r failed: %r"
              % (cmd, e), file=sys.stderr, flush=True)
        return
    profiler.record_event("server_profiler_cmd:%s" % cmd, "kvstore",
                          profiler._now_us(), 0)


def snapshot_path():
    return os.environ.get("MXNET_KVSTORE_SNAPSHOT_PATH", "")


def _write_snapshot(lib, path, optimizer_blob):
    """Serialize the whole native server state (committed stores,
    in-flight merges, idempotency watermarks) plus the optimizer blob
    to ``path``, FREEZING the server: after this no mutation can be
    acked, so nothing the snapshot missed is ever acknowledged-then-
    lost. Returns True on success."""
    cap = max(int(lib.mxtpu_server_snapshot(None, 0, 0)), 0) + (1 << 16)
    for _ in range(5):
        buf = ctypes.create_string_buffer(cap)
        got = int(lib.mxtpu_server_snapshot(buf, cap, 1))
        if got < 0:
            return False
        if got <= cap:
            blob = {"version": 1, "native": buf.raw[:got],
                    "optimizer_blob": optimizer_blob,
                    "saved_at": time.time()}
            from .. import checkpoint as ckpt
            try:
                # atomic_write adds fsync + a CRC32 manifest entry on
                # top of the tmp+rename this always did, so a restarted
                # server detects a bit-rotted snapshot instead of
                # preloading garbage state
                with ckpt.atomic_write(path) as f:
                    pickle.dump(blob, f)
            except OSError:
                # disk full / directory gone: the caller is a SIGTERM
                # handler — it must still reach its restartable exit,
                # not die on an uncaught traceback
                return False
            return True
        cap = got + (1 << 16)  # state grew between size query and copy
    return False


def _read_snapshot(path):
    import sys

    from .. import checkpoint as ckpt
    try:
        # CRC gate: a snapshot whose bytes do not match the manifest
        # entry is never preloaded as key-store state — it is logged,
        # counted, and treated as absent (the server starts empty)
        ckpt.verify(path)
        with open(path, "rb") as f:
            snap = pickle.load(f)
        if isinstance(snap, dict) and snap.get("version") == 1:
            return snap
    except MXNetError as e:
        print("kvstore server: snapshot %s failed CRC verification — "
              "starting empty (%s)" % (path, e), file=sys.stderr,
              flush=True)
        profiler.note_checkpoint_rejected({"path": path,
                                           "reason": "snapshot_crc"})
    except (OSError, ValueError, pickle.UnpicklingError, EOFError):
        pass
    return None


def run_server(port=None, num_workers=None, poll_ms=200):
    """Server process main loop (ref: python/mxnet/kvstore_server.py).

    Starts the native transport, then waits for control events: a
    pickled optimizer installs a Python updater applied per merge round;
    a stop command ends the loop. With MXNET_KVSTORE_SNAPSHOT_PATH set,
    SIGTERM snapshots the whole server state (frozen atomically) before
    exiting, and a start finding that snapshot restores it BEFORE
    listening — the restart-with-state half of the recovery protocol.
    """
    import signal
    import sys

    import jax

    # a parameter server is a host process: its merges and optimizer
    # updates run on the CPU backend, and it must never take the chip a
    # worker on the same host owns (a chip belongs to one process)
    jax.config.update("jax_platforms", "cpu")

    lib = _native.load_comm()
    if port is None:
        _, port = server_address()
        port += int(os.environ.get("DMLC_SERVER_ID", "0"))
    if num_workers is None:
        num_workers = num_workers_env()

    if _tracing.enabled():
        # traced worker requests become server_recv:* child spans in
        # THIS process's rings (dumped via the trace_dump directive or
        # MXTPU_TRACE_FILE at exit)
        from ..tracing import wire as _tw
        _tw.install_server_sink(lib)

    rules = fault_mod.plan_from_env()
    if rules:
        fault_mod.install_server_rules(lib, rules)

    snap_file = snapshot_path()
    restored = None
    if snap_file and os.path.exists(snap_file):
        restored = _read_snapshot(snap_file)
        if restored is not None:
            native = restored.get("native", b"")
            if not native or lib.mxtpu_server_preload(
                    native, len(native)) != 0:
                print("kvstore server: snapshot %s is malformed — "
                      "starting empty" % snap_file, file=sys.stderr,
                      flush=True)
                restored = None
            else:
                print("kvstore server: restored %d-byte snapshot from %s"
                      % (len(native), snap_file), file=sys.stderr,
                      flush=True)

    states = {}
    # the optimizer blob travels in the snapshot so a restarted server
    # keeps applying updates without the workers resending set_optimizer
    current = {"optimizer_blob": None}

    def install_updater(blob):
        optimizer = pickle.loads(blob)
        current["optimizer_blob"] = blob

        update_series = {}   # per-key counter series, resolved once

        def updater(key, recved, stored, _opt=optimizer, _states=states):
            from ..ndarray import NDArray
            import jax.numpy as jnp
            t0 = time.perf_counter()
            tr = _tracing.NOOP
            if _tracing.enabled():
                # parent the update span to the worker push that
                # completed the round (thread-local set by the native
                # connection thread handling that push, comm.cc);
                # untraced pushes (ctx 0,0) record nothing
                from ..tracing import wire as _tw
                ctx = _tw.server_parent_ctx(_native.load_comm())
                if ctx[0]:
                    tr = _tracing.span_at(ctx, "server_update",
                                          cat="comm", key=key,
                                          role="server")
            with tr, profiler.timed_region("server_update:key%d" % key,
                                           "kvstore"):
                w = NDArray(jnp.asarray(stored))
                g = NDArray(jnp.asarray(recved))
                if key not in _states:
                    _states[key] = _opt.create_state(key, w)
                _opt.update(key, w, g, _states[key])
                stored[:] = np.asarray(w._data, dtype=np.float32)
            if _tm_metrics.enabled():
                m = _server_met()
                s = update_series.get(key)
                if s is None:
                    s = update_series[key] = m["updates"].labels(
                        key=str(key))
                s.inc()
                m["update_s"].observe(time.perf_counter() - t0)

        _native.set_server_updater(updater)

    if snap_file:
        # installed BEFORE the listen socket opens and BEFORE the
        # consumed snapshot is unlinked: a SIGTERM at any point either
        # finds the old file still on disk (pre-start snapshots fail
        # cleanly and exit restartable) or snapshots the live state —
        # back-to-back preemptions can never destroy the only copy
        def _snapshot_and_exit(signum, frame):
            ok = _write_snapshot(lib, snap_file,
                                 current["optimizer_blob"])
            print("kvstore server: SIGTERM — snapshot %s: %s"
                  % (snap_file, "saved" if ok else "FAILED"),
                  file=sys.stderr, flush=True)
            # frozen either way; nothing more this process can do
            os._exit(SERVER_RESTART_EXITCODE)

        signal.signal(signal.SIGTERM, _snapshot_and_exit)

    # reconnect tolerance: default the grace to the workers' recovery
    # budget (the launcher forwards the whole env); a restored server
    # always gets a floor so reconnecting workers are never declared
    # dead before they can dial back in. Both the grace and a restored
    # optimizer are STAGED before start — the native side adopts them
    # pre-accept, so no worker resend racing the restart can degrade
    # the job or complete a merge round without the optimizer.
    grace = os.environ.get("MXNET_KVSTORE_RECOVERY_GRACE_MS")
    if grace is None:
        grace = os.environ.get("MXNET_KVSTORE_RECOVERY_BUDGET_MS", "0")
    grace = int(grace)
    if restored is not None and grace <= 0:
        grace = 30000
    if grace > 0:
        lib.mxtpu_server_set_recovery_grace(grace)

    if restored is not None and restored.get("optimizer_blob"):
        # NOTE: optimizer STATE (momentum etc.) restarts empty — only
        # stateless server optimizers keep exact trajectories across a
        # restart (docs/robustness.md documents the limitation)
        install_updater(restored["optimizer_blob"])

    rc = lib.mxtpu_server_start(int(port), int(num_workers))
    if rc != 0:
        raise MXNetError(f"kvstore server failed to start (rc={rc})")

    if restored is not None:
        # consumed — only now that the restored server is serving (a
        # LATER restart must snapshot fresh state, not resurrect this)
        try:
            os.unlink(snap_file)
        except OSError:
            pass

    buf = ctypes.create_string_buffer(64 << 20)
    while True:
        got = lib.mxtpu_server_poll(buf, len(buf), poll_ms)
        if got < 0:
            break
        if got > 0:
            blob = buf.raw[:got]
            if blob.startswith(PROF_MAGIC):
                _apply_profiler_directive(blob[len(PROF_MAGIC):])
                continue
            install_updater(blob)
