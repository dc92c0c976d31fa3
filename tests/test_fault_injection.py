"""Fault-injection + recovery unit slice (tier-1 safe) and the
multi-process kill/restart scenarios (marked slow).

The fast half runs entirely in-process with no sockets: the
MXNET_KVSTORE_FAULT_PLAN parser, the recovery backoff schedule on a
fake clock, the request-id idempotency protocol against a stub
transport, and the server snapshot round-trip on a state-only native
server (mxtpu_server_start(port=-1) binds nothing).

The slow half launches real 4-worker jobs through tools/launch.py and
proves the acceptance scenario end-to-end: with kill_server@round=5
injected and --restart-policy=server the job finishes with bitwise-
identical final weights to a no-fault run; with restart disabled the
survivors raise MXNetError within the recovery budget.
"""
import ctypes
import os
import re
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.kvstore import fault  # noqa: E402
from mxnet_tpu.kvstore import dist  # noqa: E402


# ---------------------------------------------------------------- parser
def test_parse_fault_plan_example():
    rules = fault.parse_fault_plan(
        "drop_conn@round=3;delay_ms=500@key=0;kill_server@round=5")
    assert [r.kind for r in rules] == ["drop_conn", "delay_ms",
                                      "kill_server"]
    # round on a client rule means a BSP round -> defaults to op=push
    assert rules[0].round == 3 and rules[0].op == "push"
    assert rules[1].arg == 500 and rules[1].key == 0 and rules[1].op is None
    assert rules[2].round == 5 and rules[2].is_server_side


def test_parse_fault_plan_conditions():
    (r,) = fault.parse_fault_plan("trunc_frame@round=2@key=7@rank=1@op=pull")
    assert (r.kind, r.round, r.key, r.rank, r.op) == \
        ("trunc_frame", 2, 7, 1, "pull")
    (r,) = fault.parse_fault_plan("reject_accept=3@server=1")
    assert r.arg == 3 and r.server == 1 and r.is_server_side
    # bare reject_accept defaults to one rejection
    (r,) = fault.parse_fault_plan("reject_accept")
    assert r.arg == 1
    assert fault.parse_fault_plan("") == []
    assert fault.parse_fault_plan(" ; ;") == []


@pytest.mark.parametrize("plan,frag", [
    ("bogus@round=1", "unknown fault kind"),
    ("drop_conn@when=3", "unknown fault condition"),
    ("drop_conn@round=x", "not an integer"),
    ("delay_ms@key=0", "needs a value"),
    ("delay_ms=abc", "not an integer"),
    ("kill_server", "needs round"),
    ("drop_conn@op=frobnicate", "unknown op"),
])
def test_parse_fault_plan_rejects(plan, frag):
    with pytest.raises(MXNetError, match=frag):
        fault.parse_fault_plan(plan)


class _RecordingLib:
    def __init__(self):
        self.client_rules = []
        self.server_rules = []

    def mxtpu_fault_client_add(self, kind, op, key, rnd, arg):
        self.client_rules.append((kind, op, key, rnd, arg))

    def mxtpu_fault_server_add(self, kind, op, key, rnd, arg):
        self.server_rules.append((kind, op, key, rnd, arg))


def test_install_rules_split_and_codes():
    rules = fault.parse_fault_plan(
        "drop_conn@round=3@rank=1;kill_server@round=5;"
        "delay_ms=20@key=2@op=pull;reject_accept=2@server=0")
    lib = _RecordingLib()
    # rank filter: worker 0 skips the rank=1 rule
    assert fault.install_client_rules(lib, rules, worker_rank=0) == 1
    assert lib.client_rules == [(fault.KIND_CODES["delay_ms"],
                                 fault.OP_CODES["pull"], 2, -1, 20)]
    lib2 = _RecordingLib()
    assert fault.install_client_rules(lib2, rules, worker_rank=1) == 2
    assert lib2.client_rules[0] == (fault.KIND_CODES["drop_conn"],
                                    fault.OP_CODES["push"], -1, 3, 0)
    assert fault.install_server_rules(lib, rules, server_id=0) == 2
    assert fault.install_server_rules(_RecordingLib(), rules,
                                      server_id=1) == 1  # reject is @server=0


# ------------------------------------------------------- backoff schedule
class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


class FakeRng:
    """random() == 0.5 -> jitter factor exactly 1.0 (deterministic)."""

    def random(self):
        return 0.5


def test_backoff_schedule_exponential_on_fake_clock():
    clock = FakeClock()
    s = fault.BackoffSchedule(budget_ms=10_000, base_ms=50, max_ms=400,
                              jitter=0.25, clock=clock, rng=FakeRng())
    waits = []
    for _ in range(6):
        w = s.next_wait()
        waits.append(round(w * 1000, 3))
        clock.t += w  # pretend we slept exactly that long
    # 50 * 2^k capped at 400, no jitter with the fake rng
    assert waits == [50.0, 100.0, 200.0, 400.0, 400.0, 400.0]
    assert s.attempts == 6
    assert s.total_wait_ms == pytest.approx(sum(waits))


def test_backoff_schedule_budget_exhaustion_and_clip():
    clock = FakeClock()
    s = fault.BackoffSchedule(budget_ms=120, base_ms=50, max_ms=4000,
                              jitter=0.25, clock=clock, rng=FakeRng())
    w1 = s.next_wait()
    clock.t += w1
    w2 = s.next_wait()
    clock.t += w2
    # 50 + clipped-to-remaining 70 spends the whole budget
    assert round((w1 + w2) * 1000, 3) == 120.0
    assert s.next_wait() is None
    assert s.exhausted()


def test_backoff_schedule_jitter_bounds():
    import random
    s = fault.BackoffSchedule(budget_ms=1e9, base_ms=100, max_ms=100,
                              jitter=0.25, clock=FakeClock(),
                              rng=random.Random(7))
    for _ in range(50):
        w = s.next_wait() * 1000
        assert 75.0 <= w <= 125.0


def test_backoff_schedule_rejects_zero_budget():
    with pytest.raises(MXNetError, match="budget"):
        fault.BackoffSchedule(budget_ms=0)


# --------------------------------------- request-id idempotency (no sockets)
class StubTransport:
    """Scriptable stand-in for the native client lib: fails the first
    ``fail_requests`` requests with rc -1 (transport loss), refuses the
    first ``fail_reconnects`` reconnect attempts, and records every
    request id it sees — the assertable view of the resend protocol."""

    def __init__(self, fail_requests=1, fail_reconnects=0):
        self.next_id = 5  # pretend 4 requests already happened
        self.fail_requests = fail_requests
        self.fail_reconnects = fail_reconnects
        self.seen_push_ids = []
        self.reconnects = 0
        self.pinned = []

    def mxtpu_client_push(self, h, key, ptr, n):
        rid = self.next_id
        self.next_id += 1
        self.seen_push_ids.append(rid)
        if self.fail_requests > 0:
            self.fail_requests -= 1
            return -1
        return 0

    def mxtpu_client_get_next_req_id(self, h):
        return self.next_id

    def mxtpu_client_set_next_req_id(self, h, rid):
        self.pinned.append(rid)
        self.next_id = rid

    def mxtpu_client_connect_as(self, host, port, rank):
        if self.fail_reconnects > 0:
            self.fail_reconnects -= 1
            return 0
        self.reconnects += 1
        return 0xBEEF

    def mxtpu_client_set_timeout(self, h, ms):
        pass

    def mxtpu_client_close(self, h):
        pass


def _stub_conn(stub, budget_ms=2000):
    conn = dist.WorkerConnection.__new__(dist.WorkerConnection)
    conn._lib = stub
    conn._host, conn._port = "127.0.0.1", 9
    conn._budget_ms = budget_ms
    conn.telemetry = fault.RecoveryTelemetry()
    conn._h = ctypes.c_void_p(1)
    conn.rank, conn.num_workers = 0, 1
    return conn


def test_resend_reuses_failed_request_id(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RECOVERY_BACKOFF_MS", "1")
    stub = StubTransport(fail_requests=1)
    conn = _stub_conn(stub)
    conn.push(0, np.ones(2, np.float32))
    # the resend carried the SAME id the failed request consumed — the
    # idempotency contract the server's last_push_id watermark relies on
    assert stub.seen_push_ids == [5, 5]
    assert stub.pinned == [5]
    assert stub.reconnects == 1
    assert conn.telemetry.recovered == 1
    assert conn.telemetry.exhausted == 0


def test_recovery_retries_through_refused_reconnects(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RECOVERY_BACKOFF_MS", "1")
    stub = StubTransport(fail_requests=1, fail_reconnects=3)
    conn = _stub_conn(stub)
    conn.push(0, np.ones(2, np.float32))
    assert stub.seen_push_ids == [5, 5]
    assert conn.telemetry.reconnects == 1
    # 3 refused + 1 successful reconnect attempt
    assert conn.telemetry.attempts == 4


def test_recovery_budget_exhausted_raises_cleanly(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RECOVERY_BACKOFF_MS", "1")
    monkeypatch.setenv("MXNET_KVSTORE_RECOVERY_BACKOFF_MAX_MS", "5")

    class DeadTransport(StubTransport):
        def mxtpu_client_connect_as(self, host, port, rank):
            return 0  # server never comes back

    conn = _stub_conn(DeadTransport(fail_requests=1), budget_ms=50)
    with pytest.raises(MXNetError) as ei:
        conn.push(0, np.ones(2, np.float32))
    msg = str(ei.value)
    assert "recovery budget exhausted" in msg
    assert "push" in msg and "50ms budget" in msg
    assert conn.telemetry.exhausted == 1


def test_recovery_disabled_keeps_fail_fast():
    stub = StubTransport(fail_requests=99)
    conn = _stub_conn(stub, budget_ms=0)
    with pytest.raises(MXNetError, match="connection lost"):
        conn.push(0, np.ones(2, np.float32))
    assert stub.reconnects == 0  # no recovery without a budget


def test_non_transport_errors_pass_through(monkeypatch):
    monkeypatch.setenv("MXNET_KVSTORE_RECOVERY_BACKOFF_MS", "1")

    class RejectingTransport(StubTransport):
        def mxtpu_client_push(self, h, key, ptr, n):
            self.next_id += 1
            return -3  # server ANSWERED with a rejection: resending

    stub = RejectingTransport()
    conn = _stub_conn(stub)
    with pytest.raises(MXNetError, match="rejected"):
        conn.push(0, np.ones(2, np.float32))
    assert stub.reconnects == 0  # cannot help — no retry


def test_recovery_telemetry_reaches_profiler(monkeypatch):
    from mxnet_tpu import profiler
    monkeypatch.setenv("MXNET_KVSTORE_RECOVERY_BACKOFF_MS", "1")
    before = profiler.recovery_summary()["incidents"]
    conn = _stub_conn(StubTransport(fail_requests=1))
    conn.push(0, np.ones(2, np.float32))
    summary = profiler.recovery_summary()
    assert summary["incidents"] == before + 1
    assert summary["last"]["outcome"] == "recovered"
    assert summary["last"]["op"] == "push"


def test_rendezvous_deadline_error_names_endpoint():
    """Satellite: the connect loop must raise MXNetError with host/
    port/elapsed context, not fall through with a raw socket error."""
    with pytest.raises(MXNetError) as ei:
        dist.WorkerConnection(host="127.0.0.1", port=9, timeout=0.3)
    msg = str(ei.value)
    assert "127.0.0.1:9" in msg
    assert "deadline 0s" in msg or re.search(r"after \d+\.\d+s", msg), msg


# ----------------------------------- snapshot round-trip (state-only server)
def test_snapshot_roundtrip_state_only_server():
    """mxtpu_server_start(port=-1) runs the server state machine with
    no listening socket: write keys, snapshot, tear down, preload,
    restart, read back — the exact persistence path a SIGTERM'd server
    uses, bit-for-bit, without any process or socket games."""
    import mxnet_tpu._native as native
    lib = native.load_comm()
    assert lib.mxtpu_server_start(-1, 4) == 0
    try:
        data = np.arange(12, dtype=np.float32) * 0.5
        fptr = data.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        assert lib.mxtpu_server_key_write(7, fptr, data.size) == 0
        size = lib.mxtpu_server_snapshot(None, 0, 0)
        assert size > 0
        buf = ctypes.create_string_buffer(size)
        assert lib.mxtpu_server_snapshot(buf, size, 0) == size
        blob = buf.raw[:size]
    finally:
        lib.mxtpu_server_shutdown()

    assert lib.mxtpu_server_preload(blob, len(blob)) == 0
    assert lib.mxtpu_server_start(-1, 4) == 0
    try:
        out = np.zeros(64, np.float32)
        optr = out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        got = lib.mxtpu_server_key_read(7, optr, out.size)
        assert got == data.size
        np.testing.assert_array_equal(out[:got], data)
        # missing key reports, not zeros
        assert lib.mxtpu_server_key_read(99, optr, out.size) == -2
    finally:
        lib.mxtpu_server_shutdown()


def test_snapshot_preload_rejects_garbage():
    import mxnet_tpu._native as native
    lib = native.load_comm()
    assert lib.mxtpu_server_preload(b"not a snapshot", 14) == -1
    assert lib.mxtpu_server_preload(b"", 0) == -1


def test_python_snapshot_file_roundtrip(tmp_path):
    """The pickle envelope dist.run_server writes/reads around the
    native blob: versioned, optimizer blob carried alongside."""
    import pickle
    path = str(tmp_path / "server_0.snap")
    blob = {"version": 1, "native": b"MXTSNP01xxxx",
            "optimizer_blob": pickle.dumps({"lr": 0.5}), "saved_at": 0}
    with open(path, "wb") as f:
        pickle.dump(blob, f)
    snap = dist._read_snapshot(path)
    assert snap is not None and snap["native"].startswith(b"MXTSNP01")
    assert pickle.loads(snap["optimizer_blob"]) == {"lr": 0.5}
    # corrupted file -> None, never an exception
    with open(path, "wb") as f:
        f.write(b"\x00garbage")
    assert dist._read_snapshot(path) is None
    assert dist._read_snapshot(str(tmp_path / "absent.snap")) is None


# --------------------------------------------- multi-process scenarios (slow)
def _launch(nworkers, script, env_extra, restart=False, timeout=300):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.update(env_extra)
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", str(nworkers)]
    if restart:
        cmd += ["--restart-policy", "server"]
    cmd += [sys.executable, os.path.join(REPO, "tests", script)]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


def _final_digests(stdout):
    return set(re.findall(r"FINAL ([0-9a-f]{16})", stdout))


@pytest.mark.slow
def test_kill_server_restart_bitwise_identical_weights():
    """Acceptance: kill_server@round=5 + --restart-policy=server — the
    4-worker BSP run reconnects, resumes, and finishes with final
    weights BIT-identical to the no-fault run (idempotent resend: no
    lost, no double-applied gradient)."""
    clean = _launch(4, "dist_fault_recovery.py", {})
    sys.stdout.write(clean.stdout)
    sys.stderr.write(clean.stderr)
    assert clean.returncode == 0, "no-fault run failed"
    clean_digests = _final_digests(clean.stdout)
    assert len(clean_digests) == 1, clean.stdout

    faulted = _launch(4, "dist_fault_recovery.py", {
        "MXNET_KVSTORE_FAULT_PLAN": "kill_server@round=5",
        "MXNET_KVSTORE_RECOVERY_BUDGET_MS": "60000",
    }, restart=True)
    sys.stdout.write(faulted.stdout)
    sys.stderr.write(faulted.stderr)
    assert faulted.returncode == 0, "faulted run failed"
    assert "SIGTERM — snapshot" in faulted.stderr, "kill never fired"
    assert "restart 1/" in faulted.stderr, "server never restarted"
    assert "restored" in faulted.stderr, "snapshot never restored"
    faulted_digests = _final_digests(faulted.stdout)
    assert len(faulted_digests) == 1, faulted.stdout
    assert faulted_digests == clean_digests, (
        f"weights diverged: {faulted_digests} vs {clean_digests}")
    assert faulted.stdout.count("RECOVERY OK") == 4


@pytest.mark.slow
def test_kill_server_no_restart_fails_within_budget():
    """Acceptance: same kill, restart disabled — every survivor raises
    one clean MXNetError within the recovery budget (no hang, no raw
    socket spew)."""
    proc = _launch(4, "dist_fault_exhaust.py", {
        "MXNET_KVSTORE_FAULT_PLAN": "kill_server@round=5",
        "MXNET_KVSTORE_RECOVERY_BUDGET_MS": "6000",
    }, timeout=180)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, "survivors did not fail cleanly"
    assert proc.stdout.count("EXHAUST OK") == 4


@pytest.mark.slow
def test_drop_conn_mid_round_recovers():
    """Every worker drops its connection at its 2nd push; all reconnect,
    resend idempotently, and the BSP sums stay exact."""
    proc = _launch(2, "dist_fault_dropconn.py", {
        "MXNET_KVSTORE_FAULT_PLAN": "drop_conn@round=2",
        "MXNET_KVSTORE_RECOVERY_BUDGET_MS": "20000",
    }, timeout=180)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0
    assert proc.stdout.count("DROPCONN OK") == 2
