"""Preemption-safe checkpointing: atomic writes, CRC manifests, full
training-state capture, and worker auto-resume (mxnet_tpu/checkpoint.py,
ISSUE 2 tentpole).

The fast half runs entirely in-process: the atomic-write/manifest/CRC
primitive (any single flipped or truncated byte must be rejected at
load, never deserialized as weights), nd.save coercion + load error
wrapping, iterator state_dict round-trips, the SIGTERM PreemptionGuard
with the kill_worker@batch=N fault seam, CheckpointManager
newest-valid resume skipping corrupt candidates, and the headline
bit-identical kill/resume loop WITHOUT real process kills.

The slow half launches a real worker through tools/launch.py
--restart-policy=worker with kill_worker@batch=N injected and proves
the acceptance scenario end-to-end: the respawned worker auto-resumes
and prints a final-weights digest bit-identical to an uninterrupted
run, including with a shuffling data iterator.
"""
import hashlib
import json
import os
import re
import signal
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import checkpoint as ckpt  # noqa: E402
from mxnet_tpu import nd, profiler  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.io import NDArrayIter  # noqa: E402
from mxnet_tpu.kvstore import fault  # noqa: E402


@pytest.fixture
def fresh_faults(monkeypatch):
    """Re-read MXNET_KVSTORE_FAULT_PLAN before and after the test."""
    ckpt._reset_faults()
    yield monkeypatch
    monkeypatch.delenv("MXNET_KVSTORE_FAULT_PLAN", raising=False)
    ckpt._reset_faults()


# ------------------------------------------------------------ atomic write
def test_atomic_write_basic_and_manifest(tmp_path):
    p = str(tmp_path / "a.params")
    with ckpt.atomic_write(p) as f:
        f.write(b"hello checkpoint")
    assert open(p, "rb").read() == b"hello checkpoint"
    assert not os.path.exists(p + ".tmp")
    entry = ckpt.manifest_entry(p)
    assert entry is not None
    assert entry["size"] == 16
    import zlib
    assert entry["crc32"] == zlib.crc32(b"hello checkpoint")
    assert ckpt.verify(p) is True


def test_atomic_write_failure_preserves_old_file(tmp_path):
    p = str(tmp_path / "a.params")
    ckpt.write_bytes(p, b"old good bytes")
    with pytest.raises(RuntimeError, match="boom"):
        with ckpt.atomic_write(p) as f:
            f.write(b"half a new fi")
            raise RuntimeError("boom")
    # the torn write never reached the final name; old file verifies
    assert open(p, "rb").read() == b"old good bytes"
    assert not os.path.exists(p + ".tmp")
    assert ckpt.verify(p) is True


def test_atomic_write_text_mode(tmp_path):
    p = str(tmp_path / "sym.json")
    with ckpt.atomic_write(p, mode="w") as f:
        f.write('{"nodes": []}')
    assert ckpt.verify(p) is True
    with pytest.raises(MXNetError, match="mode"):
        with ckpt.atomic_write(p, mode="a"):
            pass


def test_every_single_byte_flip_is_rejected(tmp_path):
    """Acceptance pin: a checkpoint file with ANY single flipped byte
    is rejected by CRC at load — never loaded as weights."""
    p = str(tmp_path / "w.params")
    nd.save(p, {"w": np.arange(4, dtype=np.float32)})
    good = open(p, "rb").read()
    for i in range(len(good)):
        bad = bytearray(good)
        bad[i] ^= 0x01
        with open(p, "wb") as f:
            f.write(bytes(bad))
        with pytest.raises(MXNetError, match="integrity|CRC"):
            nd.load(p)
    # restored original loads fine
    with open(p, "wb") as f:
        f.write(good)
    out = nd.load(p)
    np.testing.assert_array_equal(out["w"].asnumpy(),
                                  np.arange(4, dtype=np.float32))


def test_every_truncation_is_rejected(tmp_path):
    p = str(tmp_path / "w.params")
    nd.save(p, {"w": np.arange(4, dtype=np.float32)})
    good = open(p, "rb").read()
    for cut in range(len(good)):
        with open(p, "wb") as f:
            f.write(good[:cut])
        with pytest.raises(MXNetError, match="integrity|size|CRC"):
            nd.load(p)


def test_verify_required_without_entry(tmp_path):
    p = str(tmp_path / "naked.params")
    with open(p, "wb") as f:
        f.write(b"x")
    assert ckpt.verify(p) is False  # no entry, not required: soft pass
    with pytest.raises(MXNetError, match="no MANIFEST"):
        ckpt.verify(p, required=True)


def test_manifest_disabled_by_env(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_CHECKPOINT_MANIFEST", "0")
    p = str(tmp_path / "w.params")
    nd.save(p, {"w": np.ones(2, np.float32)})
    assert ckpt.manifest_entry(p) is None
    assert ckpt.verify(p) is False


def test_manifest_disabled_resume_still_works(tmp_path, monkeypatch):
    """MXNET_CHECKPOINT_MANIFEST=0 is a degraded mode, not a resume
    kill switch: checkpoints written without manifests must still
    validate (by commit marker + file existence) and resume."""
    monkeypatch.setenv("MXNET_CHECKPOINT_MANIFEST", "0")
    mgr = ckpt.CheckpointManager(str(tmp_path))
    mgr.save(3, params={"w": np.full(2, 3.0, np.float32)})
    assert mgr.validate(3)
    assert mgr.latest_valid() == 3
    state = mgr.resume_latest()
    assert state["step"] == 3
    np.testing.assert_array_equal(state["params"]["w"].asnumpy(),
                                  np.full(2, 3.0, np.float32))
    # a partial save (no commit marker) is still rejected
    os.unlink(os.path.join(mgr._ckpt_dir(3), "meta.json"))
    assert not mgr.validate(3)


def test_crash_between_manifest_and_rename_keeps_old_valid(tmp_path):
    """The manifest entry lands BEFORE the rename and keeps the
    superseded generation under "prev": a preemption between the two
    steps leaves the old file paired with the new entry, which verify()
    must still accept — while corrupt bytes match neither generation."""
    p = str(tmp_path / "w.states")
    ckpt.write_bytes(p, b"generation one")
    gen1 = open(p, "rb").read()
    ckpt.write_bytes(p, b"generation two!")
    entry = ckpt.manifest_entry(p)
    assert entry["prev"]["size"] == len(gen1)
    # simulate the crash window: manifest says gen2, file is gen1
    with open(p, "wb") as f:
        f.write(gen1)
    assert ckpt.verify(p) is True
    # a third, unknown content still fails both generations
    with open(p, "wb") as f:
        f.write(b"corrupt bytes!!")
    with pytest.raises(MXNetError, match="neither"):
        ckpt.verify(p)


def test_trunc_checkpoint_fault_halves_file(tmp_path, fresh_faults):
    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "trunc_checkpoint")
    ckpt._reset_faults()
    p = str(tmp_path / "w.params")
    with ckpt.atomic_write(p) as f:
        f.write(b"x" * 1000)
    assert os.path.getsize(p) == 500
    with pytest.raises(MXNetError):
        ckpt.verify(p)


# --------------------------------------------- nd.save / nd.load satellites
def test_nd_save_coerces_numpy_and_rejects_junk(tmp_path):
    """Satellite: plain numpy values are coerced (the old code raised a
    bare AttributeError from v.asnumpy()); anything else is a clear
    TypeError naming the key and type."""
    p = str(tmp_path / "mix.params")
    nd.save(p, {"a": np.arange(3, dtype=np.float32),
                "b": mx.nd.ones((2,))})
    out = nd.load(p)
    np.testing.assert_array_equal(out["a"].asnumpy(),
                                  np.arange(3, dtype=np.float32))
    nd.save(p, [np.zeros(2, np.float32), mx.nd.ones((2,))])
    out = nd.load(p)
    assert isinstance(out, list) and len(out) == 2
    with pytest.raises(TypeError, match=r"'a'.*got str"):
        nd.save(p, {"a": "not an array"})
    with pytest.raises(TypeError, match=r"got list"):
        nd.save(p, [[1, 2, 3]])
    with pytest.raises(TypeError, match="save expects"):
        nd.save(p, 42)


def test_nd_load_wrong_format_names_file(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_CHECKPOINT_MANIFEST", "0")
    p = str(tmp_path / "garbage.params")
    with open(p, "wb") as f:
        f.write(b"this is not any container format at all")
    with pytest.raises(MXNetError) as ei:
        nd.load(p)
    msg = str(ei.value)
    assert "garbage.params" in msg
    assert "not a recognized NDArray container" in msg


def test_nd_load_torn_npz_names_probable_cause(tmp_path, monkeypatch):
    """Satellite: a truncated npz used to surface a raw
    zipfile.BadZipFile; now one MXNetError names the file and the
    probable cause (manifest disabled to exercise the decode wrap,
    not the CRC gate)."""
    monkeypatch.setenv("MXNET_CHECKPOINT_MANIFEST", "0")
    p = str(tmp_path / "torn.params")
    nd.save(p, {"w": np.arange(64, dtype=np.float32)})
    raw = open(p, "rb").read()
    with open(p, "wb") as f:
        f.write(raw[:len(raw) // 2])
    with pytest.raises(MXNetError) as ei:
        nd.load(p)
    msg = str(ei.value)
    assert "torn.params" in msg and "torn/truncated write" in msg


def test_nd_load_torn_reference_format(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_CHECKPOINT_MANIFEST", "0")
    from mxnet_tpu.ndarray.ref_serde import save_reference_buffer
    buf = save_reference_buffer({"w": np.arange(8, dtype=np.float32)})
    p = str(tmp_path / "ref.params")
    with open(p, "wb") as f:
        f.write(buf[:len(buf) - 10])  # torn tail
    with pytest.raises(MXNetError) as ei:
        nd.load(p)
    assert "ref.params" in str(ei.value)
    assert "torn/truncated" in str(ei.value)


def test_load_frombuffer_wraps_decode_failures():
    with pytest.raises(MXNetError, match="<buffer>"):
        nd.load_frombuffer(b"PK\x03\x04 torn zip bytes..........")
    # garbage that matches the reference magic then dies mid-decode
    import struct
    torn_ref = struct.pack("<QQQ", 0x112, 0, 5)
    with pytest.raises(MXNetError, match="torn/truncated"):
        nd.load_frombuffer(torn_ref)


def test_roundtrip_byte_stability(tmp_path):
    """Satellite: save -> load -> save must be byte-identical for both
    containers (manifest CRCs would otherwise churn on every rewrite)."""
    params = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
              "b": np.ones(3, np.float32)}
    # npz container
    p1, p2 = str(tmp_path / "a.params"), str(tmp_path / "b.params")
    nd.save(p1, params)
    loaded = nd.load(p1)
    nd.save(p2, loaded)
    assert open(p1, "rb").read() == open(p2, "rb").read()
    # reference container
    from mxnet_tpu.ndarray.ref_serde import (load_reference_buffer,
                                             save_reference_buffer)
    buf1 = save_reference_buffer(params)
    buf2 = save_reference_buffer(load_reference_buffer(buf1))
    assert buf1 == buf2


def test_reference_container_corrupt_byte_raises(tmp_path):
    """Satellite: reference-format checkpoint through
    save -> corrupt-one-byte -> load must raise, not return wrong
    weights (CRC gate when manifested; decode wrap regardless)."""
    from mxnet_tpu.ndarray.ref_serde import save_reference_buffer
    p = str(tmp_path / "ref.params")
    ckpt.write_bytes(p, save_reference_buffer(
        {"w": np.arange(6, dtype=np.float32)}))
    raw = bytearray(open(p, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(p, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(MXNetError):
        nd.load(p)


# ------------------------------------------------- trainer states satellite
def test_trainer_load_states_syncs_local_updaters(tmp_path):
    """Satellite: in the update_on_kvstore branch load_states never
    re-synced _updaters, so a later fallback to local update used stale
    optimizer state. The loaded state must be mirrored locally."""
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.kvstore import create as kv_create

    def make(seed):
        mx.random.seed(seed)
        net = nn.Dense(2, in_units=3)
        net.initialize()
        return net

    net = make(7)
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.1})
    x = mx.nd.array(np.arange(12, dtype=np.float32).reshape(4, 3))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    fname = str(tmp_path / "trainer.states")
    tr.save_states(fname)

    net2 = make(7)
    kv = kv_create("local")
    tr2 = gluon.Trainer(net2.collect_params(), "adam",
                        {"learning_rate": 0.1}, kvstore=kv,
                        update_on_kvstore=True)
    tr2.load_states(fname)
    # kvstore updater holds the state...
    assert len(kv._updater.states) > 0
    # ...and the LOCAL updater now mirrors it instead of staying empty
    assert set(tr2._updaters.states.keys()) == \
        set(kv._updater.states.keys())
    assert tr2._updaters.optimizer is tr2._optimizer
    # the mirrored tensors carry the exact loaded values (get_states
    # pickles a numpy-ified copy — byte equality is the full check)
    assert tr2._updaters.get_states(dump_optimizer=False) == \
        kv._updater.get_states(dump_optimizer=False)


# ------------------------------------------------------ iterator state dicts
def _batch_sig(b):
    return b.data[0].asnumpy().tobytes()


def test_ndarrayiter_state_exact_resume_across_epochs():
    data = np.arange(60, dtype=np.float32).reshape(30, 2)
    ref = NDArrayIter(data, batch_size=8, shuffle=True, seed=42)
    # consume 2 batches, capture, then record the uninterrupted stream
    for _ in range(2):
        ref.next()
    state = ref.state_dict()
    expect = []
    for _ in range(2):  # finish epoch + one full later epoch
        try:
            while True:
                expect.append(_batch_sig(ref.next()))
        except StopIteration:
            ref.reset()
    fresh = NDArrayIter(data, batch_size=8, shuffle=True, seed=42)
    fresh.next()  # desync on purpose: load_state_dict must fully restore
    fresh.load_state_dict(state)
    got = []
    for _ in range(2):
        try:
            while True:
                got.append(_batch_sig(fresh.next()))
        except StopIteration:
            fresh.reset()
    assert got == expect


def test_ndarrayiter_state_rejects_wrong_dataset():
    it = NDArrayIter(np.zeros((10, 2), np.float32), batch_size=2)
    other = NDArrayIter(np.zeros((12, 2), np.float32), batch_size=2)
    with pytest.raises(MXNetError, match="not the same dataset"):
        other.load_state_dict(it.state_dict())
    with pytest.raises(MXNetError, match="version-1"):
        it.load_state_dict({"bogus": True})


def test_iterator_state_rejects_changed_batching():
    """cursor/consumed are tied to the batching config: a resume with a
    different batch_size or shuffle mode must raise, not silently
    misalign the data stream."""
    data = np.zeros((16, 2), np.float32)
    it = NDArrayIter(data, batch_size=4, shuffle=True, seed=1)
    state = it.state_dict()
    with pytest.raises(MXNetError, match="batch_size"):
        NDArrayIter(data, batch_size=2, shuffle=True,
                    seed=1).load_state_dict(state)
    with pytest.raises(MXNetError, match="shuffle"):
        NDArrayIter(data, batch_size=4, shuffle=False).load_state_dict(
            state)
    with pytest.raises(MXNetError, match="last_batch_handle"):
        NDArrayIter(data, batch_size=4, shuffle=True, seed=1,
                    last_batch_handle="discard").load_state_dict(state)


def test_imagerecorditer_state_rejects_changed_batching(recfile):
    it = mx.io.ImageRecordIter(path_imgrec=recfile, data_shape=(3, 32, 32),
                               batch_size=8, shuffle=True, seed=5,
                               preprocess_threads=1)
    state = it.state_dict()
    it.close()
    it2 = mx.io.ImageRecordIter(path_imgrec=recfile,
                                data_shape=(3, 32, 32), batch_size=4,
                                shuffle=True, seed=5,
                                preprocess_threads=1)
    with pytest.raises(MXNetError, match="batch_size"):
        it2.load_state_dict(state)
    it2.close()


def test_ndarrayiter_rollover_cache_survives_state():
    data = np.arange(20, dtype=np.float32).reshape(10, 2)
    it = NDArrayIter(data, batch_size=4, shuffle=True, seed=3,
                     last_batch_handle="roll_over")
    try:
        while True:
            it.next()
    except StopIteration:
        pass
    state = it.state_dict()  # 2 samples carried to next epoch
    it.reset()
    first = _batch_sig(it.next())
    it2 = NDArrayIter(data, batch_size=4, shuffle=True, seed=3,
                      last_batch_handle="roll_over")
    it2.load_state_dict(state)
    it2.reset()
    assert _batch_sig(it2.next()) == first


def test_wrap_iter_state_delegates(tmp_path):
    csv = tmp_path / "d.csv"
    np.savetxt(csv, np.arange(24, dtype=np.float32).reshape(12, 2),
               delimiter=",")
    it = mx.io.CSVIter(data_csv=str(csv), data_shape=(2,), batch_size=3)
    it.next()
    state = it.state_dict()
    assert state["type"] == "CSVIter"
    it2 = mx.io.CSVIter(data_csv=str(csv), data_shape=(2,), batch_size=3)
    it2.load_state_dict(state)
    assert _batch_sig(it2.next()) == _batch_sig(it.next())
    # un-consumed lookahead cannot be checkpointed
    it.iter_next()
    with pytest.raises(MXNetError, match="lookahead"):
        it.state_dict()


@pytest.fixture(scope="module")
def recfile(tmp_path_factory):
    from mxnet_tpu.recordio import IRHeader, MXIndexedRecordIO, pack_img
    d = tmp_path_factory.mktemp("rec")
    prefix = str(d / "train")
    rec = MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.default_rng(0)
    for i in range(64):
        img = rng.integers(0, 255, (32, 32, 3), dtype=np.uint8)
        rec.write_idx(i, pack_img(IRHeader(0, float(i), i, 0), img))
    rec.close()
    return prefix + ".rec"


def test_imagerecorditer_state_exact_resume(recfile):
    """Mid-epoch resume of the record iterator: the respawned iterator
    regenerates the same shuffled order (pre-shuffle RNG state) and
    skips the consumed batches — remaining label stream identical."""
    it = mx.io.ImageRecordIter(path_imgrec=recfile, data_shape=(3, 32, 32),
                               batch_size=8, shuffle=True, seed=5,
                               preprocess_threads=1)
    for _ in range(3):
        it.next()
    state = it.state_dict()
    expect = []
    try:
        while True:
            expect.append(it.next().label[0].asnumpy().tolist())
    except StopIteration:
        pass
    it2 = mx.io.ImageRecordIter(path_imgrec=recfile,
                                data_shape=(3, 32, 32), batch_size=8,
                                shuffle=True, seed=5,
                                preprocess_threads=1)
    it2.load_state_dict(state)
    got = []
    try:
        while True:
            got.append(it2.next().label[0].asnumpy().tolist())
    except StopIteration:
        pass
    assert got == expect
    it.close()
    it2.close()


# ------------------------------------------------------- preemption guard
def test_preemption_guard_defers_sigterm():
    guard = ckpt.PreemptionGuard()
    try:
        assert not guard.preempted
        os.kill(os.getpid(), signal.SIGTERM)
        # handler only set the flag; we are still running
        assert guard.preempted
        assert guard.batch_done() is True
    finally:
        guard.restore()
    assert signal.getsignal(signal.SIGTERM) != guard._handler


def test_kill_worker_fault_fires_at_global_batch(fresh_faults):
    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "kill_worker@batch=3")
    guard = ckpt.PreemptionGuard()
    try:
        assert guard.batch_done() is False  # batch 1
        assert guard.batch_done() is False  # batch 2
        assert guard.batch_done() is True   # batch 3: SIGTERM fired
    finally:
        guard.restore()


def test_kill_worker_does_not_refire_after_resume(fresh_faults):
    """batch=N counts GLOBAL batches: a resumed worker restores the
    counter past N, so the kill cannot refire on its own recovery."""
    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "kill_worker@batch=3")
    guard = ckpt.PreemptionGuard()
    try:
        guard.batches = 5  # resumed past the kill point
        for _ in range(10):
            assert guard.batch_done() is False
    finally:
        guard.restore()


def test_kill_worker_rank_filter(fresh_faults):
    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN",
                        "kill_worker@batch=1@rank=3")
    fresh_faults.setenv("DMLC_WORKER_ID", "0")
    guard = ckpt.PreemptionGuard()
    try:
        assert guard._kill_rules == []
        assert guard.batch_done() is False
    finally:
        guard.restore()


def test_fault_plan_new_kinds_parse_and_validate():
    rules = fault.parse_fault_plan(
        "kill_worker@batch=7;trunc_checkpoint;corrupt_checkpoint@round=2")
    assert [r.kind for r in rules] == \
        ["kill_worker", "trunc_checkpoint", "corrupt_checkpoint"]
    assert rules[0].batch == 7 and rules[0].is_checkpoint_side
    assert rules[2].round == 2
    with pytest.raises(MXNetError, match="needs batch"):
        fault.parse_fault_plan("kill_worker")
    with pytest.raises(MXNetError, match="only applies to"):
        fault.parse_fault_plan("drop_conn@batch=3")
    # conditions the python-side seams never read must fail loudly,
    # not be silently dropped (the module's own contract)
    with pytest.raises(MXNetError, match="do not apply"):
        fault.parse_fault_plan("kill_worker@batch=5@round=3")
    with pytest.raises(MXNetError, match="do not apply"):
        fault.parse_fault_plan("trunc_checkpoint@server=1")
    with pytest.raises(MXNetError, match="do not apply"):
        fault.parse_fault_plan("corrupt_checkpoint@key=0")
    # rank stays allowed on all three (per-worker fault targeting)
    fault.parse_fault_plan("kill_worker@batch=5@rank=1;"
                           "corrupt_checkpoint@round=2@rank=0")
    # python-side kinds never reach the native seams
    class _Rec:
        def __init__(self):
            self.calls = []

        def mxtpu_fault_client_add(self, *a):
            self.calls.append(a)

        def mxtpu_fault_server_add(self, *a):
            self.calls.append(a)
    lib = _Rec()
    assert fault.install_client_rules(lib, rules, worker_rank=0) == 0
    assert fault.install_server_rules(lib, rules, server_id=0) == 0
    assert lib.calls == []


def test_worker_restart_exitcode_pinned_to_launcher():
    """tools/launch.py mirrors the sentinel without importing the
    package; the two constants must stay equal."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_launch", os.path.join(REPO, "tools", "launch.py"))
    launch = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(launch)
    assert launch.WORKER_RESTART_EXITCODE == ckpt.WORKER_RESTART_EXITCODE
    from mxnet_tpu.kvstore import dist
    assert ckpt.WORKER_RESTART_EXITCODE != dist.SERVER_RESTART_EXITCODE


# ---------------------------------------------------- checkpoint manager
def test_manager_save_load_roundtrip(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=3)
    it = NDArrayIter(np.arange(16, dtype=np.float32).reshape(8, 2),
                     batch_size=2, shuffle=True, seed=1)
    it.next()
    mx.random.seed(99)
    cdir = mgr.save(5, params={"w": np.arange(3, dtype=np.float32)},
                    data_iter=it, extra={"epoch": 2})
    assert os.path.isdir(cdir)
    man = ckpt.read_manifest(cdir)
    assert man is not None and "meta.json" in man["files"]
    state = mgr.load(5)
    assert state["step"] == 5 and state["extra"]["epoch"] == 2
    np.testing.assert_array_equal(state["params"]["w"].asnumpy(),
                                  np.arange(3, dtype=np.float32))
    assert state["iter_state"]["type"] == "NDArrayIter"
    assert state["rng"] is not None


def test_manager_prune_keeps_newest(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, params={"w": np.full(2, step, np.float32)})
    assert mgr.steps() == [3, 4]


def test_manager_skips_corrupt_newest(tmp_path, fresh_faults):
    """A torn newest checkpoint (injected corrupt_checkpoint) is
    rejected by CRC, warned about, counted, and resume falls back to
    the previous valid one."""
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, params={"w": np.ones(2, np.float32)})
    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "corrupt_checkpoint")
    ckpt._reset_faults()
    mgr.save(2, params={"w": np.full(2, 2.0, np.float32)})
    fresh_faults.delenv("MXNET_KVSTORE_FAULT_PLAN")
    ckpt._reset_faults()
    assert mgr.validate(1) and not mgr.validate(2)
    before = profiler.recovery_summary()["checkpoints_rejected"]
    with pytest.warns(RuntimeWarning, match="torn or corrupt"):
        assert mgr.latest_valid() == 1
    summary = profiler.recovery_summary()
    assert summary["checkpoints_rejected"] == before + 1
    with pytest.warns(RuntimeWarning):
        state = mgr.resume_latest()
    assert state["step"] == 1
    np.testing.assert_array_equal(state["params"]["w"].asnumpy(),
                                  np.ones(2, np.float32))


def test_manager_trunc_checkpoint_fault(tmp_path, fresh_faults):
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=5)
    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "trunc_checkpoint")
    ckpt._reset_faults()
    mgr.save(1, params={"w": np.ones(2, np.float32)})
    fresh_faults.delenv("MXNET_KVSTORE_FAULT_PLAN")
    ckpt._reset_faults()
    assert not mgr.validate(1)
    with pytest.warns(RuntimeWarning):
        assert mgr.latest_valid() is None
    assert mgr.resume_latest() is None


def test_manager_partial_save_is_invalid(tmp_path):
    """A checkpoint missing its meta.json commit marker (preemption
    mid-save) never validates."""
    mgr = ckpt.CheckpointManager(str(tmp_path))
    mgr.save(1, params={"w": np.ones(2, np.float32)})
    cdir = mgr._ckpt_dir(1)
    os.unlink(os.path.join(cdir, "meta.json"))
    assert not mgr.validate(1)


def test_resume_restores_rng_chain(tmp_path):
    mgr = ckpt.CheckpointManager(str(tmp_path))
    mx.random.seed(1234)
    np.random.seed(77)
    mgr.save(1, params={"w": np.zeros(1, np.float32)})
    expect_mx = np.asarray(mx.random.next_key()).copy()
    expect_np = np.random.rand(3)
    # perturb both chains, then resume: draws must replay exactly
    mx.random.seed(1)
    np.random.seed(1)
    mgr.resume_latest()
    np.testing.assert_array_equal(np.asarray(mx.random.next_key()),
                                  expect_mx)
    np.testing.assert_array_equal(np.random.rand(3), expect_np)


# --------------------------------- headline: in-process kill/resume slice
def _train_loop(ckpt_dir, guard, total_batches=12, wrap=None):
    """Deterministic SGD over a shuffling iterator; checkpoint every
    batch; stop early (preempted) when the guard says so. ``wrap``
    optionally decorates the iterator (e.g. PrefetchingIter) — resume
    must stay bit-identical with in-flight prefetched batches."""
    data = (np.arange(64, dtype=np.float32) % 13).reshape(32, 2)
    it = NDArrayIter(data, batch_size=8, shuffle=True, seed=13)
    if wrap is not None:
        it = wrap(it)
    mgr = ckpt.CheckpointManager(ckpt_dir, keep=3)
    w = np.zeros(2, np.float32)
    epoch = 0
    state = mgr.resume_latest(data_iter=it)
    if state is not None:
        w = state["params"]["w"].asnumpy().copy()
        epoch = int(state["extra"]["epoch"])
        guard.batches = int(state["step"])
    step = guard.batches
    while step < total_batches:
        try:
            b = it.next()
        except StopIteration:
            epoch += 1
            it.reset()
            b = it.next()
        w = w - np.float32(0.5) * b.data[0].asnumpy().mean(
            axis=0, dtype=np.float32)
        step += 1
        preempted = guard.batch_done()
        mgr.save(step, params={"w": w}, data_iter=it,
                 extra={"epoch": epoch})
        if preempted:
            return "preempted", w
    return "done", w


def test_kill_worker_resume_bitwise_identical(tmp_path, fresh_faults):
    """Headline (tier-1 slice, no process kills): kill_worker@batch=N
    + auto-resume yields final weights BIT-identical to an
    uninterrupted run, with a SHUFFLING data iterator crossing an
    epoch boundary."""
    fresh_faults.delenv("MXNET_KVSTORE_FAULT_PLAN", raising=False)
    guard = ckpt.PreemptionGuard()
    try:
        status, w_clean = _train_loop(str(tmp_path / "clean"), guard)
    finally:
        guard.restore()
    assert status == "done"

    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "kill_worker@batch=7")
    guard = ckpt.PreemptionGuard()
    try:
        status, w_part = _train_loop(str(tmp_path / "faulted"), guard)
    finally:
        guard.restore()
    assert status == "preempted"
    assert w_part.tobytes() != w_clean.tobytes()

    # simulated respawn: fresh guard/iterator/weights, auto-resume
    before = profiler.recovery_summary()["worker_resumes"]
    guard = ckpt.PreemptionGuard()
    try:
        status, w_resumed = _train_loop(str(tmp_path / "faulted"), guard)
    finally:
        guard.restore()
    assert status == "done"
    assert w_resumed.tobytes() == w_clean.tobytes()
    assert profiler.recovery_summary()["worker_resumes"] == before + 1


def test_kill_worker_resume_bitwise_identical_prefetching(tmp_path,
                                                         fresh_faults):
    """The PR 2 headline extended to a PREFETCHING iterator: the
    producer thread runs batches ahead of the checkpoint, so resume
    state is (inner epoch-start state, delivered count) and replay
    must discard exactly the in-flight lookahead — final weights
    bit-identical to the uninterrupted prefetching run."""
    from mxnet_tpu.io import PrefetchingIter

    def wrap(it):
        return PrefetchingIter(it, prefetch_to_device=True)

    fresh_faults.delenv("MXNET_KVSTORE_FAULT_PLAN", raising=False)
    guard = ckpt.PreemptionGuard()
    try:
        status, w_clean = _train_loop(str(tmp_path / "clean"), guard,
                                      wrap=wrap)
    finally:
        guard.restore()
    assert status == "done"

    fresh_faults.setenv("MXNET_KVSTORE_FAULT_PLAN", "kill_worker@batch=7")
    guard = ckpt.PreemptionGuard()
    try:
        status, w_part = _train_loop(str(tmp_path / "faulted"), guard,
                                     wrap=wrap)
    finally:
        guard.restore()
    assert status == "preempted"
    assert w_part.tobytes() != w_clean.tobytes()

    guard = ckpt.PreemptionGuard()
    try:
        status, w_resumed = _train_loop(str(tmp_path / "faulted"), guard,
                                        wrap=wrap)
    finally:
        guard.restore()
    assert status == "done"
    assert w_resumed.tobytes() == w_clean.tobytes()


# ------------------------------------------- server snapshot CRC adoption
def test_server_snapshot_file_crc_gated(tmp_path):
    """The kvstore server snapshot now rides atomic_write: a flipped
    byte makes _read_snapshot return None (server starts empty) instead
    of preloading corrupt state."""
    import pickle

    from mxnet_tpu.kvstore import dist
    path = str(tmp_path / "server_0.snap")
    blob = {"version": 1, "native": b"MXTSNP01" + b"\x01" * 64,
            "optimizer_blob": None, "saved_at": 0}
    with ckpt.atomic_write(path) as f:
        pickle.dump(blob, f)
    snap = dist._read_snapshot(path)
    assert snap is not None and snap["native"].startswith(b"MXTSNP01")
    raw = bytearray(open(path, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(raw))
    before = profiler.recovery_summary()["checkpoints_rejected"]
    assert dist._read_snapshot(path) is None
    # the rejection is counted, not silently swallowed
    assert profiler.recovery_summary()["checkpoints_rejected"] == \
        before + 1


# --------------------------------------------- multi-process scenario (slow)
def _launch_worker_job(script, env_extra, ckpt_root, timeout=300):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # the worker script runs as `python tests/dist_worker_resume.py`:
    # its sys.path[0] is tests/, so the repo root must ride PYTHONPATH
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [REPO, env.get("PYTHONPATH", "")] if p)
    env["MXNET_WORKER_CHECKPOINT_DIR"] = ckpt_root
    # the scenario is single-worker local training (no collective data
    # plane); suppress the -s 0 jax.distributed mesh join so a respawned
    # worker doesn't re-rendezvous with a dead coordinator
    env["_MXTPU_DIST_JOINED"] = "1"
    env.update(env_extra)
    cmd = [sys.executable, os.path.join(REPO, "tools", "launch.py"),
           "-n", "1", "-s", "0", "--restart-policy", "worker",
           sys.executable, os.path.join(REPO, "tests", script)]
    return subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.slow
def test_worker_preemption_auto_resume_bitwise_identical(tmp_path):
    """Acceptance: kill_worker@batch=10 + --restart-policy=worker — the
    worker is SIGTERM'd mid-job, writes its final checkpoint, exits
    with the sentinel, is respawned, auto-resumes from the newest valid
    manifest, and finishes with a final-weights digest BIT-identical to
    the uninterrupted run (shuffling iterator included)."""
    clean = _launch_worker_job("dist_worker_resume.py", {},
                               str(tmp_path / "clean"))
    sys.stdout.write(clean.stdout)
    sys.stderr.write(clean.stderr)
    assert clean.returncode == 0, "clean run failed"
    clean_digests = set(re.findall(r"FINAL ([0-9a-f]{16})", clean.stdout))
    assert len(clean_digests) == 1

    faulted = _launch_worker_job(
        "dist_worker_resume.py",
        {"MXNET_KVSTORE_FAULT_PLAN": "kill_worker@batch=10"},
        str(tmp_path / "faulted"))
    sys.stdout.write(faulted.stdout)
    sys.stderr.write(faulted.stderr)
    assert faulted.returncode == 0, "faulted run failed"
    assert "PREEMPTED" in faulted.stdout, "kill never fired"
    assert "preempted (rc=%d)" % ckpt.WORKER_RESTART_EXITCODE \
        in faulted.stderr, "launcher never restarted the worker"
    assert "RESUMED" in faulted.stdout, "worker never auto-resumed"
    faulted_digests = set(re.findall(r"FINAL ([0-9a-f]{16})",
                                     faulted.stdout))
    assert faulted_digests == clean_digests, (
        f"weights diverged: {faulted_digests} vs {clean_digests}")
