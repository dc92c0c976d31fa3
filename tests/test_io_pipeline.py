"""Sharded multi-process input pipeline (io/pipeline.py): ring
correctness under crash/respawn, shard disjointness + epoch
completeness, streaming chunk-boundary records, device-prefetch batch
identity, clean shutdown (no shm/worker leaks), and the telemetry
proof that device prefetch collapses mx_step_data_seconds."""
import io as _io
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio
from mxnet_tpu.io import (DataBatch, DataIter, NDArrayIter,
                          PrefetchingIter, ShardedRecordPipeline)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_REC = 64
HW = 40
CROP = 32
BATCH = 8


def _pack_rec(path, n=N_REC, hw=HW):
    from PIL import Image
    rec = os.path.join(path, "t.rec")
    idx = os.path.join(path, "t.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    rng = np.random.default_rng(0)
    for i in range(n):
        img = Image.fromarray(
            rng.integers(0, 255, (hw, hw, 3), dtype=np.uint8))
        buf = _io.BytesIO()
        img.save(buf, format="JPEG", quality=90)
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), buf.getvalue()))
    w.close()
    return rec


@pytest.fixture(scope="module")
def rec_path(tmp_path_factory):
    return _pack_rec(str(tmp_path_factory.mktemp("iopipe")))


def _shm_names():
    return {f for f in os.listdir("/dev/shm") if f.startswith("psm_")}


def _drain(it):
    out = []
    for b in it:
        out.append((b.data[0].asnumpy().copy(),
                    b.label[0].asnumpy().copy()))
    return out


# ------------------------------------------------------- stream reader

def test_stream_reader_chunk_boundary_records(rec_path):
    """Tiny chunks force records to straddle every chunk boundary; the
    parser must reassemble them bit-exactly and in order."""
    offs = recordio.load_record_offsets(rec_path)
    r = recordio.MXRecordIO(rec_path, "r")
    expect = []
    while True:
        item = r.read()
        if item is None:
            break
        expect.append(item)
    r.close()
    reader = recordio.RecordIOStreamReader(rec_path, chunk_bytes=97)
    got = list(reader)
    reader.close()
    assert [o for o, _ in got] == offs
    assert [rec for _, rec in got] == expect


def test_stream_reader_byte_range(rec_path):
    offs = recordio.load_record_offsets(rec_path)
    reader = recordio.RecordIOStreamReader(rec_path, start=offs[10],
                                           stop=offs[20])
    got = list(reader)
    reader.close()
    assert [o for o, _ in got] == offs[10:20]


# ---------------------------------------------------- shard semantics

def test_epoch_completeness_with_shuffle(rec_path):
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2, shuffle=True, seed=11)
    try:
        epochs = []
        for _ in range(2):
            labels = np.concatenate(
                [b.label[0].asnumpy() for b in p]).astype(int)
            p.reset()
            epochs.append(labels)
        for labels in epochs:
            # disjoint shards, together exactly one pass over the data
            assert sorted(labels.tolist()) == list(range(N_REC))
        # epochs reshuffle
        assert not np.array_equal(epochs[0], epochs[1])
    finally:
        p.close()


def test_order_matches_single_process(rec_path):
    """Batch-striped shards: the N-worker stream must equal the
    in-process iterator's batch order bit-for-bit (same seed)."""
    it0 = mx.io.ImageRecordIter(path_imgrec=rec_path,
                                data_shape=(3, CROP, CROP),
                                batch_size=BATCH, num_workers=0)
    ref = _drain(it0)
    it0.close()
    p = mx.io.ImageRecordIter(path_imgrec=rec_path,
                              data_shape=(3, CROP, CROP),
                              batch_size=BATCH, num_workers=2)
    assert isinstance(p, ShardedRecordPipeline)
    try:
        got = _drain(p)
    finally:
        p.close()
    assert len(ref) == len(got)
    for (rd, rl), (gd, gl) in zip(ref, got):
        np.testing.assert_allclose(rd, gd, atol=1e-5)
        np.testing.assert_array_equal(rl, gl)


def test_streaming_epoch_completeness(rec_path):
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2, streaming=True,
                              readahead_mb=1, seed=3)
    try:
        labels = np.concatenate(
            [b.label[0].asnumpy() for b in p]).astype(int)
        assert sorted(labels.tolist()) == list(range(N_REC))
    finally:
        p.close()


def test_streaming_shuffle_deterministic(rec_path):
    runs = []
    for _ in range(2):
        p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                                  num_workers=2, streaming=True,
                                  shuffle=True, seed=7)
        try:
            runs.append(_drain(p))
        finally:
            p.close()
    flat = np.concatenate([lb for _, lb in runs[0]])
    assert sorted(flat.astype(int).tolist()) == list(range(N_REC))
    assert not np.array_equal(flat, np.arange(N_REC))   # shuffled
    for (ad, al), (bd, bl) in zip(*runs):
        np.testing.assert_array_equal(ad, bd)
        np.testing.assert_array_equal(al, bl)


# ------------------------------------------------------ crash respawn

def test_worker_crash_respawn_bit_identical(rec_path):
    """Kill a worker mid-epoch: the shard resumes from its last acked
    batch and the delivered stream is bit-identical to an unkilled
    run (ring slots beyond the ack point are redecoded)."""
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2, shuffle=True, seed=5)
    try:
        clean = _drain(p)
    finally:
        p.close()
    # two ring slots and a kill after the first batch: worker 0 owns
    # batches 0, 2, 4, 6 and cannot have started 6 before 2 is handed
    # over, so it is certainly still needed when it dies (with more
    # slots or a later kill it may have finished its shard already, and
    # then nothing respawns)
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2, shuffle=True, seed=5,
                              ring_batches=2)
    try:
        got = []
        b = p.next()
        got.append((b.data[0].asnumpy().copy(),
                    b.label[0].asnumpy().copy()))
        p._workers[0].proc.kill()
        while True:
            try:
                b = p.next()
            except StopIteration:
                break
            got.append((b.data[0].asnumpy().copy(),
                        b.label[0].asnumpy().copy()))
        assert p.respawns >= 1
        assert len(got) == len(clean)
        for (cd, cl), (gd, gl) in zip(clean, got):
            np.testing.assert_array_equal(cl, gl)
            np.testing.assert_array_equal(cd, gd)
    finally:
        p.close()


def test_state_dict_resume_mid_epoch(rec_path):
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2, shuffle=True, seed=5)
    try:
        for _ in range(3):
            p.next()
        state = p.state_dict()
        rest = _drain(p)
    finally:
        p.close()
    q = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2, shuffle=True, seed=5)
    try:
        q.load_state_dict(state)
        rest2 = _drain(q)
    finally:
        q.close()
    assert len(rest) == len(rest2)
    for (ad, al), (bd, bl) in zip(rest, rest2):
        np.testing.assert_array_equal(ad, bd)
        np.testing.assert_array_equal(al, bl)


def test_decode_error_surfaces(tmp_path):
    """A corrupt payload fails the epoch with the worker's message, not
    a hang."""
    rec = os.path.join(str(tmp_path), "bad.rec")
    idx = os.path.join(str(tmp_path), "bad.idx")
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(16):
        w.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0),
            b"\xff\xd8not really a jpeg"))
    w.close()
    p = ShardedRecordPipeline(rec, (3, 8, 8), 8, num_workers=2)
    try:
        with pytest.raises(mx.MXNetError, match="decode worker failed"):
            p.next()
    finally:
        p.close()


# ------------------------------------------------------ clean shutdown

def test_clean_shutdown_no_leaks(rec_path):
    """Teardown leaves no shared-memory segment (resource_tracker's
    /dev/shm namespace) and no worker process."""
    before = _shm_names()
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2)
    p.next()
    segs = _shm_names() - before
    assert len(segs) == 2            # one ring per worker
    procs = [w.proc for w in p._workers]
    p.close()
    assert _shm_names() - before == set()
    for proc in procs:
        assert proc is None or proc.poll() is not None
    # close() is idempotent and __del__-safe
    p.close()


def test_shutdown_on_delete(rec_path):
    before = _shm_names()
    p = ShardedRecordPipeline(rec_path, (3, CROP, CROP), BATCH,
                              num_workers=2)
    p.next()
    pids = [w.proc.pid for w in p._workers]
    del p
    import gc
    gc.collect()
    deadline = time.time() + 10
    while time.time() < deadline and (_shm_names() - before):
        time.sleep(0.1)
    assert _shm_names() - before == set()
    for pid in pids:
        deadline = time.time() + 5
        while time.time() < deadline:
            try:
                os.kill(pid, 0)
                time.sleep(0.1)
            except OSError:
                break
        else:
            pytest.fail(f"worker {pid} survived iterator deletion")


# ------------------------------------------------- DataLoader wiring

def _vision_dataset(rec_path):
    from mxnet_tpu.gluon.data.vision import (ImageRecordDataset,
                                             transforms)
    return ImageRecordDataset(rec_path).transform_first(
        transforms.Compose([transforms.CenterCrop(CROP),
                            transforms.ToTensor(),
                            transforms.Normalize(0.5, 0.25)]))


def test_dataloader_multiprocess_matches_threads(rec_path):
    from mxnet_tpu.gluon.data import DataLoader
    ds = _vision_dataset(rec_path)
    ref = [(d.asnumpy(), lb.asnumpy())
           for d, lb in DataLoader(ds, batch_size=BATCH, num_workers=0)]
    mp = DataLoader(ds, batch_size=BATCH, num_workers=2,
                    thread_pool=False)
    assert mp._mp_config is not None
    try:
        got = [(d.asnumpy(), lb.asnumpy()) for d, lb in mp]
        assert len(ref) == len(got)
        for (rd, rl), (gd, gl) in zip(ref, got):
            np.testing.assert_allclose(rd, gd, atol=1e-5)
            np.testing.assert_array_equal(rl, gl)
        # second epoch reuses the worker fleet
        got2 = [(d.asnumpy(), lb.asnumpy()) for d, lb in mp]
        np.testing.assert_allclose(got2[0][0], ref[0][0], atol=1e-5)
    finally:
        mp.close()


def test_dataloader_prefetch_device_identity(rec_path):
    """Device prefetch must change WHEN batches move, never WHAT they
    hold."""
    from mxnet_tpu.gluon.data import DataLoader
    ds = _vision_dataset(rec_path)
    ref = [(d.asnumpy(), lb.asnumpy())
           for d, lb in DataLoader(ds, batch_size=BATCH, num_workers=0)]
    pf = DataLoader(ds, batch_size=BATCH, num_workers=0,
                    prefetch_to_device=True)
    got = [(d.asnumpy(), lb.asnumpy()) for d, lb in pf]
    assert len(ref) == len(got)
    for (rd, rl), (gd, gl) in zip(ref, got):
        np.testing.assert_array_equal(rd, gd)
        np.testing.assert_array_equal(rl, gl)


def test_dataloader_pin_memory_routes_to_feeder(rec_path):
    from mxnet_tpu.gluon.data import DataLoader
    ds = _vision_dataset(rec_path)
    with pytest.warns(UserWarning, match="pin_memory"):
        loader = DataLoader(ds, batch_size=BATCH, pin_memory=True)
    assert loader._prefetch_device
    # explicit prefetch_to_device wins silently
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        loader = DataLoader(ds, batch_size=BATCH, pin_memory=True,
                            prefetch_to_device=False)
    assert not loader._prefetch_device


# ------------------------------------------- prefetching checkpoints

def test_prefetching_iter_state_roundtrip():
    X = (np.arange(160, dtype=np.float32) % 13).reshape(80, 2)
    y = np.arange(80, dtype=np.float32)
    pf = PrefetchingIter(NDArrayIter(X, y, batch_size=8, shuffle=True,
                                     seed=11))
    for _ in range(3):
        pf.next()
    state = pf.state_dict()
    rest = []
    while True:
        try:
            rest.append(pf.next().data[0].asnumpy().copy())
        except StopIteration:
            break
    pf2 = PrefetchingIter(NDArrayIter(X, y, batch_size=8, shuffle=True,
                                      seed=11))
    pf2.load_state_dict(state)
    rest2 = []
    while True:
        try:
            rest2.append(pf2.next().data[0].asnumpy().copy())
        except StopIteration:
            break
    assert len(rest) == len(rest2) > 0
    for a, b in zip(rest, rest2):
        np.testing.assert_array_equal(a, b)


def test_prefetching_iter_rejects_stateless_inner():
    class NoState(DataIter):
        def __init__(self):
            super().__init__(4)

        def next(self):
            raise StopIteration

    pf = PrefetchingIter(NoState())
    with pytest.raises(mx.MXNetError, match="does not support"):
        pf.state_dict()


# --------------------------------------------------- telemetry proof

class _SlowIter(DataIter):
    """Synthetic slow decoder (fixed sleep per batch)."""

    def __init__(self, nbatches=12, delay=0.008, batch=4):
        super().__init__(batch)
        self._n = nbatches
        self._delay = delay
        self._i = 0
        self._data = np.ones((batch, 4), np.float32)

    def reset(self):
        self._i = 0

    def next(self):
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        time.sleep(self._delay)
        return DataBatch(data=[mx.nd.array(self._data)], label=[],
                         pad=0)


def test_device_prefetch_drops_step_data_seconds():
    """The committable overlap claim: with the device feeder, the step
    breakdown's data share collapses on a slow-decoder fixture."""
    from mxnet_tpu.telemetry import metrics as tmetrics
    from mxnet_tpu.telemetry import step as tstep

    def run(wrap):
        it = _SlowIter()
        src = PrefetchingIter(it, prefetch_to_device=True) if wrap \
            else it
        tmetrics.registry().reset()
        tstep.reset()
        for _ in src:
            time.sleep(0.012)      # the "step"
            tstep.step_boundary("test")
        snap = tmetrics.registry().snapshot()["metrics"]

        def total(name):
            return sum(s.get("value", 0.0)
                       for s in snap.get(name, {}).get("series", []))

        return (total("mx_step_data_seconds_total"),
                total("mx_step_time_seconds_total"))

    data_plain, step_plain = run(False)
    data_pf, step_pf = run(True)
    frac_plain = data_plain / step_plain
    frac_pf = data_pf / step_pf
    assert frac_plain > 0.25       # sleep 8ms of ~20ms step
    assert frac_pf < frac_plain / 2
    assert frac_pf < 0.15


def test_prefetching_iter_batches_match_plain():
    it = _SlowIter(nbatches=5, delay=0.0)
    plain = [b.data[0].asnumpy() for b in it]
    it2 = PrefetchingIter(_SlowIter(nbatches=5, delay=0.0),
                          prefetch_to_device=True)
    pf = [b.data[0].asnumpy() for b in it2]
    assert len(plain) == len(pf)
    for a, b in zip(plain, pf):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ gate self-test

def test_perf_gate_io_passes_on_committed_artifact():
    art = os.path.join(REPO, "docs", "artifacts",
                       "io_bench_20260803.json")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "perf_gate.py"),
         art, "--io"], capture_output=True, text=True)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PASS" in r.stdout


def test_io_artifact_meets_roadmap_contract():
    """The committed artifact itself carries the PR's claims: >=3x the
    single-process DataLoader and <5% input wait with prefetch."""
    art = os.path.join(REPO, "docs", "artifacts", "IO_LAST_GOOD.json")
    with open(art) as f:
        doc = json.load(f)
    assert doc["version"] == 2
    assert doc["ratios"]["pipeline_vs_python_1proc"] >= 3.0
    assert doc["train"]["input_wait_frac_prefetch"] < 0.05
    assert doc["train"]["input_wait_frac_noprefetch"] > \
        doc["train"]["input_wait_frac_prefetch"]


def test_imagerecorditer_nondivisible_falls_back(rec_path):
    """64 records with workers*batch=48: the pipeline would tail-drop
    records silently, so routing must fall back to the in-process
    iterator with a warning (which pads/serves everything)."""
    from mxnet_tpu.io.io import ImageRecordIter
    with pytest.warns(UserWarning, match="do not divide"):
        it = mx.io.ImageRecordIter(path_imgrec=rec_path,
                                   data_shape=(3, CROP, CROP),
                                   batch_size=24, num_workers=2)
    assert isinstance(it, ImageRecordIter)
    it.close()


def test_env_knobs_registered():
    from mxnet_tpu import libinfo
    for knob in ("MXTPU_IO_WORKERS", "MXTPU_IO_READAHEAD_MB",
                 "MXTPU_IO_RING_BATCHES", "MXTPU_IO_PREFETCH_DEVICE"):
        assert knob in libinfo._ENV_VARS
        with open(os.path.join(REPO, "docs", "env_vars.md")) as f:
            assert knob in f.read()
