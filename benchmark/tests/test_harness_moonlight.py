"""The Moonlight train driver end to end on the CPU at a tiny size (fixtures
of its own under ``tiny_moonlight/``: one routed layer of latent
attention; the dense layer is the model tests'): a traced run's well-formed last line that agrees
with the plain reference and carries the per-layer metrics a CPU run can
read, the device readers on a hand-made digest, the controls and the
planted faults failing the limits."""
import io
import json
import os
import shutil

import pytest

from conftest import BENCH, HERE, ROOT

from benchmark import run as harness

CELL = "tiny_moonlight_train"
SPAN_READERS = ["train_fwd_bwd_host_ms", "train_update_host_ms",
                "device_idle_pct.train", "train_block_call_host_ms",
                "train_vjp_trace_host_ms", "train_pullback_host_ms",
                "train_tape_host_ms", "train_update_loop_host_ms",
                "train_update_dispatches", "train_health_host_ms",
                "train_health_readbacks"]
# read from the spans' ring over the untraced window, the CPU's too
RING_READERS = ["train_host_busy_ms", "train_host_unspanned_ms",
                "train_host_floor_pct", "train_block_call_busy_ms",
                "train_pullback_busy_ms", "train_update_busy_ms",
                "train_launch_blocked_ms", "train_readback_blocked_ms"]
DEVICE_READERS = ("train_attn_mla_device_ms.moonlight",
                  "flash_mla_roofline_pct.moonlight",
                  "flash_mla_bwd_roofline_pct.moonlight")


def _tiny_root(path):
    root = path / "root"
    bench = root / "benchmark"
    bench.mkdir(parents=True)
    tiny = os.path.join(HERE, "tiny_moonlight")
    shutil.copy(os.path.join(tiny, "BENCHMARK.json"), root)
    for d in ("configs", "workloads"):
        shutil.copytree(os.path.join(tiny, d), bench / d)
    for d in ("drivers", "lib", "reference", "layer_metrics"):
        os.symlink(os.path.join(BENCH, d), bench / d)
    os.symlink(os.path.join(ROOT, "mxnet_tpu"), root / "mxnet_tpu")
    return str(root)


@pytest.fixture
def tiny_root(tmp_path):
    return _tiny_root(tmp_path)


def _load(root):
    bench, entry, workload, cfg = harness.load_cell(root, CELL)
    driver = harness.load_by_path(
        os.path.join(BENCH, "drivers", workload["driver"] + ".py"),
        "benchmark_driver_" + workload["driver"])
    return driver, workload, cfg


def _reader(name):
    return harness.load_by_path(
        os.path.join(BENCH, "layer_metrics", name + ".py"),
        "metric_" + name.replace(".", "_"))


def test_driver_traced_agrees_with_its_reference(tiny_root):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(tiny_root, CELL, 2 ** 31 + 41, 2.0, 1, gate=False,
                          peaks_kind="TPU v5 lite", out=out, err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    err = err.getvalue()
    assert rc == 0 and result["correct"], err
    assert result["attempted"] > 0 and result["failed"] == 0
    checks = result["checks"]
    assert checks["window_compiles"]["value"] == 0
    assert checks["move_gap"]["value"] < 1e-3
    # what a CPU capture can show: the host spans, the whole step's
    # share, the counter's two readers; no TPU plane, no kernel, no scope:
    # the device readers this model brings return nothing and the line
    # leaves them out
    assert set(result["metrics"]) == set(
        SPAN_READERS + RING_READERS + ["mfu_pct.train_moonlight",
                        "moe_load_max_over_mean", "moe_live_rows_pct"])
    assert 0 < result["metrics"]["mfu_pct.train_moonlight"]["value"] < 100
    assert result["metrics"]["train_update_dispatches"]["value"] == 1.0
    said = json.loads(err.splitlines()[-1 - len(checks)])
    _, _, cfg = _load(tiny_root)
    # one routed layer; the routers' selection bias is no trainable leaf
    assert len(said["detail"]["route_disagree_pct_by_layer"]) == 1
    ref = harness.load_by_path(
        os.path.join(BENCH, "reference", "deepseek_v3.py"), "deepseek_ref")
    assert set(said["detail"]["grad_gap_by_leaf"]) == set(ref.trainable(cfg))
    assert not any(n.endswith("expert_bias") for n in ref.trainable(cfg))


def test_counter_and_the_routers_bias_loaded(tiny_root):
    import numpy as np

    driver, workload, cfg = _load(tiny_root)
    cell = driver.Cell(cfg, workload, 7)
    cell.setup()
    # the selection's buffer holds the reference's draw, and no step
    # moved it
    bias = cell.net.layers[0].ff.router.expert_bias.data().asnumpy()
    want = np.asarray(cell.ref.init_params(7, cfg)["layer0.moe.expert_bias"])
    np.testing.assert_array_equal(bias, want)
    assert np.abs(want).max() > 0
    run = cell.window(1.0, None)
    (n0, c0), (n1, c1) = run["counter_reads"]
    assert n0 == driver.CHECK_STEPS and len(c0) == 1 and len(c0[0]) == 16
    assert sum(c1[0]) - sum(c0[0]) == (n1 - n0) * 64 * 3
    assert run["batch"] == 1 and run["seq"] == 64
    cell.release()


def test_device_readers_on_a_hand_made_digest(tiny_root):
    """The three device readers read the driver's digest of the scope
    ``moonlight.attn.mla`` and the kernels' events, the work at the true
    widths (24-lane scores, 16-lane values here); a run without the digest
    (the parent's), or with another model's scopes, reads nothing."""
    _, _, cfg = _load(tiny_root)
    window = ("bench.window", 0, 2_000_000, None)
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            window, ("trainer_step", 100, 200, None),
            ("trainer_step", 300, 400, None)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("_flash_call.1", 10_000, 110_000, "x"),
            ("_flash_call.2", 300_000, 360_000, "x"),
            ("_flash_bwd_call.1", 400_000, 600_000, "x"),
            ("_flash_bwd_call.2", 2_500_000, 2_600_000, "x")]}]}]
    run = {"batch": 1, "seq": 64, "scope_events": {
        "moonlight.attn.mla": [(9_000, 10_000), (290_000, 300_000),
                               (360_000, 380_000)],
        "moonlight.head": [(700_000, 710_000)]}}
    from benchmark.lib import flops_moonlight, peaks
    ctx = {"planes": planes, "run": run, "cfg": cfg,
           "peaks": peaks.peaks("TPU v5 lite")}
    mla, fwd, bwd = (_reader(n) for n in DEVICE_READERS)
    # scoped operations and both kernels' events in the window, a union,
    # over two steps
    assert mla.read(ctx) == pytest.approx(
        (101_000 + 70_000 + 20_000 + 200_000) / 2 / 1e6)
    pairs = 64 * 65 // 2
    assert flops_moonlight.attention_fwd_flops(cfg, 1, 64) == \
        2 * pairs * 4 * (24 + 16)
    assert fwd.read(ctx) == pytest.approx(
        100 * 2 * 2 * pairs * 4 * 40 / 197e12 / 160e-6)
    # the event past the window's end is not counted
    assert bwd.read(ctx) == pytest.approx(
        100 * 2 * pairs * 4 * (3 * 24 + 2 * 16) / 197e12 / 200e-6)
    other = {"laguna.attn.full": run["scope_events"]["moonlight.attn.mla"]}
    for bare in ({"batch": 1, "seq": 64},
                 {"batch": 1, "seq": 64, "scope_events": other}):
        for reader in (mla, fwd, bwd):
            assert reader.read(dict(ctx, run=bare)) is None


def test_the_control_path_reads_a_fault(tiny_root):
    """``readings`` (``benchmark/control.py``'s entry) on a planted fault:
    the reference with softmax router scores in the program's place fails
    a limit (every planted fault against the reference is the model
    tests')."""
    driver, workload, cfg = _load(tiny_root)
    limits = workload["limits"]
    rows = list(driver.readings(cfg, workload, [5], "softmax_router"))
    assert [row["what"] for row in rows] == ["softmax_router"]
    numbers = rows[0]["numbers"]
    assert [n for n, v in numbers.items()
            if limits.get(n) is not None and v > limits[n]], numbers
