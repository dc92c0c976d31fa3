"""The SmallThinker train driver end to end on the CPU at a tiny size
(fixtures of its own under ``tiny_smallthinker/``): a well-formed last line
that agrees with the plain reference, the per-layer metrics a CPU run can
read and the device readers on a hand-made digest, the controls and the
planted faults failing the limits."""
import io
import json
import os
import shutil

import pytest

from conftest import BENCH, HERE, ROOT

from benchmark import run as harness

CELL = "tiny_smallthinker_train"
SPAN_READERS = ["train_fwd_bwd_host_ms", "train_update_host_ms",
                "device_idle_pct.train", "train_block_call_host_ms",
                "train_vjp_trace_host_ms", "train_pullback_host_ms",
                "train_tape_host_ms", "train_update_loop_host_ms",
                "train_update_dispatches", "train_health_host_ms",
                "train_health_readbacks"]
# the all-bfloat16 reference and the six planted faults (the program
# without ``multi_precision`` would compile the model once more: the chip's
# reading of it is in PERF.md)
WHATS = ["control_ref", "no_window", "rope_all", "router_after",
         "silu_experts", "top5", "half_batch"]


def _tiny_root(path):
    root = path / "root"
    bench = root / "benchmark"
    bench.mkdir(parents=True)
    tiny = os.path.join(HERE, "tiny_smallthinker")
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


def run_cell(root, seed=3, seconds=1.0, trace=0):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(root, CELL, seed, seconds, trace, gate=False,
                          peaks_kind="TPU v5 lite", out=out, err=err)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


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


def test_driver_agrees_with_its_reference(tiny_root):
    rc, result, err = run_cell(tiny_root, seed=2 ** 31 + 11)
    assert rc == 0
    assert set(result["metrics"]) == {"train_img_per_s", "setup_s"}
    assert result["correct"], err
    assert result["attempted"] > 0 and result["failed"] == 0
    checks = result["checks"]
    assert set(checks) == {"loss1_gap", "loss2_gap", "loss3_gap",
                           "grad_gap", "grad_gap_rest", "grad2_gap",
                           "grad3_gap", "move_gap", "move_ref_gap",
                           "route_disagree_pct", "window_compiles"}
    assert checks["window_compiles"]["value"] == 0
    assert checks["move_gap"]["value"] < 1e-3
    said = json.loads(err.splitlines()[-1 - len(checks)])
    _, _, cfg = _load(tiny_root)
    assert len(said["detail"]["route_disagree_pct_by_layer"]) == 2
    ref = harness.load_by_path(
        os.path.join(BENCH, "reference", "smallthinker.py"), "small_ref")
    assert set(said["detail"]["grad_gap_by_leaf"]) == set(ref.trainable(cfg))
    # one img is one sequence of 70 tokens
    assert said["extra"]["train_tokens_per_s"] == pytest.approx(
        70 * result["metrics"]["train_img_per_s"]["value"])


def test_driver_traced(tiny_root):
    rc, result, err = run_cell(tiny_root, seconds=2.0, trace=1)
    assert rc == 0 and result["correct"], err
    # what a CPU capture can show: the host spans, the whole step's
    # share, the counter's two readers; no TPU plane, no kernel, no scope:
    # the three device readers this model brings return nothing and the
    # line leaves them out
    assert set(result["metrics"]) == set(
        SPAN_READERS + ["mfu_pct.train_smallthinker",
                        "moe_load_max_over_mean", "moe_live_rows_pct"])
    assert 0 < result["metrics"]["mfu_pct.train_smallthinker"]["value"] < 100
    # 4 of 16 experts are held and the routing starts near even
    assert 10 < result["metrics"]["moe_live_rows_pct"]["value"] < 50
    assert result["metrics"]["train_update_dispatches"]["value"] == 1.0


def test_counter_and_the_batch_of_one_sequence(tiny_root):
    driver, workload, cfg = _load(tiny_root)
    cell = driver.Cell(cfg, workload, 7)
    cell.setup()
    run = cell.window(1.5, None)
    reads = run["counter_reads"]
    steps = len(run["step_ends_ns"]) - driver.CHECK_STEPS
    # set-up's fence and the window's: no reading inside the loop
    assert [n for n, _ in reads] == [driver.CHECK_STEPS,
                                     driver.CHECK_STEPS + steps]
    (n0, c0), (n1, c1) = reads
    assert len(c0) == 2 and len(c0[0]) == 16
    assert run["batch"] == 1 and run["seq"] == 70
    for a, b in zip(c0, c1):
        assert sum(b) - sum(a) == (n1 - n0) * 70 * 3
    assert cell.x_pool.shape == (4, 1, 70)
    assert (cell.y_pool[:, :, :-1] == cell.x_pool[:, :, 1:]).all()
    cell.release()


def test_device_readers_on_a_hand_made_digest(tiny_root):
    """``flash_window_roofline_pct``, ``train_attn_window_device_ms`` and
    ``train_attn_full_device_ms`` read the driver's digest of the named
    scopes and the kernel's events; a run without the digest (the
    parent's) reads nothing."""
    _, _, cfg = _load(tiny_root)
    window = ("bench.window", 0, 2_000_000, None)
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            window, ("trainer_step", 100, 200, None),
            ("trainer_step", 300, 400, None)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ("_flash_call.1", 10_000, 110_000, "x"),
            ("_flash_call.2", 300_000, 360_000, "x"),
            ("_flash_call.3", 500_000, 560_000, "x")]}]}]
    run = {"batch": 1, "seq": 70, "scope_events": {
        "smallthinker.attn.full": [(9_000, 10_000), (10_000, 110_000),
                                   (110_000, 150_000)],
        "smallthinker.attn.window": [(299_000, 300_000), (300_000, 360_000),
                                     (499_000, 500_000), (560_000, 600_000)],
        "smallthinker.head": [(700_000, 710_000)]}}
    from benchmark.lib import flops_smallthinker, peaks
    ctx = {"planes": planes, "run": run, "cfg": cfg,
           "peaks": peaks.peaks("TPU v5 lite")}
    assert _reader("train_attn_full_device_ms").read(ctx) == \
        pytest.approx(141_000 / 2 / 1e6)
    # the third kernel event carries no scope: its layer's do
    assert _reader("train_attn_window_device_ms").read(ctx) == \
        pytest.approx((61_000 + 101_000) / 2 / 1e6)
    # 14 heads of 16 lanes: every causal pair of 70 tokens in the full
    # layer, the banded ones (a window of 24) in the two window layers
    assert flops_smallthinker.seen_pairs(70) == 2485
    assert flops_smallthinker.seen_pairs(70, 24) == 300 + 46 * 24
    flops = 2 * 2 * 14 * 16 * (2485 + 2 * 1404)
    share = _reader("flash_window_roofline_pct").read(ctx)
    assert share == pytest.approx(100 * flops / 197e12 / 220e-6)
    bare = dict(ctx, run={"batch": 1, "seq": 70})
    for name in ("train_attn_full_device_ms", "train_attn_window_device_ms",
                 "flash_window_roofline_pct"):
        assert _reader(name).read(bare) is None


@pytest.fixture(scope="module")
def fault_rows(tmp_path_factory):
    driver, workload, cfg = _load(_tiny_root(tmp_path_factory.mktemp("faults")))
    rows = list(driver.readings(cfg, workload, [5], ",".join(WHATS)))
    assert [row["what"] for row in rows] == WHATS
    return {row["what"]: row for row in rows}, workload["limits"]


@pytest.mark.parametrize("what", WHATS)
def test_controls_and_planted_faults_fail_the_limits(fault_rows, what):
    rows, limits = fault_rows
    numbers = rows[what]["numbers"]
    over = [n for n, v in numbers.items()
            if limits.get(n) is not None and v > limits[n]]
    assert over, rows[what]
    if what == "half_batch":
        # half the sequence's tokens left out of the loss: the routing is
        # the same, every gradient is not
        assert numbers["grad_gap_rest"] > limits["grad_gap_rest"]
        assert numbers["route_disagree_pct"] == 0.0
    if what in ("top5", "router_after"):
        assert numbers["route_disagree_pct"] > limits["route_disagree_pct"]
