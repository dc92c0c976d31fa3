"""The Qwen3-Next train driver end to end on the CPU at a tiny size
(fixtures of its own under ``tiny_qwen3next/``): a well-formed last line
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

CELL = "tiny_qwen3next_train"
SPAN_READERS = ["train_fwd_bwd_host_ms", "train_update_host_ms",
                "device_idle_pct.train", "train_block_call_host_ms",
                "train_vjp_trace_host_ms", "train_pullback_host_ms",
                "train_tape_host_ms", "train_update_loop_host_ms",
                "train_update_dispatches", "train_health_host_ms",
                "train_health_readbacks"]


@pytest.fixture
def tiny_root(tmp_path):
    root = tmp_path / "root"
    bench = root / "benchmark"
    bench.mkdir(parents=True)
    tiny = os.path.join(HERE, "tiny_qwen3next")
    shutil.copy(os.path.join(tiny, "BENCHMARK.json"), root)
    for d in ("configs", "workloads"):
        shutil.copytree(os.path.join(tiny, d), bench / d)
    for d in ("drivers", "lib", "reference", "layer_metrics"):
        os.symlink(os.path.join(BENCH, d), bench / d)
    os.symlink(os.path.join(ROOT, "mxnet_tpu"), root / "mxnet_tpu")
    return str(root)


def run_cell(root, seed=3, seconds=2.0, trace=0):
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
    assert len(said["detail"]["route_disagree_pct_by_layer"]) == 3
    ref = harness.load_by_path(
        os.path.join(BENCH, "reference", "qwen3_next.py"), "qwen3_ref")
    assert set(said["detail"]["grad_gap_by_leaf"]) == set(ref.trainable(cfg))
    assert said["extra"]["train_tokens_per_s"] == pytest.approx(
        40 * result["metrics"]["train_img_per_s"]["value"])


def test_driver_traced(tiny_root):
    rc, result, err = run_cell(tiny_root, seconds=3.0, trace=1)
    assert rc == 0 and result["correct"], err
    # what a CPU capture can show: the host spans, the whole step's
    # share, the counter's two readers; no TPU plane, no kernel, no scope
    assert set(result["metrics"]) == set(
        SPAN_READERS + ["mfu_pct.train_qwen3next", "moe_load_max_over_mean",
                        "moe_live_rows_pct"])
    assert 0 < result["metrics"]["mfu_pct.train_qwen3next"]["value"] < 100
    # 4 of 16 experts are held and the routing starts near even
    assert 5 < result["metrics"]["moe_live_rows_pct"]["value"] < 60
    assert result["metrics"]["train_update_dispatches"]["value"] == 1.0


def test_counter_and_the_live_rows(tiny_root):
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
    assert len(c0) == 3 and len(c0[0]) == 16
    tokens = run["batch"] * run["seq"]
    for a, b in zip(c0, c1):
        assert sum(b) - sum(a) == \
            (n1 - n0) * tokens * cfg["num_experts_per_tok"]
    held = sum(sum(b[:4]) - sum(a[:4]) for a, b in zip(c0, c1))
    got = _reader("moe_live_rows_pct").read({"run": run, "cfg": cfg})
    assert got == pytest.approx(
        100.0 * held / ((n1 - n0) * tokens * 3 * 3))
    assert _reader("moe_live_rows_pct").read(
        {"run": {"seq": 40}, "cfg": cfg}) is None
    cell.release()


def test_device_readers_on_a_hand_made_digest(tiny_root):
    """``train_gdn_device_ms`` and ``gdn_rule_roofline_pct`` read the
    driver's digest of the named scopes; a run without it (the parent's)
    reads nothing."""
    _, _, cfg = _load(tiny_root)
    window = ("bench.window", 0, 2_000_000, None)
    planes = [{"name": "/host:CPU", "lines": [{"name": "main", "events": [
        window, ("trainer_step", 100, 200, None),
        ("trainer_step", 300, 400, None)]}]}]
    run = {"batch": 2, "seq": 40, "scope_events": {
        "qwen3next.gdn.proj": [(1000, 3000)],
        "qwen3next.gdn.rule": [(2000, 402_000), (500_000, 1_100_000)],
        "qwen3next.gdn.conv": [], "qwen3next.gdn.out": [(3000, 5000)]}}
    from benchmark.lib import peaks
    ctx = {"planes": planes, "run": run, "cfg": cfg,
           "peaks": peaks.peaks("TPU v5 lite")}
    # the union: 1000 .. 402000 and 500000 .. 1100000 ns over two steps
    assert _reader("train_gdn_device_ms").read(ctx) == \
        pytest.approx((401_000 + 600_000) / 2 / 1e6)
    # two rule layers of 80 tokens: 3 x 80 x (2 x 192 + 2 x 4 x 4) bytes
    # a layer bound it (3 x 2 x 80 x 3 x 4 x 16 x 16 FLOPs do not)
    least = 3 * 80 * (2 * (2 * 32 + 2 * 64) + 32) / 819e9
    assert 6 * 80 * 3072 / 197e12 < least
    share = _reader("gdn_rule_roofline_pct").read(ctx)
    assert share == pytest.approx(100 * 2 * least / (1_000_000 / 2 / 1e9))
    bare = dict(ctx, run={"batch": 2, "seq": 40})
    assert _reader("train_gdn_device_ms").read(bare) is None
    assert _reader("gdn_rule_roofline_pct").read(bare) is None


def test_controls_and_planted_faults_fail_the_limits(tiny_root):
    driver, workload, cfg = _load(tiny_root)
    limits = workload["limits"]
    whats = ["control", "control_ref", "top9", "no_decay", "no_shared",
             "half_batch"]
    rows = list(driver.readings(cfg, workload, [5], ",".join(whats)))
    assert [row["what"] for row in rows] == whats
    for row in rows:
        over = [n for n, v in row["numbers"].items()
                if limits.get(n) is not None and v > limits[n]]
        assert over, row
    half = rows[-1]["numbers"]
    assert half["grad_gap_rest"] > limits["grad_gap_rest"]
    assert half["route_disagree_pct"] == 0.0
