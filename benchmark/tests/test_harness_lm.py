"""The language-model train driver end to end on the CPU at a tiny size
(fixtures of its own under ``tiny_lm/``): a well-formed last line that
agrees with the plain reference, the per-layer metrics a CPU run can read,
the counter read after the fences and never inside the stepped loop, the
controls and the planted faults failing the limits, and the timed path broken underneath
coming out as not correct."""
import io
import json
import os
import shutil

import pytest

from conftest import BENCH, HERE, ROOT

from benchmark import run as harness

CELL = "tiny_lm_train"
SPAN_READERS = ["train_fwd_bwd_host_ms", "train_update_host_ms",
                "device_idle_pct.train", "train_block_call_host_ms",
                "train_vjp_trace_host_ms", "train_pullback_host_ms",
                "train_tape_host_ms", "train_update_loop_host_ms",
                "train_update_dispatches", "train_health_host_ms"]


@pytest.fixture
def tiny_lm_root(tmp_path):
    root = tmp_path / "root"
    bench = root / "benchmark"
    bench.mkdir(parents=True)
    tiny = os.path.join(HERE, "tiny_lm")
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


def test_lm_driver_agrees_with_its_reference(tiny_lm_root):
    rc, result, err = run_cell(tiny_lm_root, seed=2 ** 31 + 11)
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
    said = json.loads(err.splitlines()[-1 - len(checks)])
    assert len(said["detail"]["route_disagree_pct_by_layer"]) == 2
    assert set(said["detail"]["grad_gap_by_leaf"]) == \
        set(harness.load_by_path(
            os.path.join(BENCH, "reference", "lfm2_moe.py"),
            "lfm2_ref").trainable(
                harness.load_cell(tiny_lm_root, CELL)[3]))
    extra = said["extra"]
    assert extra["train_tokens_per_s"] == pytest.approx(
        32 * result["metrics"]["train_img_per_s"]["value"])


def test_lm_driver_traced(tiny_lm_root):
    rc, result, err = run_cell(tiny_lm_root, seconds=3.0, trace=1)
    assert rc == 0 and result["correct"], err
    # what a CPU capture can show: the host spans, the whole step's
    # share, the counter; no TPU plane, no kernel, no scope
    assert set(result["metrics"]) == set(
        SPAN_READERS + ["mfu_pct.train_lm", "moe_load_max_over_mean"])
    assert 0 < result["metrics"]["mfu_pct.train_lm"]["value"] < 100
    assert result["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert result["metrics"]["train_update_dispatches"]["value"] == 1.0


def _cell(tiny_lm_root, seed=5, **kw):
    bench, entry, workload, cfg = harness.load_cell(tiny_lm_root, CELL)
    driver = harness.load_by_path(
        os.path.join(BENCH, "drivers", workload["driver"] + ".py"),
        "benchmark_driver_" + workload["driver"])
    return driver, workload, cfg


def test_counter_is_read_after_the_fences_only(tiny_lm_root):
    driver, workload, cfg = _cell(tiny_lm_root)
    cell = driver.Cell(cfg, workload, 7)
    cell.setup()
    run = cell.window(1.5, None)
    steps = len(run["step_ends_ns"]) - driver.CHECK_STEPS
    reads = run["counter_reads"]
    assert steps > 4
    # set-up's fence and the window's: no reading inside the loop
    assert [n for n, _ in reads] == [driver.CHECK_STEPS,
                                     driver.CHECK_STEPS + steps]
    # the counter counts every visit: tokens x k a step and layer
    (n0, c0), (n1, c1) = reads
    tokens = workload["traffic_params"]["batch"] * \
        workload["traffic_params"]["seq"]
    for a, b in zip(c0, c1):
        assert sum(b) - sum(a) == \
            (n1 - n0) * tokens * cfg["num_experts_per_tok"]
    cell.release()


def test_controls_and_planted_faults_fail_the_limits(tiny_lm_root):
    driver, workload, cfg = _cell(tiny_lm_root)
    limits = workload["limits"]
    whats = ["control", "control_ref", "top3", "no_bias", "half_batch"]
    rows = list(driver.readings(cfg, workload, [5], ",".join(whats)))
    assert [row["what"] for row in rows] == whats
    for row in rows:
        over = [n for n, v in row["numbers"].items()
                if limits.get(n) is not None and v > limits[n]]
        assert over, row
    half = rows[-1]["numbers"]
    assert half["grad_gap_rest"] > limits["grad_gap_rest"]
    assert half["route_disagree_pct"] == 0.0


def test_fault_step_returns_its_state_unchanged(tiny_lm_root, monkeypatch):
    from mxnet_tpu import gluon

    real = gluon.Trainer.step
    calls = [0]

    def first_only(self, batch_size, **kw):
        calls[0] += 1
        if calls[0] == 1:       # the first builds the optimizer's state
            return real(self, batch_size, **kw)

    monkeypatch.setattr(gluon.Trainer, "step", first_only)
    _, result, _ = run_cell(tiny_lm_root)
    assert not result["correct"]
    assert result["checks"]["move_gap"]["value"] > \
        result["checks"]["move_gap"]["limit"]
