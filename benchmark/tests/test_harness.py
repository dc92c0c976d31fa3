"""The harness end to end on the CPU at tiny sizes: both drivers print a
well-formed last line and agree with their plain references; a cell and
a metric added as files are found; the timed path broken underneath
comes out as not correct; the control fails the limits; and without a
TPU the command exits non-zero and prints no result."""
import io
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT

from benchmark import run as harness


def run_cell(root, cell, seed=3, seconds=2.0, trace=0):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(root, cell, seed, seconds, trace, gate=False,
                          peaks_kind="TPU v5 lite", out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    return rc, json.loads(lines[-1]), err.getvalue()


def well_formed(result, metrics):
    assert list(result)[-1] == "checks"
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert set(result["metrics"]) == set(metrics)
    for m in result["metrics"].values():
        assert isinstance(m["value"], float) and m["value"] > 0
    assert result["device"]["platform"] and result["device"]["kind"]
    assert result["device"]["count"] >= 1
    assert result["attempted"] > 0 and result["failed"] == 0


def test_without_a_tpu_the_command_exits_nonzero_and_prints_nothing():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "resnet50_train_b64", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_train_driver_agrees_with_its_reference(tiny_root):
    rc, result, err = run_cell(tiny_root, "tiny_train", seed=2 ** 31 + 7)
    assert rc == 0
    well_formed(result, ["train_img_per_s", "setup_s"])
    assert result["correct"], err
    assert "check move_norm_gap" in err.splitlines()[-2]


def test_train_driver_traced(tiny_root):
    rc, result, err = run_cell(tiny_root, "tiny_train", seconds=3.0,
                               trace=1)
    assert rc == 0
    well_formed(result, ["train_fwd_bwd_host_ms", "train_update_host_ms",
                         "mfu_pct.train", "device_idle_pct.train"])
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["breakdown"]["device_ops"]
    assert result["metrics"]["mfu_pct.train"]["value"] < 100


def test_generation_driver_agrees_with_its_reference(tiny_root):
    rc, result, err = run_cell(tiny_root, "tiny_chat", seed=2 ** 31 + 9)
    assert rc == 0
    well_formed(result, ["gen_tok_per_s", "gen_ttft_p95_ms",
                         "gen_gap_p95_ms", "setup_s"])
    assert result["correct"], err
    assert result["checks"]["served_gap_max"]["value"] <= 1e-4


def test_generation_driver_traced(tiny_root):
    rc, result, err = run_cell(tiny_root, "tiny_chat", seconds=3.0, trace=1)
    assert rc == 0
    well_formed(result, ["gen_batch_fill_pct", "gen_prefill_stall_ms",
                         "gen_gap_p50_ms", "mfu_pct.gen",
                         "device_idle_pct.gen"])
    assert result["metrics"]["gen_batch_fill_pct"]["value"] <= 100


def test_a_cell_and_a_metric_added_as_files_are_found(tiny_root):
    """No file that exists is edited: one workload file, one reader
    file, and their entries in BENCHMARK.json."""
    bench_dir = os.path.join(tiny_root, "benchmark")
    with open(os.path.join(bench_dir, "workloads", "tiny_chat.json")) as f:
        workload = json.load(f)
    workload["traffic_params"]["clients"] = 2
    workload["traffic"] = "chat_c2"
    with open(os.path.join(bench_dir, "workloads", "added_cell.json"),
              "w") as f:
        json.dump(workload, f)
    with open(os.path.join(bench_dir, "layer_metrics",
                           "added_metric.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return float(len(ctx['run']['requests']))\n")
    with open(os.path.join(bench_dir, "layer_metrics",
                           "silent_metric.py"), "w") as f:
        f.write("def read(ctx):\n    return None\n")
    path = os.path.join(tiny_root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "added_cell",
                               "config": "tiny-decoder",
                               "traffic": "chat_c2", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("gen_"):
            m["workloads"].append("added_cell")
    for name in ("added_metric", "silent_metric"):
        bench["per_layer"].append({
            "name": name, "unit": "requests", "better": "higher",
            "source": "program_counter", "layer": "serving scheduler",
            "moves": "gen_tok_per_s", "workloads": ["added_cell"]})
    with open(path, "w") as f:
        json.dump(bench, f)
    rc, result, _ = run_cell(tiny_root, "added_cell", trace=1)
    assert rc == 0 and result["correct"]
    assert result["metrics"]["added_metric"]["value"] > 0
    # a reader that finds nothing returns nothing, and the line leaves
    # the metric out
    assert "silent_metric" not in result["metrics"]
    assert "gen_batch_fill_pct" in result["metrics"]
    rc, result, _ = run_cell(tiny_root, "tiny_chat", trace=1)
    assert "added_metric" not in result["metrics"]


# -- the timed path broken underneath ---------------------------------

def test_fault_step_returns_its_state_unchanged(tiny_root, monkeypatch):
    from mxnet_tpu import gluon

    monkeypatch.setattr(gluon.Trainer, "step",
                        lambda self, batch_size, **kw: None)
    _, result, _ = run_cell(tiny_root, "tiny_train")
    assert not result["correct"]
    checks = result["checks"]
    assert checks["move_norm_gap"]["value"] > \
        checks["move_norm_gap"]["limit"]


def test_fault_half_of_the_batch_left_out(tiny_root, monkeypatch):
    """The first half of every batch reaches the net, and the mean is
    taken over it."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    real_array, real_step = mx.nd.array, gluon.Trainer.step

    def half_array(data, *a, **kw):
        data = np.asarray(data)
        if data.ndim in (1, 4) and data.shape[0] == 8:
            data = data[:4]
        return real_array(data, *a, **kw)

    monkeypatch.setattr(mx.nd, "array", half_array)
    monkeypatch.setattr(gluon.Trainer, "step",
                        lambda self, batch_size, **kw:
                        real_step(self, batch_size // 2, **kw))
    _, result, _ = run_cell(tiny_root, "tiny_train")
    assert not result["correct"]
    over = [n for n, c in result["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert "loss1_gap" in over or "grad_norm_gap" in over


def test_fault_a_token_altered_where_it_is_produced(tiny_root,
                                                    monkeypatch):
    from mxnet_tpu.serving.generate import scheduler

    real = scheduler.GenLane._host_tokens
    calls = [0]

    def altered(self, tok_dev):
        toks = np.array(real(self, tok_dev))
        calls[0] += 1
        if calls[0] % 5 == 0:
            toks = (toks + 1) % 512
        return toks

    monkeypatch.setattr(scheduler.GenLane, "_host_tokens", altered)
    _, result, _ = run_cell(tiny_root, "tiny_chat")
    assert not result["correct"]
    assert result["checks"]["served_gap_max"]["value"] > \
        result["checks"]["served_gap_max"]["limit"]


# -- the controls, at a size a test run can hold ----------------------

def _readings(tiny_root, cell, what, seeds, seconds=1.0):
    bench, entry, workload, cfg = harness.load_cell(tiny_root, cell)
    driver = harness.load_by_path(
        os.path.join(BENCH, "drivers", workload["driver"] + ".py"),
        "benchmark_driver_" + workload["driver"])
    return workload, list(driver.readings(cfg, workload, seeds, what,
                                          seconds=seconds))


def test_control_bfloat16_fails_the_training_limits(tiny_root):
    workload, rows = _readings(tiny_root, "tiny_train", "control", [5])
    limits = workload["limits"]
    for row in rows:
        over = [n for n, v in row["numbers"].items() if v > limits[n]]
        assert over, row


def test_fault_half_batch_in_the_reference_fails_the_limits(tiny_root):
    workload, rows = _readings(tiny_root, "tiny_train", "half_batch", [5])
    limits = workload["limits"]
    for row in rows:
        assert row["numbers"]["grad_norm_gap"] > limits["grad_norm_gap"]


def test_control_fp8_fails_the_served_limit(tiny_root):
    workload, rows = _readings(tiny_root, "tiny_chat", "control",
                               [5, 6, 7])
    limit = workload["limits"]["served_gap_max"]
    assert all(r["served_gap_max"] <= limit for r in rows)
    # fp8 flips a greedy token somewhere in a run's sample; the chip
    # readings at the cells' own sizes are in PERF.md
    assert all(r["control_gap_max"] > limit for r in rows)
