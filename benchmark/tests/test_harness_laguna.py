"""The Laguna train driver end to end on the CPU at a tiny size (fixtures
of its own under ``tiny_laguna/``: a dense full layer and a routed window
layer): a well-formed last line
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

CELL = "tiny_laguna_train"
SPAN_READERS = ["train_fwd_bwd_host_ms", "train_update_host_ms",
                "device_idle_pct.train", "train_block_call_host_ms",
                "train_vjp_trace_host_ms", "train_pullback_host_ms",
                "train_tape_host_ms", "train_update_loop_host_ms",
                "train_update_dispatches", "train_health_host_ms",
                "train_health_readbacks"]
# the all-bfloat16 reference and the seven planted faults (the program
# without ``multi_precision`` would compile the model once more)
WHATS = ["control_ref", "no_window", "no_yarn", "no_head_gate",
         "sigmoid_router", "no_routed_scale", "top9", "half_batch"]


def _tiny_root(path):
    root = path / "root"
    bench = root / "benchmark"
    bench.mkdir(parents=True)
    tiny = os.path.join(HERE, "tiny_laguna")
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
    # one routed layer: the dense one has no router
    assert len(said["detail"]["route_disagree_pct_by_layer"]) == 1
    ref = harness.load_by_path(
        os.path.join(BENCH, "reference", "laguna.py"), "laguna_ref")
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
        SPAN_READERS + ["mfu_pct.train_laguna",
                        "moe_load_max_over_mean", "moe_live_rows_pct"])
    assert 0 < result["metrics"]["mfu_pct.train_laguna"]["value"] < 100
    # 4 of 16 experts are held, the routing starts near even (a quarter)
    # and drifts to them fast: only they return a gradient
    assert 10 < result["metrics"]["moe_live_rows_pct"]["value"] < 90
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
    assert len(c0) == 1 and len(c0[0]) == 16        # the routed layer
    assert run["batch"] == 1 and run["seq"] == 70
    for a, b in zip(c0, c1):
        assert sum(b) - sum(a) == (n1 - n0) * 70 * 3
    assert cell.x_pool.shape == (4, 1, 70)
    assert (cell.y_pool[:, :, :-1] == cell.x_pool[:, :, 1:]).all()
    cell.release()


def test_device_readers_on_a_hand_made_digest(tiny_root):
    """``flash_window_roofline_pct.laguna``,
    ``train_attn_window_device_ms.laguna`` and
    ``train_attn_full_device_ms.laguna`` read the driver's digest of the
    named scopes and the kernel's events, each kind's work at its own head
    count; a run without the digest (the parent's), or with another
    model's scopes, reads nothing."""
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
        "laguna.attn.full": [(9_000, 10_000), (10_000, 110_000),
                             (110_000, 150_000)],
        "laguna.attn.window": [(299_000, 300_000), (300_000, 360_000),
                               (499_000, 500_000), (560_000, 600_000)],
        "laguna.head": [(700_000, 710_000)]}}
    from benchmark.lib import flops_laguna, peaks
    ctx = {"planes": planes, "run": run, "cfg": cfg,
           "peaks": peaks.peaks("TPU v5 lite")}
    names = ("train_attn_full_device_ms.laguna",
             "train_attn_window_device_ms.laguna",
             "flash_window_roofline_pct.laguna")
    assert _reader(names[0]).read(ctx) == pytest.approx(141_000 / 2 / 1e6)
    # the third kernel event carries no scope: its layer's do
    assert _reader(names[1]).read(ctx) == \
        pytest.approx((61_000 + 101_000) / 2 / 1e6)
    # 16 lanes: every causal pair of 70 tokens at the full layer's 6 heads,
    # the banded ones (a window of 24) at the window layer's 9, twice
    assert flops_laguna.kind_shape(cfg, "full") == (6, 0)
    assert flops_laguna.kind_shape(cfg, "window") == (9, 24)
    flops = 2 * 2 * 16 * (6 * 2485 + 2 * 9 * (300 + 46 * 24))
    share = _reader(names[2]).read(ctx)
    assert share == pytest.approx(100 * flops / 197e12 / 220e-6)
    other = {"smallthinker.attn.full": run["scope_events"]["laguna.attn.full"]}
    for bare in ({"batch": 1, "seq": 70},
                 {"batch": 1, "seq": 70, "scope_events": other}):
        for name in names:
            assert _reader(name).read(dict(ctx, run=bare)) is None


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
    if what == "top9":
        assert numbers["route_disagree_pct"] > limits["route_disagree_pct"]
