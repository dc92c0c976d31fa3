"""Unit tests for what bench.py keeps around its sections: the device
gate, the failure line with its diagnostic snapshot, the stage marker
and the CPU cost-ledger child — without touching any accelerator.
"""
import json
import os
import subprocess
import sys

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _last_json(out):
    return json.loads([ln for ln in out.splitlines()
                       if ln.startswith("{")][-1])


def test_main_gates_on_platform(capsys):
    """On the CPU mesh main() refuses before it builds, compiles or
    spawns anything, and puts nothing on stdout."""
    assert bench.main() == 2
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "needs a TPU" in cap.err
    assert bench._LEDGER_PROC[0] is None


def test_cli_exits_nonzero_without_tpu():
    """`python bench.py` under JAX_PLATFORMS=cpu: non-zero exit and no
    number from this or any earlier run."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          env=env, cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 2, proc.stderr[-500:]
    assert proc.stdout == ""


def test_fail_json_prints_metric_line(capsys):
    bench._fail_json("backend init failed")
    line = capsys.readouterr().out.strip()
    parsed = json.loads(line)
    assert parsed["metric"] == bench.METRIC
    assert parsed["value"] == 0.0 and parsed["error"] == "backend init failed"


def test_fail_json_carries_no_earlier_number(capsys):
    """A failure says what failed. It re-prints no measurement: no
    stale line, and none of the committed CPU-host summaries."""
    bench._fail_json("section died")
    parsed = _last_json(capsys.readouterr().out)
    for key in ("stale", "measured_at", "goodput", "serving", "tail",
                "kernels"):
        assert key not in parsed, key
    assert parsed["vs_baseline"] == 0.0


def test_fail_json_embeds_diagnostic_snapshot(capsys, monkeypatch):
    """A failure line carries the debugging context: last lifecycle
    stage, recent diagnostics, env, and caller-provided bookkeeping —
    bounded in size."""
    monkeypatch.setenv("MXTPU_BENCH_ITERS", "50")
    bench._hb("backend-up: compiling")
    bench._diag("fp32 section timed out")
    bench._fail_json("int8 section failed", diag={"failed_sections": 2})
    out = capsys.readouterr().out
    parsed = _last_json(out)
    assert parsed["value"] == 0.0 and "int8" in parsed["error"]
    diag = parsed["diag"]
    assert diag["stage"] == "backend-up: compiling"
    assert diag["failed_sections"] == 2
    assert any("fp32 section timed out" in ln for ln in diag["recent"])
    assert "MXTPU_BENCH_ITERS" in diag["env"]
    assert len(out.strip()) <= 16384


def test_fail_json_truncates_oversize_diag(capsys):
    bench._fail_json("boom", diag={"blob": "x" * 40000})
    out = capsys.readouterr().out.strip()
    parsed = json.loads(out)
    assert len(out) <= 16384
    assert parsed["diag"]["truncated"] is True and parsed["error"] == "boom"


def test_hb_marks_stage_on_stderr_only(capsys):
    bench._hb("section int8 starting")
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "section int8 starting" in cap.err
    assert bench._LAST_STAGE[0] == "section int8 starting"


def test_ledger_child_is_pinned_to_cpu(tmp_path, monkeypatch):
    """The cost-ledger child must never load the TPU library beside the
    process that holds the chip."""
    seen = {}

    class FakeProc:
        pid = 1

    def fake_popen(argv, env=None, **kw):
        seen["argv"], seen["env"] = argv, env
        return FakeProc()

    monkeypatch.setenv("MXTPU_PROFILE_ATTRIB", "1")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(bench, "_LEDGER_PATH", str(tmp_path / "l.json"))
    monkeypatch.setattr(bench.subprocess, "Popen", fake_popen)
    try:
        assert bench._ledger_start() is not None
    finally:
        bench._LEDGER_PROC[0] = None
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["MXTPU_LEDGER_OUT"] == str(tmp_path / "l.json")
    assert "mxnet_tpu.profiling.bench_ledger" in seen["argv"]


def test_ledger_disabled_drops_previous_file(tmp_path, monkeypatch):
    """With attribution off, an earlier run's table must not ride into
    this run's lines."""
    path = tmp_path / "ledger.json"
    path.write_text(json.dumps({"stages": {"old": {}}}))
    monkeypatch.setattr(bench, "_LEDGER_PATH", str(path))
    monkeypatch.setenv("MXTPU_PROFILE_ATTRIB", "0")
    assert bench._ledger_start() is None
    assert not path.exists()
    assert bench._ledger_snapshot() is None
