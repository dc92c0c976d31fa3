"""chip_smoke.py rehearsed on the CPU mesh.

The script's proof is its run on the chip; these call each of its phase
functions at tiny sizes so that a wrong path, argument or check is
found here, at no chip time. What only the chip can show — the compiled
kernels, donation, the int8 native lowering — the phases take as
expectations, and here they expect the CPU branch.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import mxnet_tpu as mx

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

TINY = dict(model="resnet18_v1", hw=32, classes=10)


@pytest.fixture(scope="module")
def meter():
    m = chip_smoke.CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(m)
    yield m
    jax.monitoring.unregister_event_duration_listener(m)


@pytest.fixture(scope="module")
def trained(meter):
    return chip_smoke.phase_train(meter, batch=8, steps=12, **TINY)


def test_train_phase(trained):
    report, (net, inputs, labels) = trained
    assert report["failed"] == []
    assert report["compiles_per_step"][0] > 0
    assert not any(report["compiles_per_step"][1:]), report
    assert report["losses"][-1] < report["losses"][0]
    assert report["param_context"] == "cpu(0)"
    assert len(net.collect_params().keys()) == report["params"]
    assert inputs.shape == (8, 3, 32, 32) and labels.shape == (8,)


def test_serve_phase(trained):
    report = chip_smoke.phase_serve(trained[1], rows=4,
                                    expect_native=False)
    assert report["failed"] == []
    assert report["fp32_rel_err"] <= chip_smoke.FP32_TOL
    assert report["int8_top1_agree"] == "4/4"
    assert report["int8_lowering"] == "dequant"
    # the CPU lowering computes in float: no int8 convolution, no kernel
    assert report["int8_convolutions_in_hlo"] == 0
    assert report["int8_epilogue_kernel_in_hlo"] is False


def test_a_failed_check_is_printed_then_raised(trained, meter, capsys):
    """The phase line goes out with what was seen and ok: false; then
    the run ends."""
    with pytest.raises(AssertionError, match="serve: int8_lowering"):
        chip_smoke.run_phase("serve", meter, chip_smoke.phase_serve,
                             trained[1], rows=2, expect_native=True)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "serve" and line["ok"] is False
    assert line["failed"] == ["int8_lowering resolved to 'dequant'"]
    assert "fp32_rel_err" in line


def test_decode_phase():
    report = chip_smoke.phase_decode(
        vocab=256, d_model=64, heads=4, layers=2,
        prompt_lens=(3, 7, 12, 20), new_tokens=8, max_prompt_tokens=32,
        expect_kernel=False)
    assert report["failed"] == []
    assert report["tokens_equal_reference"] is True
    assert report["head_dim"] == 16 and report["executables"] > 0
    assert report["paged_kernel_in_hlo"] is False
    # the process-wide precision setting is put back
    assert jax.config.jax_default_matmul_precision is None


def test_greedy_check_is_never_dropped():
    """Equal tokens pass; a different token passes only as a near-tie
    in the reference's own logits, and fails otherwise."""
    class Logits:
        def __init__(self, a):
            self.a = a

        def asnumpy(self):
            return self.a

    class Decoder:
        def __init__(self, gap):
            self.gap = gap

        def full_logits(self, toks):
            out = np.zeros((1, toks.shape[1], 4), np.float32)
            out[0, -1] = [1.0, 1.0 - self.gap, 0.0, -1.0]
            return Logits(out)

    check = chip_smoke.Checks()
    assert chip_smoke._greedy_matches(check, None, [5], [0, 0],
                                      [0, 0]) == 0.0
    near = chip_smoke._greedy_matches(check, Decoder(1e-5), [5], [1, 1],
                                      [0, 0])
    assert 0.0 < near <= chip_smoke.TIE_TOL and check == []
    chip_smoke._greedy_matches(check, Decoder(0.5), [5], [1, 1], [0, 0])
    assert len(check) == 2 and "reference logits prefer 0" in check[0]


@pytest.mark.skipif(jax.device_count() < 4, reason="needs 4 devices")
def test_data_parallel_phase():
    report = chip_smoke.phase_data_parallel(chips=4, batch=8, steps=3,
                                            **TINY)
    assert report["failed"] == []
    assert report["output_devices"] == 4 and report["param_devices"] == 4
    assert report["data_shard_rows"] == 2
    assert report["all_reduce_in_hlo"] is True
    np.testing.assert_allclose(report["losses_4_contexts"],
                               report["losses_1_context"],
                               rtol=chip_smoke.DP_LOSS_RTOL)


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_gate_exits_nonzero_without_tpu(argv):
    """As the driver's first check runs it: no accelerator, so a
    non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")] + argv,
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2, proc.stderr[-500:]
    assert proc.stdout == ""
    assert "needs %d TPU" % (4 if argv else 1) in proc.stderr


def test_main_runs_only_the_pair_on_four_chips(monkeypatch, capsys):
    """--chips 4: the data-parallel pair and no other phase; the last
    line is the contract's, with the device as JAX reports it."""
    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

        def __str__(self):
            return "TPU_0"

    ran = []
    monkeypatch.setattr(chip_smoke, "device_gate", lambda n: [Dev()] * n)
    monkeypatch.setattr(mx.util, "enable_compile_cache", lambda: "/c")
    for name in ("phase_train", "phase_serve", "phase_decode",
                 "phase_data_parallel"):
        monkeypatch.setattr(
            chip_smoke, name,
            lambda *a, _n=name, **k: ran.append(_n) or {"failed": []})
    assert chip_smoke.main(["--chips", "4"]) == 0
    assert ran == ["phase_data_parallel"]
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln.get("phase") for ln in lines[:-1]] == ["gate",
                                                      "data_parallel"]
    assert lines[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}}


def test_compile_cache_helper(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code.
    Otherwise the fixed <checkout>/.xla_cache — nothing from tempfile,
    a pid or the time, or a second run would never hit."""
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert mx.util.enable_compile_cache() == "/somewhere/else"
    assert "jax_compilation_cache_dir" not in updates
    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".xla_cache")
    assert mx.util.enable_compile_cache() == want
    assert mx.util.enable_compile_cache() == want
    assert updates["jax_compilation_cache_dir"] == want


@pytest.mark.skipif(jax.device_count() < 2, reason="needs 2 devices")
@pytest.mark.parametrize("deferred", [False, True])
def test_initialize_places_on_the_given_context(deferred):
    """initialize(ctx=mx.tpu(1)) used to record the context and leave
    the data on device 0, so every use elsewhere re-transferred it."""
    from mxnet_tpu import gluon
    net = gluon.nn.Dense(4) if deferred else gluon.nn.Dense(4, in_units=3)
    net.initialize(ctx=mx.tpu(1))
    x = mx.nd.ones((2, 3), ctx=mx.tpu(1))
    out = net(x)
    dev1 = jax.devices()[1]
    for p in net.collect_params().values():
        assert p.list_ctx() == [mx.tpu(1)]
        assert p.data()._data.devices() == {dev1}, p.name
    assert out._data.devices() == {dev1}
    # no context given: uncommitted on the default device, as before
    free = gluon.nn.Dense(4, in_units=3)
    free.initialize()
    assert free.weight.data()._data.devices() == {jax.devices()[0]}
