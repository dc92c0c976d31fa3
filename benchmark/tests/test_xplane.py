"""The reduction from a capture to busy time, operation time and idle
gaps, on hand-made planes in the layout of a chip capture, and the
reader on a capture taken here."""
import pytest

from benchmark.lib import xplane


def _planes():
    ops = [("fusion.1", 100, 200, "jit_step"),
           ("fusion.2", 150, 300, "jit_step"),     # overlaps fusion.1
           ("custom-call.7", 500, 600, "jit_step"),
           ("fusion.1", 900, 1200, "jit_step")]    # runs past the window
    host = [(xplane.WINDOW_SPAN, 0, 1000, None),
            ("bench.fwd_bwd", 290, 510, None),
            ("PjitFunction(f)", 300, 400, None),
            ("bench.update", 600, 910, None)]
    return [{"name": "/device:TPU:0",
             "lines": [{"name": "XLA Ops", "events": ops},
                       {"name": "Steps",
                        "events": [("step", 0, 5000, None)]}]},
            {"name": "/host:CPU",
             "lines": [{"name": "python", "events": host}]}]


def test_union():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert xplane.union_ns([]) == 0


def test_busy_is_the_union_inside_the_window():
    got = xplane.busy(_planes())
    # 100..300, 500..600, 900..1000 of a 1000 ns window; the Steps line
    # does not count
    assert got == {"busy_s": 400e-9, "window_s": 1000e-9}


def test_top_ops_and_match():
    top = dict(xplane.top_ops(_planes()))
    # fusion.1 (100 + 100 ns inside the window) and fusion.2 (150 ns)
    # are one operation's numbered copies
    assert top["fusion"] == pytest.approx(350e-9)
    assert top["custom-call"] == pytest.approx(100e-9)
    assert xplane.stem("fusion.12.remat2") == "fusion"
    assert xplane.stem("_paged_call.16") == "_paged_call"
    only = xplane.op_seconds(_planes(),
                             lambda n, m: n.startswith("custom-call"))
    assert only == {"custom-call.7": pytest.approx(100e-9)}


def test_idle_gaps_go_to_what_the_host_was_doing():
    gaps = dict(xplane.idle_gaps(_planes()))
    # 0..100 nothing on the host; 300..500 inside fwd_bwd, half of it
    # inside the nested PjitFunction, which the outer span outweighs;
    # 600..900 inside update
    assert gaps["bench.update"] == pytest.approx(300e-9)
    assert gaps["bench.fwd_bwd"] == pytest.approx(200e-9)
    assert gaps["unattributed"] == pytest.approx(100e-9)


def test_a_capture_without_device_operations_is_refused():
    planes = _planes()
    planes[0]["lines"][0]["events"] = []
    with pytest.raises(ValueError):
        xplane.busy(planes)


def test_reader_on_a_capture_taken_here(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(xplane.WINDOW_SPAN):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = xplane.load(str(tmp_path))
    got = xplane.busy(planes)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert xplane.top_ops(planes)


def test_paged_roofline_reader_counts_the_steps_inside_the_slice():
    import importlib.util
    import os

    from conftest import BENCH

    spec = importlib.util.spec_from_file_location(
        "paged_reader", os.path.join(BENCH, "layer_metrics",
                                     "paged_attn_roofline_pct.py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    planes = _planes()
    planes[0]["lines"][0]["events"] += [
        ("_paged_call.3", 610, 620, "hlo"), ("_paged_call.4", 700, 710, "hlo")]
    cfg = {"hidden_size": 8, "num_hidden_layers": 2,
           "kv_cache_bytes_per_value": 2}
    # one request of 5 prompt tokens: token 0 from the prefill, token 1
    # from a decode step inside the slice (6 cached tokens), token 2 from
    # one that ends after it
    req = {"prompt_len": 5, "steps": [(10, 20, 0, 5, 16),
                                      (105, 190, 0, 1, 1),
                                      (195, 260, 0, 1, 1)]}
    ctx = {"planes": planes, "cfg": cfg,
           "run": {"traced_ns": (100, 250), "requests": [req]},
           "peaks": {"hbm_bytes_per_s": 1e9}}
    # 2 layers x (6 tokens x K and V x 8 values x 2 bytes) = 384 bytes at
    # 1 GB/s = 384 ns, over 20 ns of kernel time
    assert reader.read(ctx) == pytest.approx(100.0 * 384e-9 / 20e-9)
    planes[0]["lines"][0]["events"] = planes[0]["lines"][0]["events"][:4]
    assert reader.read(ctx) is None
