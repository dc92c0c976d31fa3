"""MFU diagnosis probe: what limits ResNet-50 throughput on this chip?

Times (a) a raw bf16 matmul (MXU ceiling), (b) a representative conv
microbench, (c) a hand-written pure-JAX NHWC bf16 ResNet-50 forward
with folded BN (the framework-free ceiling), and (d) the framework's
own hybridized forward, at several batch sizes. Comparing (c) vs (d)
separates lowering overhead from XLA/hardware limits.

    python tools/mfu_probe.py [--quick]
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# single source of truth for the per-image FLOP estimate (bench.py:32)
from bench import RESNET50_GFLOPS  # noqa: E402


def _peak_tflops():
    """bf16 peak of the attached chip; an unknown device kind raises."""
    import jax
    from mxnet_tpu.profiling.ledger import device_peaks
    return device_peaks(jax.devices()[0].device_kind)["bf16_tflops"]


def timeit(fn, args, sync, iters=30, warmup=3):
    for _ in range(warmup):
        sync(fn(*args))
    best = None
    for _ in range(3):
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        sync(out)
        dt = (time.perf_counter() - t0) / iters
        best = dt if best is None else min(best, dt)
    return best


def probe_matmul(sync):
    import jax
    import jax.numpy as jnp
    n = 4096
    a = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda x, y: x @ y)
    dt = timeit(f, (a, a), sync)
    tf = 2 * n ** 3 / dt / 1e12
    print("matmul %dx%d bf16: %.1f TFLOP/s (%.2f of peak)"
          % (n, n, tf, tf / _peak_tflops()))
    return tf


def probe_conv(sync, batch=128):
    import jax
    import jax.numpy as jnp
    from jax import lax
    # mid-network ResNet conv: 3x3 s1 28x28x128
    x = jnp.ones((batch, 28, 28, 128), jnp.bfloat16)
    w = jnp.ones((3, 3, 128, 128), jnp.bfloat16)
    f = jax.jit(functools.partial(
        lax.conv_general_dilated, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    dt = timeit(f, (x, w), sync)
    fl = 2 * batch * 28 * 28 * 128 * 128 * 9
    tf = fl / dt / 1e12
    print("conv3x3 28x28x128 bs%d: %.1f TFLOP/s (%.2f of peak)"
          % (batch, tf, tf / _peak_tflops()))
    return tf


def _pure_resnet50(batch):
    """Framework-free NHWC bf16 ResNet-50 v1 with BN pre-folded."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.default_rng(0)
    layers = [3, 4, 6, 3]
    chans = [64, 128, 256, 512]

    params = []

    def mk(shape):
        params.append(jnp.asarray(
            rng.normal(0, 0.05, shape).astype(np.float32), jnp.bfloat16))
        return len(params) - 1

    def conv_spec(cin, cout, k):
        return mk((k, k, cin, cout)), mk((cout,))  # weight, folded bias

    stem = conv_spec(3, 64, 7)
    blocks = []
    cin = 64
    for st, (n, c) in enumerate(zip(layers, chans)):
        stage = []
        for b in range(n):
            mid = c
            cout = c * 4
            proj = conv_spec(cin, cout, 1) if (b == 0) else None
            stage.append((proj,
                          conv_spec(cin, mid, 1),
                          conv_spec(mid, mid, 3),
                          conv_spec(mid, cout, 1),
                          2 if (b == 0 and st > 0) else 1))
            cin = cout
        blocks.append(stage)
    fc_w = mk((2048, 1000))
    fc_b = mk((1000,))

    def conv(x, wi, bi, stride=1, k=1):
        w = P[wi]
        pad = "SAME"
        y = lax.conv_general_dilated(
            x, w, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return y + P[bi]

    P = None

    def forward(pvals, x):
        nonlocal P
        P = pvals
        x = conv(x, stem[0], stem[1], 2, 7)
        x = jax.nn.relu(x)
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
        for stage in blocks:
            for proj, c1, c2, c3, stride in stage:
                sc = x
                if proj is not None:
                    sc = conv(x, proj[0], proj[1], stride)
                y = jax.nn.relu(conv(x, c1[0], c1[1], stride))
                y = jax.nn.relu(conv(y, c2[0], c2[1], 1))
                y = conv(y, c3[0], c3[1], 1)
                x = jax.nn.relu(y + sc)
        x = jnp.mean(x, axis=(1, 2))
        return x @ P[fc_w] + P[fc_b]

    return jax.jit(forward), tuple(params)


def probe_pure(sync, batch):
    import jax.numpy as jnp
    f, pvals = _pure_resnet50(batch)
    x = jnp.ones((batch, 224, 224, 3), jnp.bfloat16)
    dt = timeit(f, (pvals, x), sync, iters=20)
    ips = batch / dt
    mfu = ips * RESNET50_GFLOPS / (_peak_tflops() * 1e3)
    print("pure-jax resnet50 NHWC bs%d: %.0f img/s mfu %.3f"
          % (batch, ips, mfu))
    return ips, mfu


def probe_framework(sync, batch, layout="NHWC", fuse=True):
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench
    f, pvals = bench.build_forward(batch, layout=layout, fuse=fuse)
    pvals = jax.device_put(pvals)
    x = jnp.ones((batch, 3, 224, 224), jnp.bfloat16)
    dt = timeit(f, (pvals, x), sync, iters=20)
    ips = batch / dt
    mfu = ips * RESNET50_GFLOPS / (_peak_tflops() * 1e3)
    print("framework resnet50 %s fuse=%s bs%d: %.0f img/s mfu %.3f"
          % (layout, fuse, batch, ips, mfu))
    return ips, mfu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--skip-framework", action="store_true")
    ap.add_argument("--json", help="write results to this path "
                                   "(machine-readable artifact)")
    args = ap.parse_args()

    import jax

    import mxnet_tpu as mx
    mx.util.enable_compile_cache()
    print("devices:", jax.devices())
    sync = jax.block_until_ready

    results = {"backend": jax.default_backend(),
               "peak_tflops": _peak_tflops(), "batch": args.batch}
    results["matmul_tflops"] = round(probe_matmul(sync), 2)
    results["conv_tflops_bs%d" % args.batch] = round(
        probe_conv(sync, args.batch), 2)
    ips, mfu = probe_pure(sync, args.batch)
    results["pure_resnet50_img_s"] = round(ips, 1)
    results["pure_resnet50_mfu"] = round(mfu, 4)
    if not args.quick:
        ips2, _ = probe_pure(sync, args.batch * 2)
        results["pure_resnet50_img_s_bs%d" % (args.batch * 2)] = round(
            ips2, 1)
    if not args.skip_framework:
        fips, fmfu = probe_framework(sync, args.batch)
        results["framework_resnet50_img_s"] = round(fips, 1)
        results["framework_resnet50_mfu"] = round(fmfu, 4)
    if args.json:
        import json
        results["measured_at"] = time.strftime("%Y-%m-%d %H:%M:%S")
        # atomic, like bench._save_last_good: a kill mid-dump must not
        # leave a truncated artifact
        with open(args.json + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
        os.replace(args.json + ".tmp", args.json)
        print("artifact:", args.json)


if __name__ == "__main__":
    main()
