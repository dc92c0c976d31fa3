#!/usr/bin/env python
"""kernel_bench — per-kernel bench artifact for the Pallas fleet.

    python tools/kernel_bench.py -o docs/artifacts/kernel_bench.json
    python tools/kernel_bench.py --quick          # CI-sized shapes
    python tools/kernel_bench.py --update-last-good

One JSON artifact, one section per kernel in ``ops/pallas_kernels.py``
(flash_attention, paged_attention, int8_conv_epilogue, fused_sgd_mom,
fused_adam), each carrying:

- ``parity_max_abs`` / ``parity_ok`` — interpret-mode kernel output vs
  its numerics oracle (the jnp fallback, which IS the CPU hot path:
  ops/quantized.py for the INT8 epilogue, ops/optimizer_ops.py for the
  fused updates, the dense/gather references for attention);
- ``fallback_ms`` — jitted fallback timing on THIS host (the regression
  baseline perf_gate --kernels tracks);
- ``kernel_ms`` / ``kernel_vs_fallback`` — compiled-kernel timing and
  the speedup ratio, measured only on chip backends; ``null`` on CPU
  (interpret-mode timing is an interpreter benchmark, not a kernel
  benchmark — the committed artifact records parity + fallback and the
  compiled numbers land on the first live chip window, the same
  doctrine as the paged-attention artifact of the decode-plane PR).

Gate: ``tools/perf_gate.py --kernels`` (parity presence + truth,
fallback regression vs KERNELS_LAST_GOOD, ratio floor when measured,
dropped-kernel detection) with a tier-1 self-test over the committed
artifact (tests/test_fusion_cost.py).
"""
from __future__ import annotations

import argparse
import datetime as _dt
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ARTIFACT_VERSION = 1


def _median_ms(fn, steps, warmup=3):
    import jax

    for _ in range(warmup):
        out = fn()
    jax.block_until_ready(out)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def _max_abs(a, b):
    import numpy as np

    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _entry(shape, parity_max_abs, parity_tol, fallback_ms,
           kernel_ms=None, note=None):
    out = {
        "shape": shape,
        "parity_max_abs": parity_max_abs,
        "parity_tol": parity_tol,
        "parity_ok": parity_max_abs <= parity_tol,
        "fallback_ms": round(fallback_ms, 4),
        "kernel_ms": round(kernel_ms, 4) if kernel_ms else None,
        "kernel_vs_fallback": (round(fallback_ms / kernel_ms, 3)
                               if kernel_ms else None),
    }
    if note:
        out["note"] = note
    return out


_NO_CHIP = ("compiled kernel timing awaits a live chip window; "
            "parity pinned in interpret mode")


def bench_flash(steps, quick, on_chip):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_kernels as pk

    bh, t, d = (4, 512, 64) if quick else (8, 1024, 64)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((bh, t, d)), jnp.float32)
    scale = d ** -0.5
    ref = pk._dense_reference(q, k, v, True, scale)
    out = pk.flash_attention(q, k, v, causal=True, block_q=128,
                             block_k=128, force=True)
    fb = _median_ms(lambda: pk._dense_reference(q, k, v, True, scale),
                    steps)
    km = (_median_ms(lambda: pk.flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, force=True),
        steps) if on_chip else None)
    return _entry(f"BH{bh}xT{t}xD{d} causal f32", _max_abs(ref, out),
                  2e-5, fb, km, None if on_chip else _NO_CHIP)


def bench_paged(steps, quick, on_chip):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_kernels as pk

    b, h, d, nb, bt, maxb = (4, 4, 64, 32, 16, 8)
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, bt, h, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((nb, bt, h, d)), jnp.float32)
    tables = jnp.asarray(
        rng.integers(0, nb, (b, maxb)), jnp.int32)
    lens = jnp.asarray([bt * maxb, 37, 64, 1], jnp.int32)
    ref = pk._paged_gather_reference(q, kc, vc, tables, lens,
                                     d ** -0.5)
    out = pk.paged_attention(q, kc, vc, tables, lens, force=True)
    fb = _median_ms(lambda: pk._paged_gather_reference(
        q, kc, vc, tables, lens, d ** -0.5), steps)
    km = (_median_ms(lambda: pk.paged_attention(
        q, kc, vc, tables, lens, force=True), steps)
        if on_chip else None)
    return _entry(f"B{b}xH{h}xD{d} pool{nb}x{bt}", _max_abs(ref, out),
                  2e-6, fb, km, None if on_chip else _NO_CHIP)


def bench_int8_epilogue(steps, quick, on_chip):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.ops import quantized as q8

    shape = (8, 64, 28, 28) if quick else (32, 64, 28, 28)
    rng = np.random.default_rng(2)
    acc = jnp.asarray(rng.integers(-2 ** 22, 2 ** 22, shape), jnp.int32)
    mn, mx = jnp.float32(-6.4e6), jnp.float32(6.4e6)
    calib = 4.0

    def oracle():
        out, omin, omax = q8.requantize(acc, mn, mx,
                                        min_calib_range=-calib,
                                        max_calib_range=calib)
        return q8.quantized_act(out, omin, omax)

    ref = oracle()[0]
    out = pk.quantized_conv_epilogue(acc, mn, mx,
                                     min_calib_range=-calib,
                                     max_calib_range=calib, relu=True,
                                     force=True, interpret=not on_chip)[0]
    fb = _median_ms(oracle, steps)
    km = (_median_ms(lambda: pk.quantized_conv_epilogue(
        acc, mn, mx, min_calib_range=-calib, max_calib_range=calib,
        relu=True, force=True)[0], steps) if on_chip else None)
    # integer outputs: parity is exact, not approximate
    return _entry("x".join(map(str, shape)) + " i32->i8 relu",
                  _max_abs(ref, out), 0.0, fb, km,
                  None if on_chip else _NO_CHIP)


def _bench_opt(kind, steps, quick, on_chip):
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import optimizer_ops as oo
    from mxnet_tpu.ops import pallas_kernels as pk

    n = (1024 * 128) if quick else (4096 * 128)
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.standard_normal(n), jnp.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)
    m = jnp.asarray(rng.standard_normal(n), jnp.float32)
    hyper = dict(lr=0.05, wd=1e-4, rescale_grad=1 / 32,
                 clip_gradient=1.0)
    if kind == "fused_sgd_mom":
        oracle = lambda: oo.sgd_mom_update(w, g, m, momentum=0.9,
                                           **hyper)
        kern = lambda interp: pk.fused_sgd_mom(
            w, g, m, momentum=0.9, force=True, interpret=interp,
            **hyper)
    else:
        v = jnp.abs(jnp.asarray(rng.standard_normal(n), jnp.float32))
        oracle = lambda: oo.adam_update(w, g, m, v, **hyper)
        kern = lambda interp: pk.fused_adam(
            w, g, m, v, force=True, interpret=interp, **hyper)
    ref = oracle()
    out = kern(not on_chip)
    err = max(_max_abs(a, b) for a, b in zip(ref, out))
    fb = _median_ms(oracle, steps)
    km = _median_ms(lambda: kern(False), steps) if on_chip else None
    return _entry(f"{n} f32 params", err, 2e-6, fb, km,
                  None if on_chip else _NO_CHIP)


def run(steps=10, quick=False):
    import jax

    # pin the optimizer ops to their plain jnp bodies BEFORE anything
    # traces: on chip backends MXTPU_KERNEL_FUSED_OPT=auto would route
    # oo.sgd_mom_update/adam_update through the very Pallas kernel
    # under test — parity would compare the kernel against itself and
    # fallback_ms would time the kernel, not the fallback
    os.environ["MXTPU_KERNEL_FUSED_OPT"] = "0"
    backend = jax.default_backend()
    on_chip = backend == "tpu"
    kernels = {
        "flash_attention": bench_flash(steps, quick, on_chip),
        "paged_attention": bench_paged(steps, quick, on_chip),
        "int8_conv_epilogue": bench_int8_epilogue(steps, quick,
                                                  on_chip),
        "fused_sgd_mom": _bench_opt("fused_sgd_mom", steps, quick,
                                    on_chip),
        "fused_adam": _bench_opt("fused_adam", steps, quick, on_chip),
    }
    return {
        "tool": "kernel_bench",
        "version": ARTIFACT_VERSION,
        "generated": _dt.datetime.now(_dt.timezone.utc).strftime(
            "%Y-%m-%dT%H:%M:%SZ"),
        "backend": backend,
        "quick": bool(quick),
        "kernels": kernels,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(prog="kernel_bench",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("-o", "--out",
                    default=os.path.join(REPO, "docs", "artifacts",
                                         "kernel_bench.json"))
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized shapes (seconds, not minutes)")
    ap.add_argument("--update-last-good", action="store_true",
                    help="also refresh docs/artifacts/"
                         "KERNELS_LAST_GOOD.json")
    args = ap.parse_args(argv)
    doc = run(steps=args.steps, quick=args.quick)
    for k, e in doc["kernels"].items():
        print("%-20s parity=%.3g (tol %.3g, %s)  fallback=%.3fms  "
              "kernel=%s  ratio=%s"
              % (k, e["parity_max_abs"], e["parity_tol"],
                 "ok" if e["parity_ok"] else "FAIL", e["fallback_ms"],
                 e["kernel_ms"], e["kernel_vs_fallback"]))
    paths = [args.out]
    if args.update_last_good:
        paths.append(os.path.join(REPO, "docs", "artifacts",
                                  "KERNELS_LAST_GOOD.json"))
    for path in paths:
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, path)
        print("wrote", path)
    return 0 if all(e["parity_ok"] for e in doc["kernels"].values()) \
        else 1


if __name__ == "__main__":
    sys.exit(main())
