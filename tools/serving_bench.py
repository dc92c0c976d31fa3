#!/usr/bin/env python
"""Serving-path bench — versioned artifact for ``perf_gate --serving``.

Stages (ROADMAP item 1 / VERDICT stretch #9 + Missing #4):

  1. ``serial_bs1_fp32``: direct ``Predictor.forward`` loop at bs=1 —
     the no-gateway baseline every throughput ratio divides by.
  2. ``gateway_bs1_{fp32,bf16,int8}``: single in-flight request
     latency through the gateway per precision variant (max_wait=0,
     bucket 1) — the bs=1 FP32-vs-bf16-vs-INT8 latency artifact. On
     hosts without int8 compute the int8 variant serves the weight-
     only (dequant) lowering; the native int8 graph is additionally
     measured as ``gateway_bs1_int8_native`` so the artifact carries
     both numbers, clearly labeled.
  3. ``gateway_concurrent_fp32``: closed-loop client threads through
     the continuous batcher — throughput must reach >= 3x the serial
     baseline at bounded p99 (the dynamic-batching win).
  4. ``dispatch_overhead_bs1``: the eager-dispatch probe — wall-clock
     of a jitted bs=1 forward vs the device-busy window from a
     jax.profiler capture (PR 6 xplane machinery). The committed
     python-dispatch share is the data behind the §2.7 "thin native
     completion layer" decision.
  5. ``divergence``: gateway (padded, bucketed) fp32 output vs direct
     ``Predictor.forward`` — must be bitwise zero.
  6. ``generate``: the token-granular decode plane — a gluon decoder
     LM through the paged KV cache + iteration-level continuous
     batcher. Single-stream and concurrent tokens/s, client-side
     p50/p99 inter-token latency, the cache-occupancy histogram
     sampled at every decode step, greedy-vs-unpaged-reference token
     equality, and the paged-attention kernel's interpret-mode parity
     vs its gather fallback (the per-kernel number a live chip window
     replaces with compiled timings).
  7. ``sharded``: the layout plane's mesh-sliced serving — the same
     model registered as a tp=2 slice (one SPMD program per batch,
     parameters placed from the SpecLayout role table) next to a
     replicated single-device twin: req/s + p99 for both, and the
     sharded output's divergence vs the direct single-device
     reference pinned under the DOCUMENTED ulp bound
     (serving/sharded.DIVERGENCE_BOUND — row-parallel layers
     reassociate one reduction; everything else is bitwise). Runs in
     a forced-2-device child CPU mesh so the stage exists on any
     host; the child's device count rides the stage record.

    python tools/serving_bench.py \
        [--json docs/artifacts/serving_bench_YYYYMMDD.json] \
        [--tail-json docs/artifacts/tail_YYYYMMDD.json]

Artifact is versioned (``"version": 1``), gated by
``tools/perf_gate.py --serving`` against
docs/artifacts/SERVING_LAST_GOOD.json (a committed copy).

The two open-loop storm stages (``gateway_concurrent_fp32`` and
``generate``) additionally record per-request critical-path
attribution (``mxnet_tpu.profiling.tailpath``): their time windows
are harvested from the span layer after the storms, joined into a
``tail/v1`` blame artifact written by ``--tail-json`` and embedded
(bounded) under the bench doc's ``tail`` key. That artifact is the
input to ``tools/tail_report.py`` and ``perf_gate --tail``
(docs/observability.md "Why is this request slow").
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the open-loop storms retire far more spans than the default
# per-thread trace ring holds; the tail joiner skips any request tree
# the ring evicted a child from, so give it room (before the package
# import freezes the ring size)
os.environ.setdefault("MXTPU_TRACE_RING", "65536")


def build_model(rng, width=256, layers=96):
    """Deep narrow MLP: the launch-bound bs=1 regime that motivates
    continuous batching (per-layer dispatch/thunk overhead dominates a
    single row's FLOPs — on TPU this is exactly why bs=1 serving
    underuses the chip, VERDICT Missing #4). Batched execution
    amortizes the per-op cost, so the batching gain this bench commits
    measures the scheduler, not one host's GEMM width. Quantizable
    end to end (every layer is FullyConnected)."""
    import mxnet_tpu as mx
    from mxnet_tpu import sym

    nd = mx.nd
    data = sym.var("data")
    h = data
    args = {}
    for i in range(layers):
        h = sym.Activation(
            sym.FullyConnected(h, name=f"fc{i}", num_hidden=width),
            act_type="relu")
        args[f"fc{i}_weight"] = nd.array(
            rng.normal(0, 0.1, (width, width)).astype(np.float32))
        args[f"fc{i}_bias"] = nd.array(np.zeros(width, np.float32))
    out = sym.FullyConnected(h, name="fco", num_hidden=10)
    args["fco_weight"] = nd.array(
        rng.normal(0, 0.1, (10, width)).astype(np.float32))
    args["fco_bias"] = nd.array(np.zeros(10, np.float32))
    return out, args, {}, (width,)


def lat_stats(lats_s):
    a = sorted(lats_s)
    n = len(a)
    return {
        "n": n,
        "p50_ms": round(a[n // 2] * 1e3, 4),
        "p90_ms": round(a[min(int(n * 0.9), n - 1)] * 1e3, 4),
        "p99_ms": round(a[min(int(n * 0.99), n - 1)] * 1e3, 4),
        "mean_ms": round(sum(a) / n * 1e3, 4),
    }


def stage_serial(pred, x, n):
    pred.forward(data=x)                      # compile outside timing
    lats = []
    t_all = time.perf_counter()
    for _ in range(n):
        t0 = time.perf_counter()
        pred.forward(data=x)
        lats.append(time.perf_counter() - t0)
    total = time.perf_counter() - t_all
    out = lat_stats(lats)
    out["req_per_s"] = round(n / total, 2)
    return out


def stage_gateway_bs1(gw, model, variants, x, n, blocks=6):
    """Per-variant bs=1 latency through the gateway, measured in
    interleaved blocks so slow system drift (GC, cron, thermal) lands
    on every variant equally — the fp32-vs-bf16-vs-int8 comparison is
    the artifact's point, so it must not be an artifact of ordering."""
    lats = {v: [] for v in variants}
    for v in variants:
        gw.infer(model, x, variant=v)         # warm
    per_block = max(n // blocks, 1)
    for _ in range(blocks):
        for v in variants:
            for _ in range(per_block):
                t0 = time.perf_counter()
                gw.infer(model, x, variant=v)
                lats[v].append(time.perf_counter() - t0)
    out = {}
    for v in variants:
        st = lat_stats(lats[v])
        st["req_per_s"] = round(
            st["n"] / (sum(lats[v]) or 1e-9), 2)
        out[v] = st
    return out


def stage_concurrent(gw, model, feature, clients, inflight, seconds,
                     rng):
    """Pipelined (open-loop) clients, rows=1 requests: each keeps
    ``inflight`` submissions outstanding and drains the oldest — the
    async-client load shape that lets the continuous batcher's
    busy-period accumulation coalesce real batches (a new batch scoops
    whatever queued while the previous one executed)."""
    import mxnet_tpu as mx

    xs = [rng.normal(0, 1, (1,) + feature).astype(np.float32)
          for _ in range(8)]
    gw.infer(model, xs[0])                    # warm the whole ladder
    stop = [False]
    done = []
    rejected = [0]
    lock = threading.Lock()

    def client(i):
        my = []
        rej = 0
        pend = []
        k = 0
        while not stop[0]:
            while len(pend) < inflight and not stop[0]:
                t0 = time.perf_counter()
                try:
                    pend.append((t0, gw.submit(model,
                                               xs[(i + k) % len(xs)])))
                except mx.serving.RejectedError:
                    rej += 1
                    time.sleep(0.001)         # client backoff
                k += 1
            if not pend:
                continue
            t0, req = pend.pop(0)
            req.result(60.0)
            my.append(time.perf_counter() - t0)
        for t0, req in pend:                  # drain the tail
            try:
                req.result(60.0)
                my.append(time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — shutdown race
                pass
        with lock:
            done.extend(my)
            rejected[0] += rej

    reg = mx.telemetry.registry()
    b0 = reg.value("mx_serving_batches_total", model=model,
                   variant="fp32")
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t_all = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop[0] = True
    for t in threads:
        t.join()
    total = time.perf_counter() - t_all
    batches = reg.value("mx_serving_batches_total", model=model,
                        variant="fp32") - b0
    out = lat_stats(done) if done else {"n": 0}
    out.update({
        "req_per_s": round(len(done) / total, 2),
        "clients": clients,
        "inflight_per_client": inflight,
        "duration_s": round(total, 2),
        "rejected": rejected[0],
        "batches": int(batches),
        "mean_batch_rows": round(len(done) / batches, 2)
        if batches else None,
    })
    return out


def stage_dispatch(gw, model, x, n):
    """Python dispatch vs device time at bs=1: wall of the jitted call
    minus the device-busy window of a jax.profiler capture over the
    same loop (profiling/xplane.py's reconciliation quantity)."""
    import jax

    from mxnet_tpu.profiling import xplane

    vs = gw.registry.get(model).replicas[0].variant_set
    fn, pvals = vs._fns["fp32"]
    feed = {vs.input_name: jax.device_put(x)}

    def once():
        out = fn(pvals, feed)
        out[0].block_until_ready()

    once()                                    # warm
    t0 = time.perf_counter()
    for _ in range(n):
        once()
    wall_s = (time.perf_counter() - t0) / n
    profile_dir = tempfile.mkdtemp(prefix="serving_bench_xplane_")
    jax.profiler.start_trace(profile_dir)
    try:
        for _ in range(n):
            once()
    finally:
        jax.profiler.stop_trace()
    planes = xplane.load_xspace(profile_dir)
    device_s = xplane.measure_ops(planes, set())["window_s"] / n
    dispatch_s = max(wall_s - device_s, 0.0)
    return {
        "n": n,
        "wall_ms_per_call": round(wall_s * 1e3, 4),
        "device_ms_per_call": round(device_s * 1e3, 4),
        "python_dispatch_ms": round(dispatch_s * 1e3, 4),
        "dispatch_frac": round(dispatch_s / wall_s, 4)
        if wall_s > 0 else None,
    }


def stage_divergence(gw, model, pred_cls, symbol, args, aux, feature,
                     rng, rows_list=(1, 3, 5)):
    """Gateway (padded to a bucket) vs direct Predictor at the natural
    shape — per-row results must not diverge AT ALL: padding rows are
    dead weight, never an input to live rows."""
    worst = 0.0
    bitwise = True
    for rows in rows_list:
        x = rng.normal(0, 1, (rows,) + feature).astype(np.float32)
        got = gw.infer(model, x)
        pred = pred_cls(symbol, args, aux,
                        {"data": (rows,) + feature})
        want = pred.forward(data=x)
        for g, w in zip(got, want):
            worst = max(worst, float(np.abs(
                np.asarray(g, np.float64) - np.asarray(w, np.float64))
                .max()))
            bitwise = bitwise and np.array_equal(g, w)
    return {"rows_checked": list(rows_list),
            "max_abs_fp32": worst, "bitwise_equal": bool(bitwise)}


def stage_generate(gw, rng, clients=4, seconds=4.0, vocab=256,
                   d_model=64, layers=2, heads=4, max_prompt=32,
                   block_tokens=8, max_blocks=96, max_new=32,
                   max_decode_batch=8):
    # max_blocks sized so the open-loop load actually exercises the
    # pool (~8 in-flight x up to 8 blocks each + headroom): the
    # occupancy histogram should show a WORKING cache, and admission
    # may shed kv_cache_full under bursts — that is the product
    # behaving, not a bench failure
    """The decode-plane stage: tokens/s + inter-token latency through
    ``Gateway.generate`` with the paged cache, plus the greedy
    correctness pin and the paged-kernel parity micro-check."""
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.ops import pallas_kernels as pk
    from mxnet_tpu.serving.generate import (GenerativeDecoder,
                                            reference_generate)

    mx.random.seed(7)
    dec = GenerativeDecoder(vocab_size=vocab, d_model=d_model,
                            num_layers=layers, num_heads=heads,
                            max_prompt_tokens=max_prompt)
    t0 = time.perf_counter()
    gw.register_generator("bench_lm", dec, block_tokens=block_tokens,
                          max_blocks=max_blocks,
                          max_new_tokens=max_new,
                          max_decode_batch=max_decode_batch)
    warmup_s = time.perf_counter() - t0

    # correctness pin: gateway greedy == unpaged reference, tokens
    prompt = [int(t) for t in rng.integers(1, vocab, 12)]
    got = gw.generate("bench_lm", prompt, max_new_tokens=16)
    want = reference_generate(dec, prompt, 16)
    greedy_equal = got == want

    # single stream: sequential requests, max budget each
    n_single = 5
    t0 = time.perf_counter()
    single_tokens = 0
    for i in range(n_single):
        p = [int(t) for t in rng.integers(1, vocab, 8 + 2 * i)]
        single_tokens += len(gw.generate("bench_lm", p,
                                         max_new_tokens=max_new))
    single_s = time.perf_counter() - t0

    # concurrent: open streams, iteration-level joins/leaves
    stop = [False]
    inter = []
    ttft = []
    counts = [0, 0]  # requests, rejected
    lock = threading.Lock()

    def client(ci):
        crng = np.random.default_rng(100 + ci)
        my_inter, my_ttft = [], []
        reqs = rej = 0
        while not stop[0]:
            # long-prompt mix: client 0 always submits a full-length
            # prompt so the prefill-interleave stall (other requests'
            # admission prefills holding a decode step) is robustly
            # exercised — the tail artifact's prefill_interleave bin
            # must be nonzero under this load (perf_gate --tail)
            plen = max_prompt if ci == 0 \
                else int(crng.integers(4, max_prompt + 1))
            p = crng.integers(1, vocab, plen)
            nnew = int(crng.integers(max_new // 2, max_new + 1))
            t_sub = time.perf_counter()
            try:
                req = gw.generate("bench_lm", p, max_new_tokens=nnew,
                                  stream=True)
            except mx.serving.RejectedError:
                rej += 1
                time.sleep(0.002)
                continue
            reqs += 1
            last = None
            for _ in req.stream():
                now = time.perf_counter()
                if last is None:
                    my_ttft.append(now - t_sub)
                else:
                    my_inter.append(now - last)
                last = now
        with lock:
            inter.extend(my_inter)
            ttft.extend(my_ttft)
            counts[0] += reqs
            counts[1] += rej

    reg = mx.telemetry.registry()
    tok0 = reg.value("mx_serving_generate_tokens_total",
                     model="bench_lm", phase="decode") or 0
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    t_all = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop[0] = True
    for t in threads:
        t.join()
    conc_s = time.perf_counter() - t_all
    conc_tokens = (reg.value("mx_serving_generate_tokens_total",
                             model="bench_lm", phase="decode") or 0) \
        - tok0

    # cache-occupancy histogram: sampled by the scheduler at every
    # decode step (used fraction of the block pool)
    occ = {"samples": 0, "mean_used_frac": None, "buckets": {}}
    fam = reg.find("mx_serving_generate_cache_occupancy")
    if fam is not None:
        s = fam.labels(model="bench_lm")
        count, total, cum = s.stats()
        occ = {"samples": int(count),
               "mean_used_frac": round(total / count, 4) if count
               else None,
               "buckets": {str(le): int(c) for le, c in cum}}

    pool = gw.stats()["bench_lm"]["lanes"][0]["pool"]
    gw.unregister("bench_lm")

    # per-kernel micro-check: the paged Pallas kernel against its
    # gather fallback at a serving-ish shape (interpret mode on CPU —
    # the compiled-kernel timing lands with a live chip window)
    krng = np.random.default_rng(3)
    bq, nb, nmax = 8, 64, 8
    hd = d_model // heads
    q = jnp.asarray(krng.normal(size=(bq, heads, hd)).astype(np.float32))
    kc = jnp.asarray(krng.normal(
        size=(nb, block_tokens, heads, hd)).astype(np.float32))
    vc = jnp.asarray(krng.normal(
        size=(nb, block_tokens, heads, hd)).astype(np.float32))
    tables = jnp.asarray(
        krng.integers(1, nb, (bq, nmax)).astype(np.int32))
    lens = jnp.asarray(
        krng.integers(1, nmax * block_tokens, (bq,)).astype(np.int32))
    fb = pk.paged_attention(q, kc, vc, tables, lens)
    kn = pk.paged_attention(q, kc, vc, tables, lens, force=True)
    parity = float(jnp.abs(fb - kn).max())
    t0 = time.perf_counter()
    n_kernel = 50
    for _ in range(n_kernel):
        pk.paged_attention(q, kc, vc, tables, lens).block_until_ready()
    fallback_us = (time.perf_counter() - t0) / n_kernel * 1e6

    inter_st = lat_stats(inter) if inter else {"n": 0}
    return {
        "model": {"net": "decoder-lm-d%d-l%d-h%d" % (d_model, layers,
                                                     heads),
                  "vocab": vocab, "block_tokens": block_tokens,
                  "max_blocks": max_blocks, "max_new": max_new,
                  "max_decode_batch": max_decode_batch},
        "warmup_seconds": round(warmup_s, 2),
        "greedy_equals_reference": bool(greedy_equal),
        "single_stream": {
            "requests": n_single,
            "tokens": single_tokens,
            "tokens_per_s": round(single_tokens / single_s, 2),
        },
        "concurrent": {
            "clients": clients,
            "duration_s": round(conc_s, 2),
            "requests": counts[0],
            "rejected": counts[1],
            "tokens": int(conc_tokens),
            "ttft_ms": lat_stats(ttft) if ttft else {"n": 0},
        },
        "tokens_per_s": round(conc_tokens / conc_s, 2),
        "inter_token_p50_ms": inter_st.get("p50_ms"),
        "inter_token_p99_ms": inter_st.get("p99_ms"),
        "inter_token_ms": inter_st,
        "cache_occupancy": occ,
        "pool": pool,
        "paged_kernel": {
            "parity_max_abs_vs_fallback": parity,
            "interpret_checked": True,
            "fallback_us_per_call": round(fallback_us, 1),
            "shape": {"batch": bq, "heads": heads, "head_dim": hd,
                      "blocks": nb, "table_width": nmax},
        },
    }


def run_sharded_stage(n=150, width=128, layers=12, tp=2):
    """The ``sharded`` stage body (runs in the forced-multi-device
    child): tp-sliced variant vs replicated twin on the same symbol
    + weights, plus the divergence-vs-reference pin."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.serving.sharded import DIVERGENCE_BOUND

    rng = np.random.default_rng(0)
    symbol, args, aux, feature = build_model(rng, width=width,
                                             layers=layers)
    gw = mx.serving.Gateway()
    t0 = time.perf_counter()
    gw.register("bench_tp", symbol, args, aux,
                input_shapes={"data": feature}, variants=("fp32",),
                buckets=(1, 8), max_wait_ms=0.0, tp=tp)
    gw.register("bench_tp_twin", symbol, args, aux,
                input_shapes={"data": feature}, variants=("fp32",),
                buckets=(1, 8), max_wait_ms=0.0)
    warmup_s = time.perf_counter() - t0
    x1 = rng.normal(0, 1, (1,) + feature).astype(np.float32)

    def measure(model):
        gw.infer(model, x1)                    # warm
        lats = []
        t_all = time.perf_counter()
        for _ in range(n):
            t0 = time.perf_counter()
            gw.infer(model, x1)
            lats.append(time.perf_counter() - t0)
        total = time.perf_counter() - t_all
        st = lat_stats(lats)
        st["req_per_s"] = round(n / total, 2)
        return st

    for model in ("bench_tp", "bench_tp_twin"):
        gw.infer(model, x1)                    # warm both ladders
    res = {}
    for m_name, key in (("bench_tp", "sharded"),
                        ("bench_tp_twin", "replicated")):
        res[key] = measure(m_name)

    # divergence: sharded (padded, SPMD) vs direct single-device
    # Predictor — the tp>=2 outputs-match-reference acceptance pin
    worst = 0.0
    bitwise = True
    for rows in (1, 3, 5):
        x = rng.normal(0, 1, (rows,) + feature).astype(np.float32)
        got = gw.infer("bench_tp", x)
        pred = mx.predictor.Predictor(symbol, args, aux,
                                      {"data": (rows,) + feature})
        want = pred.forward(data=x)
        for g, w in zip(got, want):
            worst = max(worst, float(np.abs(
                np.asarray(g, np.float64) - np.asarray(w, np.float64))
                .max()))
            bitwise = bitwise and np.array_equal(g, w)
    stats = gw.stats()
    report = stats["bench_tp"]
    gw.close()
    return {
        "tp": tp,
        "devices": len(jax.local_devices()),
        "backend": jax.default_backend(),
        "model": {"net": "mlp-%dx%d-relu-fc10" % (width, layers),
                  "buckets": [1, 8]},
        "warmup_seconds": round(warmup_s, 2),
        "sharded": res["sharded"],
        "replicated": res["replicated"],
        "ratio_sharded_vs_replicated": round(
            res["sharded"]["req_per_s"] /
            res["replicated"]["req_per_s"], 4)
        if res["replicated"]["req_per_s"] else None,
        "req_per_s": res["sharded"]["req_per_s"],
        "p99_ms": res["sharded"]["p99_ms"],
        "slice_devices": [r["device"]
                          for r in report["replicas"]],
        "degraded": report["degraded"],
        "divergence": {
            "rows_checked": [1, 3, 5],
            "max_abs_fp32": worst,
            "bitwise_equal": bool(bitwise),
            "bound": DIVERGENCE_BOUND,
            "within_bound": bool(worst <= DIVERGENCE_BOUND),
        },
    }


def stage_sharded(n=150, width=128, layers=12, tp=2):
    """Run :func:`run_sharded_stage` in a child interpreter on a
    forced ``tp+1``-device CPU mesh (slice + a disjoint device for
    the replicated twin) — the stage must exist on single-chip hosts
    too, and env tweaks after jax import are too late. The child keeps
    JAX_PLATFORMS=cpu, so it never loads the TPU library beside a
    parent that holds the chip."""
    import subprocess
    import tempfile

    out_path = os.path.join(tempfile.mkdtemp(prefix="serving_bench_"),
                            "sharded.json")
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env["JAX_PLATFORMS"] = "cpu"
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append("--xla_force_host_platform_device_count=%d"
                 % (tp + 1))
    env["XLA_FLAGS"] = " ".join(flags)
    code = (
        "import json, sys\n"
        "sys.path.insert(0, %r)\n"
        "import serving_bench\n"
        "doc = serving_bench.run_sharded_stage(n=%d, width=%d, "
        "layers=%d, tp=%d)\n"
        "open(%r, 'w').write(json.dumps(doc))\n"
        % (os.path.dirname(os.path.abspath(__file__)), n, width,
           layers, tp, out_path))
    try:
        proc = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True,
                              timeout=900)
    except subprocess.TimeoutExpired:
        # a wedged child must cost ONE stage, not the whole artifact
        # (the six already-measured stages still commit; perf_gate
        # flags the error record as the regression)
        return {"error": "sharded stage child timed out after 900s"}
    if proc.returncode != 0 or not os.path.exists(out_path):
        return {"error": "sharded stage child failed rc=%d: %s"
                % (proc.returncode, proc.stderr[-2000:])}
    with open(out_path, encoding="utf-8") as f:
        return json.load(f)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="serving_bench", description=__doc__.splitlines()[0])
    ap.add_argument("--json", default=None,
                    help="artifact output path (default stdout only)")
    ap.add_argument("--tail-json", default=None,
                    help="tail/v1 attribution artifact path "
                         "(perf_gate --tail input; default: embed "
                         "summary only)")
    ap.add_argument("--n", type=int, default=300,
                    help="requests per latency stage (300)")
    ap.add_argument("--clients", type=int, default=4,
                    help="pipelined client threads (4)")
    ap.add_argument("--inflight", type=int, default=32,
                    help="outstanding requests per client (32)")
    ap.add_argument("--seconds", type=float, default=4.0,
                    help="concurrent-stage duration (4s)")
    ap.add_argument("--gen-seconds", type=float, default=4.0,
                    help="generate-stage concurrent duration (4s)")
    ap.add_argument("--width", type=int, default=256,
                    help="MLP width (256)")
    ap.add_argument("--layers", type=int, default=96,
                    help="MLP depth (96 — deep enough that bs=1 is "
                         "dispatch/launch-bound)")
    ap.add_argument("--tp", type=int, default=2,
                    help="mesh-slice width for the sharded stage (2)")
    ap.add_argument("--calib-mode", default="naive",
                    choices=("naive", "entropy"),
                    help="int8 calibration mode (naive: keeps a CI "
                         "run in seconds; entropy = the KL flow)")
    args_ns = ap.parse_args(argv)

    import jax

    import mxnet_tpu as mx

    rng = np.random.default_rng(0)
    symbol, args, aux, feature = build_model(
        rng, width=args_ns.width, layers=args_ns.layers)
    calib = rng.normal(0, 1, (32,) + feature).astype(np.float32)
    x1 = rng.normal(0, 1, (1,) + feature).astype(np.float32)

    gw = mx.serving.Gateway()
    t0 = time.perf_counter()
    # bs1 model: bucket (1,), zero hold — the latency-optimal end of
    # the max_wait knob; all three precision variants
    gw.register("bench_bs1", symbol, args, aux,
                input_shapes={"data": feature},
                variants=("fp32", "bf16", "int8"), calib_data=calib,
                calib_mode=args_ns.calib_mode, buckets=(1,),
                max_wait_ms=0.0)
    # native-int8 twin: the chip-lowering number, committed next to
    # the auto one so the artifact is explicit about what ran
    gw.register("bench_bs1_native", symbol, args, aux,
                input_shapes={"data": feature}, variants=("int8",),
                calib_data=calib, calib_mode=args_ns.calib_mode,
                buckets=(1,), max_wait_ms=0.0, int8_lowering="native")
    # throughput model: coarse bucket ladder (fewer AOT compiles, <2x
    # padding), zero hold — busy-period accumulation coalesces
    gw.register("bench_conc", symbol, args, aux,
                input_shapes={"data": feature}, variants=("fp32",),
                buckets=(1, 4, 16, 64, 128), max_wait_ms=0.0)
    warmup_s = time.perf_counter() - t0

    stages = {}
    pred = mx.predictor.Predictor(symbol, args, aux,
                                  {"data": (1,) + feature})
    stages["serial_bs1_fp32"] = stage_serial(pred, x1, args_ns.n)
    for variant, st in stage_gateway_bs1(
            gw, "bench_bs1", ("fp32", "bf16", "int8"), x1,
            args_ns.n).items():
        stages["gateway_bs1_%s" % variant] = st
    stages["gateway_bs1_int8_native"] = stage_gateway_bs1(
        gw, "bench_bs1_native", ("int8",), x1,
        max(args_ns.n // 3, 50))["int8"]
    # the two open-loop storms carry the tail-attribution windows:
    # every request whose root span STARTS inside [t0, t1) is joined
    # into the tail/v1 artifact under that stage's name
    t_conc0 = mx.tracing.clock.now_ns()
    stages["gateway_concurrent_fp32"] = stage_concurrent(
        gw, "bench_conc", feature, args_ns.clients, args_ns.inflight,
        args_ns.seconds, rng)
    t_conc1 = mx.tracing.clock.now_ns()
    stages["dispatch_overhead_bs1"] = stage_dispatch(
        gw, "bench_bs1", x1, max(args_ns.n // 3, 50))
    t_gen0 = mx.tracing.clock.now_ns()
    stages["generate"] = stage_generate(
        gw, rng, clients=args_ns.clients,
        seconds=args_ns.gen_seconds)
    t_gen1 = mx.tracing.clock.now_ns()
    stages["sharded"] = stage_sharded(n=max(args_ns.n // 2, 50),
                                      tp=args_ns.tp)
    divergence = stage_divergence(gw, "bench_conc",
                                  mx.predictor.Predictor, symbol,
                                  args, aux, feature, rng)
    model_stats = gw.stats()
    gw.close()

    # harvest the storms' span trees once, after every stage retired
    # its spans, and join each storm's window separately so the
    # artifact attributes per stage
    from mxnet_tpu.profiling import tailpath
    tail_doc = None
    if tailpath.enabled():
        spans = mx.tracing.spans_snapshot()
        agg = tailpath.TailAggregator()
        agg.ingest_spans(spans, stage="concurrent",
                         t0_ns=t_conc0, t1_ns=t_conc1)
        agg.ingest_spans(spans, stage="generate",
                         t0_ns=t_gen0, t1_ns=t_gen1)
        tail_doc = agg.collect(provenance={
            "tool": "serving_bench",
            "host_cpus": os.cpu_count(),
        })

    serial = stages["serial_bs1_fp32"]["req_per_s"]
    conc = stages["gateway_concurrent_fp32"]["req_per_s"]
    fp32_p50 = stages["gateway_bs1_fp32"]["p50_ms"]
    int8_p50 = stages["gateway_bs1_int8"]["p50_ms"]
    doc = {
        "tool": "serving_bench",
        "version": 1,
        "generated": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "backend": jax.default_backend(),
        "devices": len(jax.local_devices()),
        "cpus": os.cpu_count(),
        "int8_lowering": model_stats["bench_bs1"]["int8_lowering"],
        "warmup_seconds": round(warmup_s, 2),
        "model": {"net": "mlp-%dx%d-relu-fc10"
                  % (args_ns.width, args_ns.layers),
                  "input": list(feature)},
        "stages": stages,
        "ratios": {
            "batching_gain": round(conc / serial, 3) if serial else None,
            "int8_vs_fp32_bs1": round(int8_p50 / fp32_p50, 4)
            if fp32_p50 else None,
            "bf16_vs_fp32_bs1": round(
                stages["gateway_bs1_bf16"]["p50_ms"] / fp32_p50, 4)
            if fp32_p50 else None,
        },
        "divergence": divergence,
    }
    if tail_doc is not None:
        emb = tailpath.summary(tail_doc)
        if emb is not None:
            doc["tail"] = emb
        if args_ns.tail_json:
            tailpath.dump(args_ns.tail_json, tail_doc)
            print("wrote %s" % args_ns.tail_json, file=sys.stderr)
    line = json.dumps(doc, indent=1)
    print(line)
    if args_ns.json:
        tmp = args_ns.json + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            f.write(line + "\n")
        os.replace(tmp, args_ns.json)
        print("wrote %s" % args_ns.json, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
