"""Chip smoke: the gluon / gateway / decode main path, once, on a TPU.

    python chip_smoke.py            # one chip: train, serve, decode
    python chip_smoke.py --chips 4  # four chips: the data-parallel pair only

One process holds the chip. Every phase goes through the entry points a
user calls (``import mxnet_tpu as mx``), at the width the model is
published at; depth and step counts are cut, weights are random from
``--seed``. Each phase prints one JSON line with what it saw, then
raises if a check failed, so a failure ends the run with a non-zero
exit. The last line of a passing run is ``{"ok": true, "device":
{...}}`` with the device as JAX reports it. Without a TPU the gate exits
non-zero and prints no result. tests/test_chip_smoke.py calls the phase
functions at tiny sizes on the CPU mesh.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import tempfile
import time

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# comparisons with the reference, as max |got - ref| / max |ref| over the
# logits. fp32: the gateway and the direct forward are two XLA programs
# over the same ops (convolutions at the backend's default precision),
# so they differ by reduction order only. bf16: 8 mantissa bits through
# 50 layers. int8: calibrated on eight inputs, 54 int8 convolutions.
FP32_TOL = 1e-3
BF16_TOL = 1e-1
INT8_TOL = 1e-1
# a greedy token may differ from the reference's only where the
# reference's own logits put the two within this of each other
TIE_TOL = 1e-3
# four-context vs one-context loss, same global batch and seed: the
# programs differ in reduction order across the dp axis only
DP_LOSS_RTOL = 5e-2


class CompileMeter:
    """Counts XLA backend compilations and their seconds through
    jax.monitoring (a persistent-cache hit still counts as one event,
    with its retrieval time as the duration)."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def __call__(self, event, duration, **_kw):
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration


class Checks(list):
    """A phase's failed checks. They are collected, not raised on the
    spot, so that the phase line still shows what was seen;
    :func:`run_phase` raises once the line is out."""

    def __call__(self, ok, what):
        if not ok:
            self.append(what)


def device_gate(chips):
    """The devices, or exit non-zero naming what JAX found instead."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print("chip_smoke: needs %d TPU device(s); JAX found %r"
              % (chips, devices), file=sys.stderr)
        sys.exit(2)
    return devices


def seed_all(seed):
    """Weights come from the initializers, which draw from numpy's
    global stream; dropout and friends from the framework's."""
    import mxnet_tpu as mx

    np.random.seed(seed)
    mx.random.seed(seed)


def rel_err(got, ref):
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

# The gluon step is three XLA programs (forward; forward keeping the
# residuals; backward), and the residuals cross between them in HBM in
# default NCHW f32 layouts. memory_analysis() of those programs compiled
# for a described v5e (16 GB, 15.75 G usable): at batch 128 the
# residuals alone are 18.97 GiB and the backward program is refused
# ("Used 19.85G of 15.75G hbm"); at batch 64 they are 10.85 GiB and the
# largest program needs 12.83 GiB. So the train phase runs at 64.
TRAIN_BATCH = 64


def phase_train(meter, model="resnet50_v1", batch=TRAIN_BATCH, hw=224,
                classes=1000, steps=30, lr=0.01, seed=0):
    """Gluon SGD-momentum steps on one fixed batch. Thirty, not five:
    after five the served logits of different classes still lay within
    int8's error of each other (top-1 agreed on 7 of 8 inputs on the
    v5e); after thirty the loss is 0.2 and int8 agrees on 8 of 8.
    Returns (report, (net, inputs, labels)) — the serve phase exports
    the net and serves those inputs."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    seed_all(seed)
    rng = np.random.default_rng(seed)
    ctx = mx.tpu()
    net = gluon.model_zoo.vision.get_model(model, classes=classes)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize()
    # the usual shape-resolving forward: a hybridized block whose
    # parameter shapes are still deferred runs its first call op by op,
    # and under record() that keeps every activation twice
    net(mx.nd.zeros((2, 3, hw, hw), ctx=ctx))
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    inputs = rng.standard_normal((batch, 3, hw, hw), dtype=np.float32)
    labels = rng.integers(0, classes, batch)
    x = mx.nd.array(inputs, ctx=ctx)
    y = mx.nd.array(labels.astype(np.float32), ctx=ctx)
    losses, compiles, compile_s, step_s = [], [], [], []
    for _ in range(steps):
        n0, s0, t0 = meter.count, meter.seconds, time.perf_counter()
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(batch)
        # the host read ends the step: everything queued has run
        losses.append(float(loss.mean().asscalar()))
        for p in net.collect_params().values():
            p.data().wait_to_read()
        step_s.append(round(time.perf_counter() - t0, 3))
        compiles.append(meter.count - n0)
        compile_s.append(round(meter.seconds - s0, 3))
    want = mx.context.ctx_from_jax_device(jax.devices()[0])
    stray = sorted(p.name for p in net.collect_params().values()
                   if p.data().context != want)
    check = Checks()
    check(all(np.isfinite(losses)), "non-finite loss")
    check(losses[-1] < losses[0], "loss did not fall on the fixed batch")
    check(not stray, "parameters off %s: %s" % (want, stray[:5]))
    check(not any(compiles[2:]), "compilations after step 2")
    report = {"model": model, "batch": batch, "hw": hw, "steps": steps,
              "losses": [round(v, 4) for v in losses],
              "step_seconds": step_s,
              "compiles_per_step": compiles,
              "compile_seconds_step_1_2": compile_s[:2],
              "param_context": str(want),
              "params": len(net.collect_params().keys()),
              "failed": check}
    return report, (net, inputs, labels)


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

_HLO_INT8_CONV = re.compile(r"= s32\[[^\]]*\][^\n]* convolution\(")


def phase_serve(trained, rows=8, expect_native=True):
    """Export the trained net, register it with fp32/bf16/int8
    variants, send the first ``rows`` training inputs as single-sample
    requests per variant and compare with the direct hybridized fp32
    forward. The next ``rows`` inputs calibrate the int8 variant."""
    import mxnet_tpu as mx

    net, inputs, labels = trained
    x, calib = inputs[:rows], inputs[rows:2 * rows]
    hw = x.shape[-1]
    ref = net(mx.nd.array(x, ctx=mx.tpu())).asnumpy()
    check = Checks()
    check(np.isfinite(ref).all(), "non-finite reference logits")
    report = {"rows": rows, "hw": hw}
    with tempfile.TemporaryDirectory() as tmp, mx.serving.Gateway() as gw:
        prefix = os.path.join(tmp, "net")
        net.export(prefix)
        model = gw.register_checkpoint(
            "net", prefix, 0, {"data": (3, hw, hw)},
            variants=("fp32", "bf16", "int8"), calib_data=calib,
            buckets=(rows,), max_batch=rows)
        report["warmup_seconds"] = round(model.warmup_seconds, 3)
        got = {}
        for variant in ("fp32", "bf16", "int8"):
            reqs = [gw.submit("net", x[i], variant=variant)
                    for i in range(rows)]
            got[variant] = np.concatenate(
                [r.result(120.0)[0] for r in reqs], axis=0)
            check(got[variant].shape == ref.shape
                  and np.isfinite(got[variant]).all(),
                  "%s output not finite %r" % (variant, ref.shape))
        # the blocking form of the same call, one request of all rows
        whole = gw.infer("net", x, variant="fp32")[0]
        vs = model.replicas[0].variant_set
        fn, pvals = vs._fns["int8"]
        text = fn.lower(pvals, {"data": x}).compile().as_text()
    for variant, out in got.items():
        report[variant + "_rel_err"] = rel_err(out, ref)
    report["infer_fp32_rel_err"] = rel_err(whole, ref)
    agree = int(np.sum(got["int8"].argmax(1) == ref.argmax(1)))
    report["int8_top1_agree"] = "%d/%d" % (agree, rows)
    report["fp32_top1_is_trained_label"] = "%d/%d" % (
        int(np.sum(ref.argmax(1) == labels[:rows])), rows)
    report["int8_lowering"] = vs.int8_lowering
    report["int8_convolutions_in_hlo"] = len(_HLO_INT8_CONV.findall(text))
    report["int8_epilogue_kernel_in_hlo"] = "tpu_custom_call" in text
    check(max(report["fp32_rel_err"], report["infer_fp32_rel_err"])
          <= FP32_TOL, "fp32 beyond %g" % FP32_TOL)
    check(report["bf16_rel_err"] <= BF16_TOL, "bf16 beyond %g" % BF16_TOL)
    check(report["int8_rel_err"] <= INT8_TOL, "int8 beyond %g" % INT8_TOL)
    check(agree == rows, "int8 top-1 differs from fp32's")
    check(vs.int8_lowering == ("native" if expect_native else "dequant"),
          "int8_lowering resolved to %r" % vs.int8_lowering)
    report["failed"] = check
    return report


# ---------------------------------------------------------------------------
# phase: decode
# ---------------------------------------------------------------------------

def _greedy_matches(check, decoder, prompt, got, ref):
    """0 when ``got`` is the reference's tokens. Otherwise every token
    from the first difference on must be a near-tie in the reference's
    own logits for the same prefix; returns the largest gap seen."""
    if got == ref:
        return 0.0
    first = next((i for i, (a, b) in enumerate(zip(got, ref)) if a != b),
                 min(len(got), len(ref)))
    worst = 0.0
    for t in range(first, len(got)):
        toks = np.asarray([list(prompt) + got[:t]], np.int32)
        logits = decoder.full_logits(toks).asnumpy()[0, -1]
        gap = float((logits.max() - logits[got[t]])
                    / np.max(np.abs(logits)))
        worst = max(worst, gap)
        check(gap <= TIE_TOL,
              "token %d of prompt len %d is %d, reference logits prefer "
              "%d by %g > %g" % (t, len(prompt), got[t],
                                 int(logits.argmax()), gap, TIE_TOL))
    return worst


def phase_decode(vocab=32000, d_model=2048, heads=16, layers=4,
                 prompt_lens=(5, 12, 23, 40), new_tokens=32,
                 max_prompt_tokens=64, seed=0, expect_kernel=True):
    """Four prompts of different lengths generated together through the
    gateway's decode lane; tokens must equal the unpaged reference."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.serving import GenerativeDecoder, reference_generate

    seed_all(seed + 2)
    rng = np.random.default_rng(seed + 2)
    prompts = [rng.integers(1, vocab, n).tolist() for n in prompt_lens]
    # both sides at full f32 matmul precision, so a bf16-pass tie cannot
    # flip a greedy token. The lane runs on its own thread: the setting
    # has to be the process's, not a thread-local context
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        decoder = GenerativeDecoder(
            vocab, d_model=d_model, num_layers=layers, num_heads=heads,
            max_prompt_tokens=max_prompt_tokens)
        with mx.serving.Gateway() as gw:
            gen = gw.register_generator(
                "lm", decoder, max_new_tokens=new_tokens,
                max_decode_batch=len(prompts))
            reqs = [gw.submit_generate("lm", p, max_new_tokens=new_tokens)
                    for p in prompts]
            got = [[int(t) for t in r.result(600.0)] for r in reqs]
            lane = gen.lanes[0]
            b = len(prompts)
            text = lane.steps._decode.lower(
                lane.steps.params, lane.pool.k, lane.pool.v,
                np.zeros(b, np.int32), np.zeros(b, np.int32),
                np.zeros((b, gen.table_width), np.int32)
            ).compile().as_text()
            report = {"d_model": d_model, "heads": heads,
                      "head_dim": d_model // heads, "layers": layers,
                      "vocab": vocab, "prompt_lens": list(prompt_lens),
                      "new_tokens": new_tokens,
                      "executables": gen.executables,
                      "warmup_seconds": round(gen.warmup_seconds, 3)}
        # one compile per prompt length instead of one per op
        decoder.block.hybridize()
        check = Checks()
        worst = 0.0
        for p, g in zip(prompts, got):
            ok = len(g) == new_tokens and all(0 <= t < vocab for t in g)
            check(ok, "bad completion for prompt len %d: %r" % (len(p), g))
            if ok:
                ref = reference_generate(decoder, p, new_tokens)
                worst = max(worst,
                            _greedy_matches(check, decoder, p, g, ref))
    finally:
        jax.config.update("jax_default_matmul_precision", before)
    report["tokens_equal_reference"] = worst == 0.0
    report["worst_tie_gap"] = worst
    report["paged_kernel_in_hlo"] = "tpu_custom_call" in text
    report["donated_cache"] = jax.default_backend() != "cpu"
    check(report["paged_kernel_in_hlo"] == expect_kernel,
          "tpu_custom_call in the decode step is %r"
          % report["paged_kernel_in_hlo"])
    report["failed"] = check
    return report


# ---------------------------------------------------------------------------
# phase: data parallel (--chips 4)
# ---------------------------------------------------------------------------

def _module_losses(sym, contexts, x, y, params, steps, lr):
    """``steps`` Module SGD steps on one fixed global batch; returns
    (losses, module)."""
    import mxnet_tpu as mx

    batch = x.shape[0]
    mod = mx.mod.Module(sym, context=contexts)
    mod.bind(data_shapes=[("data", x.shape)],
             label_shapes=[("softmax_label", (batch,))])
    mod.init_params(initializer=mx.init.Zero())
    mod.set_params(*params)
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": lr, "momentum": 0.9})
    data = mx.io.DataBatch(data=[mx.nd.array(x)], label=[mx.nd.array(y)])
    losses = []
    for _ in range(steps):
        mod.forward(data, is_train=True)
        prob = mod.get_outputs()[0].asnumpy()
        mod.backward()
        mod.update()
        losses.append(float(-np.mean(np.log(
            prob[np.arange(batch), y.astype(np.int64)] + 1e-30))))
    return losses, mod


def phase_data_parallel(chips=4, model="resnet50_v1", batch=128, hw=224,
                        classes=1000, steps=3, lr=0.01, seed=0):
    """The same Module steps, global batch and seed on ``chips``
    contexts and on one."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon

    seed_all(seed)
    rng = np.random.default_rng(seed)
    net = gluon.model_zoo.vision.get_model(model, classes=classes)
    net.initialize(mx.init.Xavier())
    net(mx.nd.zeros((1, 3, hw, hw)))      # resolves the deferred shapes
    sym = mx.sym.SoftmaxOutput(net(mx.sym.var("data")), name="softmax")
    aux_names = set(sym.list_auxiliary_states())
    arg_p, aux_p = {}, {}
    for name, p in net.collect_params().items():
        (aux_p if name in aux_names else arg_p)[name] = p.data()
    x = rng.standard_normal((batch, 3, hw, hw), dtype=np.float32)
    y = rng.integers(0, classes, batch).astype(np.float32)
    many, mod = _module_losses(
        sym, [mx.tpu(i) for i in range(chips)], x, y, (arg_p, aux_p),
        steps, lr)
    one, _ = _module_losses(sym, mx.tpu(0), x, y, (arg_p, aux_p),
                            steps, lr)
    ex = mod._exec
    arg_vals, aux_vals, key = ex._last_state
    cotangents = [np.ones(o.shape, np.float32) for o in ex.outputs]
    text = ex._vjp.lower(arg_vals, aux_vals, key,
                         cotangents).compile().as_text()
    weight = next(v for n, v in sorted(ex.arg_dict.items())
                  if n.endswith("weight"))
    report = {"model": model, "chips": chips, "global_batch": batch,
              "hw": hw, "steps": steps,
              "losses_%d_contexts" % chips: [round(v, 5) for v in many],
              "losses_1_context": [round(v, 5) for v in one],
              "output_devices": len(ex.outputs[0]._data.sharding.device_set),
              "param_devices": len(weight._data.sharding.device_set),
              "data_shard_rows": arg_vals["data"].sharding.shard_shape(
                  arg_vals["data"].shape)[0],
              "all_reduce_in_hlo": "all-reduce" in text}
    check = Checks()
    check(all(np.isfinite(many + one)), "non-finite loss")
    check(np.allclose(many, one, rtol=DP_LOSS_RTOL, atol=0),
          "losses differ beyond rtol %g" % DP_LOSS_RTOL)
    check(report["output_devices"] == chips
          and report["param_devices"] == chips,
          "outputs or params not on %d devices" % chips)
    check(report["data_shard_rows"] == batch // chips,
          "batch not split %d ways" % chips)
    check(report["all_reduce_in_hlo"], "no all-reduce in the step")
    report["failed"] = check
    return report


# ---------------------------------------------------------------------------

def run_phase(name, meter, fn, *args, **kwargs):
    """Run one phase, print its line, and raise if a check failed. An
    exception inside the phase propagates: nothing is caught and passed
    over."""
    n0, s0, t0 = meter.count, meter.seconds, time.perf_counter()
    out = fn(*args, **kwargs)
    report, rest = out if isinstance(out, tuple) else (out, None)
    line = {"phase": name, "ok": not report["failed"],
            "seconds": round(time.perf_counter() - t0, 3),
            "compiles": meter.count - n0,
            "compile_seconds": round(meter.seconds - s0, 3)}
    line.update(report)
    print(json.dumps(line), flush=True)
    if report["failed"]:
        raise AssertionError("chip_smoke: %s: %s"
                             % (name, "; ".join(report["failed"])))
    return rest


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the data-parallel pair")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, inputs, calibration data and prompts")
    args = ap.parse_args(argv)

    devices = device_gate(args.chips)
    import jax

    import mxnet_tpu as mx

    cache_dir = mx.util.enable_compile_cache()
    meter = CompileMeter()
    jax.monitoring.register_event_duration_secs_listener(meter)
    print(json.dumps({"phase": "gate", "ok": True,
                      "devices": [str(d) for d in devices],
                      "compile_cache": cache_dir}), flush=True)
    if args.chips == 4:
        run_phase("data_parallel", meter, phase_data_parallel,
                  chips=4, seed=args.seed)
    else:
        trained = run_phase("train", meter, phase_train, meter,
                            seed=args.seed)
        run_phase("serve", meter, phase_serve, trained)
        run_phase("decode", meter, phase_decode, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
