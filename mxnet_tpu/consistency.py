"""Accelerator-backend consistency sweep: TPU vs CPU numerics.

The reference replays its *entire* CPU unit suite on the accelerator
(ref: tests/python/gpu/test_operator_gpu.py:1 imports the whole unittest
dir) and cross-checks per-op outputs between contexts with
``check_consistency`` (ref: python/mxnet/test_utils.py:1261). That full
replay costs ~40 min per backend; the TPU-side equivalent here is a
compact table-driven sweep — ~50 representative ops spanning every
kernel family (elementwise, reduction, matmul/MXU, conv, norm, indexing,
sorting, linalg, sequence, loss) plus one model-zoo forward — run on the
real chip and compared against CPU jax within dtype-scaled tolerance.

``run_sweep()`` revalidates numerics on the chip (bf16 MXU matmul
semantics, conv algorithm differences, int8 saturation) and returns the
pass/fail tally.
"""
from __future__ import annotations

import numpy as np

__all__ = ["OP_TABLE", "run_sweep", "model_forward_consistency"]


def _r(rng, *shape):
    return rng.standard_normal(shape).astype(np.float32)


def _pos(rng, *shape):
    return np.abs(_r(rng, *shape)) + 0.1


def _build_table():
    """Each row: (name, fn(nd, *inputs) -> NDArray, inputs_builder(rng),
    {tol overrides}). Inputs are host numpy; the harness places them on
    each device context and diffs the outputs."""
    t = []

    def add(name, fn, builder, **tol):
        t.append((name, fn, builder, tol))

    # elementwise unary (VPU lanes)
    add("exp", lambda nd, a: nd.exp(a), lambda r: [_r(r, 32, 33)])
    add("log", lambda nd, a: nd.log(a), lambda r: [_pos(r, 32, 33)])
    add("sqrt", lambda nd, a: nd.sqrt(a), lambda r: [_pos(r, 32, 33)])
    add("rsqrt", lambda nd, a: nd.rsqrt(a), lambda r: [_pos(r, 32, 33)])
    add("sigmoid", lambda nd, a: nd.sigmoid(a), lambda r: [_r(r, 32, 33)])
    add("tanh", lambda nd, a: nd.tanh(a), lambda r: [_r(r, 32, 33)])
    add("erf", lambda nd, a: nd.erf(a), lambda r: [_r(r, 32, 33)])
    add("relu", lambda nd, a: nd.relu(a), lambda r: [_r(r, 32, 33)])
    add("gamma", lambda nd, a: nd.gamma(a), lambda r: [_pos(r, 16, 17)],
        rtol=1e-4)
    add("expm1", lambda nd, a: nd.expm1(a), lambda r: [_r(r, 32, 33)])
    add("sin", lambda nd, a: nd.sin(a), lambda r: [_r(r, 32, 33)])
    add("arctan", lambda nd, a: nd.arctan(a), lambda r: [_r(r, 32, 33)])

    # elementwise binary / broadcast
    add("broadcast_add", lambda nd, a, b: nd.broadcast_add(a, b),
        lambda r: [_r(r, 16, 1, 8), _r(r, 1, 5, 8)])
    add("broadcast_mul", lambda nd, a, b: nd.broadcast_mul(a, b),
        lambda r: [_r(r, 16, 1, 8), _r(r, 1, 5, 8)])
    add("broadcast_div", lambda nd, a, b: nd.broadcast_div(a, b),
        lambda r: [_r(r, 16, 8), _pos(r, 16, 8)])
    add("broadcast_power", lambda nd, a, b: nd.broadcast_power(a, b),
        lambda r: [_pos(r, 16, 8), _r(r, 16, 8)], rtol=1e-4)
    add("broadcast_maximum", lambda nd, a, b: nd.broadcast_maximum(a, b),
        lambda r: [_r(r, 16, 8), _r(r, 16, 8)])
    add("where", lambda nd, c, a, b: nd.where(c, a, b),
        lambda r: [(_r(r, 16, 8) > 0).astype(np.float32),
                   _r(r, 16, 8), _r(r, 16, 8)])
    add("clip", lambda nd, a: nd.clip(a, -0.5, 0.5),
        lambda r: [_r(r, 32, 33)])
    add("smooth_l1", lambda nd, a: nd.smooth_l1(a, scalar=1.0),
        lambda r: [_r(r, 32, 33)])

    # reductions
    add("sum_axis", lambda nd, a: nd.sum(a, axis=1),
        lambda r: [_r(r, 64, 65)], rtol=1e-4, atol=1e-4)
    add("mean", lambda nd, a: nd.mean(a, axis=(0, 2)),
        lambda r: [_r(r, 16, 17, 18)], rtol=1e-4, atol=1e-5)
    add("max_axis", lambda nd, a: nd.max(a, axis=0),
        lambda r: [_r(r, 64, 65)])
    add("prod", lambda nd, a: nd.prod(a, axis=1),
        lambda r: [1.0 + 0.01 * _r(r, 32, 16)], rtol=1e-4)
    add("norm", lambda nd, a: nd.norm(a, ord=2, axis=1),
        lambda r: [_r(r, 32, 64)], rtol=1e-4)
    add("argmax", lambda nd, a: nd.argmax(a, axis=1),
        lambda r: [_r(r, 32, 65)])
    add("nansum", lambda nd, a: nd.nansum(a, axis=0),
        lambda r: [_r(r, 32, 16)], rtol=1e-4, atol=1e-5)

    # matmul family — the MXU path, the one most likely to diverge
    add("dot", lambda nd, a, b: nd.dot(a, b),
        lambda r: [_r(r, 128, 256), _r(r, 256, 128)],
        rtol=2e-4, atol=2e-3)
    add("dot_transpose", lambda nd, a, b: nd.dot(a, b, transpose_b=True),
        lambda r: [_r(r, 64, 256), _r(r, 64, 256)],
        rtol=2e-4, atol=2e-3)
    add("batch_dot", lambda nd, a, b: nd.batch_dot(a, b),
        lambda r: [_r(r, 8, 64, 96), _r(r, 8, 96, 64)],
        rtol=2e-4, atol=2e-3)
    add("FullyConnected",
        lambda nd, x, w, b: nd.FullyConnected(x, w, b, num_hidden=64),
        lambda r: [_r(r, 32, 128), _r(r, 64, 128), _r(r, 64)],
        rtol=2e-4, atol=2e-3)
    add("linalg_gemm2", lambda nd, a, b: nd.linalg_gemm2(a, b),
        lambda r: [_r(r, 64, 64), _r(r, 64, 64)], rtol=2e-4, atol=2e-3)

    # convolution / pooling — algorithm choice differs per backend
    add("Convolution",
        lambda nd, x, w, b: nd.Convolution(
            x, w, b, kernel=(3, 3), num_filter=16, pad=(1, 1)),
        lambda r: [_r(r, 4, 8, 14, 14), _r(r, 16, 8, 3, 3), _r(r, 16)],
        rtol=5e-4, atol=5e-3)
    add("Convolution_stride2",
        lambda nd, x, w, b: nd.Convolution(
            x, w, b, kernel=(3, 3), num_filter=8, stride=(2, 2)),
        lambda r: [_r(r, 2, 4, 15, 15), _r(r, 8, 4, 3, 3), _r(r, 8)],
        rtol=5e-4, atol=5e-3)
    add("Deconvolution",
        lambda nd, x, w: nd.Deconvolution(
            x, w, kernel=(2, 2), num_filter=4, stride=(2, 2),
            no_bias=True),
        lambda r: [_r(r, 2, 8, 7, 7), _r(r, 8, 4, 2, 2)],
        rtol=5e-4, atol=5e-3)
    add("Pooling_max",
        lambda nd, x: nd.Pooling(x, kernel=(2, 2), pool_type="max",
                                 stride=(2, 2)),
        lambda r: [_r(r, 4, 8, 14, 14)])
    add("Pooling_avg",
        lambda nd, x: nd.Pooling(x, kernel=(2, 2), pool_type="avg",
                                 stride=(2, 2)),
        lambda r: [_r(r, 4, 8, 14, 14)], rtol=1e-4)

    # normalization / activation blocks
    add("BatchNorm",
        lambda nd, x, g, b, m, v: nd.BatchNorm(
            x, g, b, m, v, fix_gamma=False, use_global_stats=True),
        lambda r: [_r(r, 8, 16, 7, 7), _pos(r, 16), _r(r, 16),
                   _r(r, 16), _pos(r, 16)], rtol=1e-4, atol=1e-4)
    add("LayerNorm",
        lambda nd, x, g, b: nd.LayerNorm(x, g, b),
        lambda r: [_r(r, 16, 64), _pos(r, 64), _r(r, 64)],
        rtol=1e-4, atol=1e-4)
    add("L2Normalization", lambda nd, x: nd.L2Normalization(x),
        lambda r: [_r(r, 16, 64)], rtol=1e-4)
    add("LRN", lambda nd, x: nd.LRN(x, nsize=5),
        lambda r: [_r(r, 4, 8, 7, 7)], rtol=1e-4)
    add("softmax", lambda nd, a: nd.softmax(a, axis=-1),
        lambda r: [_r(r, 32, 65)], rtol=1e-4, atol=1e-5)
    add("log_softmax", lambda nd, a: nd.log_softmax(a, axis=-1),
        lambda r: [_r(r, 32, 65)], rtol=1e-4, atol=1e-4)
    add("LeakyReLU_elu",
        lambda nd, a: nd.LeakyReLU(a, act_type="elu", slope=0.3),
        lambda r: [_r(r, 32, 33)])

    # shape / indexing / gather-scatter
    add("transpose", lambda nd, a: nd.transpose(a, axes=(2, 0, 1)),
        lambda r: [_r(r, 8, 9, 10)])
    add("take", lambda nd, a, idx: nd.take(a, idx),
        lambda r: [_r(r, 50, 8),
                   r.integers(0, 50, (16,)).astype(np.float32)])
    add("gather_nd", lambda nd, a, idx: nd.gather_nd(a, idx),
        lambda r: [_r(r, 6, 7),
                   r.integers(0, 6, (2, 5)).astype(np.float32)])
    add("Embedding",
        lambda nd, idx, w: nd.Embedding(idx, w, input_dim=50,
                                        output_dim=16),
        lambda r: [r.integers(0, 50, (8, 4)).astype(np.float32),
                   _r(r, 50, 16)])
    add("one_hot", lambda nd, idx: nd.one_hot(idx, depth=10),
        lambda r: [r.integers(0, 10, (16,)).astype(np.float32)])
    add("slice", lambda nd, a: nd.slice(a, begin=(1, 2), end=(7, 9)),
        lambda r: [_r(r, 8, 10)])
    add("reverse", lambda nd, a: nd.reverse(a, axis=1),
        lambda r: [_r(r, 8, 10)])
    add("tile", lambda nd, a: nd.tile(a, reps=(2, 3)),
        lambda r: [_r(r, 4, 5)])
    add("space_to_depth", lambda nd, a: nd.space_to_depth(a, block_size=2),
        lambda r: [_r(r, 2, 4, 6, 6)])
    add("pick", lambda nd, a, idx: nd.pick(a, idx, axis=1),
        lambda r: [_r(r, 16, 10),
                   r.integers(0, 10, (16,)).astype(np.float32)])

    # sorting / topk
    add("sort", lambda nd, a: nd.sort(a, axis=1),
        lambda r: [_r(r, 16, 33)])
    add("topk", lambda nd, a: nd.topk(a, k=5, axis=1, ret_typ="value"),
        lambda r: [_r(r, 16, 33)])
    add("argsort", lambda nd, a: nd.argsort(a, axis=1),
        lambda r: [r.permutation(33 * 16).reshape(16, 33)
                   .astype(np.float32)])

    # linalg
    add("linalg_potrf", lambda nd, a: nd.linalg_potrf(a),
        lambda r: [_spd(r, 16)], rtol=1e-3, atol=1e-3)
    add("linalg_inverse", lambda nd, a: nd.linalg_inverse(a),
        lambda r: [_spd(r, 12)], rtol=1e-3, atol=1e-3)
    add("linalg_det", lambda nd, a: nd.linalg_det(a),
        lambda r: [_spd(r, 8)], rtol=1e-3)

    # sequence / loss ops
    add("SequenceMask",
        lambda nd, x, l: nd.SequenceMask(x, l, use_sequence_length=True),
        lambda r: [_r(r, 6, 4, 8),
                   np.array([2, 4, 6, 3], np.float32)])
    add("softmax_cross_entropy",
        lambda nd, x, l: nd.softmax_cross_entropy(x, l),
        lambda r: [_r(r, 16, 10),
                   r.integers(0, 10, (16,)).astype(np.float32)],
        rtol=1e-4, atol=1e-4)
    add("ctc_loss",
        lambda nd, x, l: nd.ctc_loss(x, l),
        lambda r: [_r(r, 10, 4, 6),
                   np.array([[1, 2, 0], [2, 3, 1], [1, 1, 0],
                             [4, 2, 3]], np.float32)],
        rtol=1e-3, atol=1e-3)
    return t


def _spd(rng, n):
    a = _r(rng, n, n)
    return (a @ a.T + n * np.eye(n)).astype(np.float32)


OP_TABLE = _build_table()

# dtype-scaled default tolerances, mirroring the reference's
# check_consistency per-dtype eps ladder
# (ref: python/mxnet/test_utils.py:1261 tol={np.dtype(np.float16): 1e-1,
# np.dtype(np.float32): 1e-3, ...})
_DEFAULT_TOL = {
    "float32": dict(rtol=1e-5, atol=1e-5),
    "bfloat16": dict(rtol=3e-2, atol=3e-2),
}


def _run_one(name, fn, builder, tol, dtype, seed=0):
    from . import nd as _nd
    from .context import cpu, tpu

    rng = np.random.default_rng(seed)
    inputs = builder(rng)
    base = dict(_DEFAULT_TOL[dtype])
    base.update(tol)
    outs = []
    for ctx in (cpu(), tpu()):
        arrs = []
        for x in inputs:
            a = _nd.array(x, ctx=ctx)
            if dtype != "float32" and not _is_index_input(x):
                a = a.astype(dtype)
            arrs.append(a)
        out = fn(_nd, *arrs)
        outs.append(np.asarray(out.astype("float32").asnumpy()))
    np.testing.assert_allclose(outs[0], outs[1], **base)
    return outs


def _is_index_input(x):
    # integer-valued index tensors must not be cast to bf16 (precision
    # loss would change the indices themselves)
    return np.allclose(x, np.round(x)) and np.all(np.abs(x) < 1e4)


def run_sweep(dtype="float32", ops=None, seed=0):
    """Run the table on cpu-vs-accelerator contexts; returns a summary
    dict {"total", "pass", "fail", "failures": [(name, err), ...],
    "rows": [{"name", "ok", "fingerprint"}, ...]}.

    Each passing row is stamped with the CPU-side output's drift
    fingerprint (``profiling.health.fingerprint_params``) — one
    vocabulary with the bit-identical-resume tests and the chaos
    suite's bounded-drift checks, so two chip windows (or two
    backends) can diff per-op numerics without re-running the peer.

    On a CPU-only host both contexts resolve to the same device and the
    sweep degenerates to a harness self-test (exactly how the reference's
    gpu suite behaves when run on a CPU-only build)."""
    from .profiling.health import fingerprint_params

    table = OP_TABLE if ops is None else [
        row for row in OP_TABLE if row[0] in ops]
    failures = []
    rows = []
    for name, fn, builder, tol in table:
        row = {"name": name, "ok": True, "fingerprint": None}
        try:
            outs = _run_one(name, fn, builder, tol, dtype, seed=seed)
            # fingerprint the REFERENCE (cpu-context) output: the
            # stable side a later chip row is compared against
            row["fingerprint"] = fingerprint_params({"out": outs[0]})
        except Exception as e:  # noqa: BLE001 — tally, don't abort sweep
            row["ok"] = False
            failures.append((name, str(e).splitlines()[0][:160]
                             if str(e) else repr(e)))
        rows.append(row)
    return {
        "total": len(table),
        "pass": len(table) - len(failures),
        "fail": len(failures),
        "failures": failures,
        "rows": rows,
    }


def model_forward_consistency(batch=4, rtol=2e-2, atol=2e-2):
    """One model-zoo forward (resnet18_v1) on cpu vs accelerator, fp32.

    The per-op table can miss composition effects (layout passes, fusion,
    accumulated bf16 rounding through 18 layers); the model-level check
    is the reference's test_gluon_model_zoo_gpu analogue
    (ref: tests/python/gpu/test_gluon_model_zoo_gpu.py:55)."""
    import jax

    from .context import cpu, tpu
    from .gluon.block import infer_shapes
    from .gluon.model_zoo import vision
    from .ndarray.ndarray import NDArray

    net = vision.resnet18_v1()
    net.initialize()
    infer_shapes(net, (batch, 3, 32, 32))
    x = np.random.default_rng(0).standard_normal(
        (batch, 3, 32, 32)).astype(np.float32)
    outs = []
    for ctx in (cpu(), tpu()):
        dev = ctx.jax_device
        xs = NDArray(jax.device_put(np.asarray(x), dev))
        with jax.default_device(dev):
            out = net(xs)
        outs.append(np.asarray(out.asnumpy()))
    np.testing.assert_allclose(outs[0], outs[1], rtol=rtol, atol=atol)
    return True
