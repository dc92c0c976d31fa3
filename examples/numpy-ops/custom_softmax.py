"""Softmax output implemented as a numpy CustomOp (ref:
example/numpy-ops/custom_softmax.py — the canonical "write your op in
the frontend language" demo).

The op computes softmax in `forward` and the fused softmax-cross-entropy
gradient (p - onehot(y)) in `backward`, both as plain numpy running on
the host via `jax.pure_callback` — the escape hatch that lets Python
code live inside an otherwise jitted TPU graph. A small MLP trains on
synthetic 2-class data through the custom head; CI asserts the loss
falls and final accuracy beats 0.9.

    python examples/numpy-ops/custom_softmax.py --steps 200
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


class Softmax(mx.operator.CustomOp):
    def forward(self, is_train, req, in_data, out_data, aux):
        x = in_data[0].asnumpy()
        x = x - x.max(axis=1, keepdims=True)
        e = np.exp(x)
        # NumPy straight into assign: the callback stays off the device
        self.assign(out_data[0], req[0], e / e.sum(axis=1, keepdims=True))

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        # fused softmax + CE gradient: p - onehot(label)
        p = out_data[0].asnumpy().copy()
        y = in_data[1].asnumpy().astype(np.int64)
        p[np.arange(p.shape[0]), y] -= 1.0
        self.assign(in_grad[0], req[0], p / p.shape[0])
        self.assign(in_grad[1], req[1], np.zeros(in_data[1].shape))


@mx.operator.register("softmax_loss")
class SoftmaxProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=False)

    def list_arguments(self):
        return ["data", "label"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return Softmax()


def make_batch(rng, batch, dim):
    ys = rng.integers(0, 2, batch)
    centers = np.where(ys[:, None] > 0, 1.0, -1.0)
    xs = centers + rng.normal(0, 0.8, (batch, dim))
    return xs.astype(np.float32), ys.astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args()

    t0 = time.time()
    rng = np.random.default_rng(0)
    net = nn.HybridSequential(prefix="mlp_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=args.dim),
                nn.Dense(2, in_units=16))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr})

    first_loss = None
    for step in range(args.steps):
        xs, ys = make_batch(rng, args.batch, args.dim)
        x, y = nd.array(xs), nd.array(ys)
        with autograd.record():
            logits = net(x)
            p = nd.Custom(logits, y, op_type="softmax_loss")
            # CE through the custom head; its backward supplies the
            # fused gradient so the recorded loss need not be exact
            loss = -nd.log(nd.pick(p, y) + 1e-8).mean()
        loss.backward()
        trainer.step(1)
        lv = float(loss.asnumpy())
        if first_loss is None:
            first_loss = lv
        if (step + 1) % 50 == 0:
            print("step %d loss %.4f" % (step + 1, lv))

    xs, ys = make_batch(rng, 512, args.dim)
    pred = net(nd.array(xs)).asnumpy().argmax(axis=1)
    acc = float((pred == ys).mean())
    print("elapsed %.1fs" % (time.time() - t0))
    print("first loss %.4f" % first_loss)
    print("final accuracy %.4f" % acc)


if __name__ == "__main__":
    main()
