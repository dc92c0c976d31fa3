"""Fast-gradient-sign adversarial examples (ref: example/adversary/
adversary_generation.ipynb — train a small net, then perturb inputs
along sign(dL/dx) and watch accuracy collapse).

Exercises input-gradient autograd: `x.attach_grad()` marks a *data*
array as differentiable and `autograd.grad`/`backward` returns dL/dx,
the less-traveled half of the tape (weights are the usual half).

Data is synthetic two-class "striped vs. blobbed" 16x16 images that a
tiny CNN separates almost perfectly, so the FGSM accuracy drop is the
observable. CI asserts clean accuracy > 0.9 and adversarial accuracy
at eps=0.2 at least 0.25 lower.

    python examples/adversary/fgsm.py --steps 150 --eps 0.2
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

IMG = 16


def build_net():
    net = nn.HybridSequential(prefix="cls_")
    with net.name_scope():
        net.add(nn.Conv2D(8, 3, 1, 1, in_channels=1),
                nn.Activation("relu"),
                nn.MaxPool2D(2),
                nn.Conv2D(16, 3, 1, 1, in_channels=8),
                nn.Activation("relu"),
                nn.MaxPool2D(2),
                nn.Flatten(),
                nn.Dense(2, in_units=16 * 4 * 4))
    return net


def make_batch(rng, batch):
    """Class 0: vertical stripes; class 1: one Gaussian blob."""
    xs = np.zeros((batch, 1, IMG, IMG), np.float32)
    ys = rng.integers(0, 2, batch).astype(np.float32)
    yy, xx = np.mgrid[0:IMG, 0:IMG]
    for i in range(batch):
        if ys[i] < 0.5:
            phase = rng.uniform(0, np.pi)
            xs[i, 0] = 0.5 + 0.5 * np.sin(xx * rng.uniform(0.8, 1.6) + phase)
        else:
            cy, cx = rng.uniform(4, 12, 2)
            s = rng.uniform(1.5, 3.0)
            xs[i, 0] = np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s * s))
        xs[i, 0] += rng.normal(0, 0.05, (IMG, IMG))
    return xs, ys


def accuracy(net, xs, ys):
    out = net(nd.array(xs))
    pred = out.asnumpy().argmax(axis=1)
    return float((pred == ys).mean())


def fgsm_perturb(net, loss_fn, xs, ys, eps):
    """x_adv = x + eps * sign(dL/dx)."""
    x = nd.array(xs)
    x.attach_grad()
    y = nd.array(ys)
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    return (x + eps * nd.sign(x.grad)).asnumpy()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--lr", type=float, default=5e-3)
    ap.add_argument("--eps", type=float, default=0.2)
    args = ap.parse_args()

    t0 = time.time()
    # initializers draw from numpy's global stream: seed it, the
    # framework's key chain and the data draw alike
    seed = int(os.environ.get("MXNET_TEST_SEED", "0"))
    np.random.seed(seed)
    mx.random.seed(seed)
    rng = np.random.default_rng(seed)
    net = build_net()
    net.initialize(mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})

    for step in range(args.steps):
        xs, ys = make_batch(rng, args.batch)
        x, y = nd.array(xs), nd.array(ys)
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(args.batch)
        if (step + 1) % 50 == 0:
            print("step %d loss %.4f" % (step + 1, float(loss.mean().asnumpy())))

    xs, ys = make_batch(rng, 256)
    clean = accuracy(net, xs, ys)
    adv_xs = fgsm_perturb(net, loss_fn, xs, ys, args.eps)
    adv = accuracy(net, adv_xs, ys)
    print("elapsed %.1fs" % (time.time() - t0))
    print("clean accuracy %.4f" % clean)
    print("adversarial accuracy %.4f" % adv)


if __name__ == "__main__":
    main()
