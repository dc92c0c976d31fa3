"""Inference throughput sweep over the Gluon model zoo
(ref: example/image-classification/benchmark_score.py — same
methodology: time `score` over batch sizes, print images/sec).

    python benchmark_score.py --networks resnet50_v1,mobilenet_v2 \
        --batch-sizes 1,8,32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.block import _flatten, infer_shapes
from mxnet_tpu.gluon.model_zoo import vision


def score(network, batch, num_iters=20, warmup=3):
    net = getattr(vision, network)()
    net.initialize()
    infer_shapes(net, (batch, 3, 224, 224))
    net.hybridize()
    plist = sorted(net.collect_params().items())
    pvals = jax.device_put(tuple(p.data()._data for _, p in plist))
    x = mx.nd.zeros((batch, 3, 224, 224))
    _, in_spec = _flatten([x])
    jfn, _o, _a = net._build_cached(plist, in_spec, training=False)
    key = jax.random.PRNGKey(0)
    fwd = jax.jit(lambda pv, d: jfn(pv, key, d)[0][0])
    data = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, 3, 224, 224), dtype=np.float32))
    reduce_fn = jax.jit(lambda t: jnp.sum(t.astype(jnp.float32)))
    for _ in range(warmup):
        float(reduce_fn(fwd(pvals, data)))
    t0 = time.perf_counter()
    out = None
    for _ in range(num_iters):
        out = fwd(pvals, data)
    float(reduce_fn(out))  # device fence: one sync bounds the queued chain
    dt = time.perf_counter() - t0
    return batch * num_iters / dt


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--networks", type=str,
                   default="resnet18_v1,resnet50_v1,mobilenet_v2_1_0")
    p.add_argument("--batch-sizes", type=str, default="1,32")
    p.add_argument("--iters", type=int, default=20)
    args = p.parse_args()
    for net in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            ips = score(net, bs, num_iters=args.iters)
            print("network: %s, batch: %d, image/sec: %.2f"
                  % (net, bs, ips))
