"""Channel-last (NHWC) layout machinery + optimize_for fusion
(VERDICT r3 #2): the round's perf lever must be covered on the CPU
mesh."""
import warnings

import numpy as np
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.block import infer_shapes
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.ndarray.ndarray import NDArray
from mxnet_tpu.ops import registry


def _nd(a):
    return NDArray(jnp.asarray(a))


def test_conv_pool_ops_channel_last_match_nc_first():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
    w = rng.standard_normal((4, 3, 3, 3)).astype(np.float32)
    b = rng.standard_normal((4,)).astype(np.float32)
    conv = registry.get("Convolution")
    ref = conv(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b),
               kernel=(3, 3), stride=(2, 2), pad=(1, 1), num_filter=4)
    got = conv(jnp.transpose(jnp.asarray(x), (0, 2, 3, 1)), jnp.asarray(w),
               jnp.asarray(b), kernel=(3, 3), stride=(2, 2), pad=(1, 1),
               num_filter=4, layout="NHWC")
    np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                               rtol=1e-4, atol=1e-5)

    pool = registry.get("Pooling")
    for kwargs in ({"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
                   {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
                    "pool_type": "avg"},
                   {"global_pool": True, "kernel": (1, 1),
                    "pool_type": "avg"},
                   {"kernel": (3, 3), "stride": (2, 2),
                    "pooling_convention": "full", "pool_type": "max"}):
        ref = pool(jnp.asarray(x), **kwargs)
        got = pool(jnp.transpose(jnp.asarray(x), (0, 2, 3, 1)),
                   layout="NHWC", **kwargs)
        np.testing.assert_allclose(np.transpose(got, (0, 3, 1, 2)), ref,
                                   rtol=1e-4, atol=1e-5,
                                   err_msg=str(kwargs))


def test_layout_scope_builds_channel_last_layers():
    with nn.layout_scope("NHWC"):
        conv = nn.Conv2D(8, 3, padding=1)
        pool = nn.MaxPool2D(2, 2)
        bn = nn.BatchNorm()
    assert conv._kwargs["layout"] == "NHWC"
    assert pool._kwargs["layout"] == "NHWC"
    assert bn._axis == -1
    # explicit layouts win; outside the scope defaults stay NC-first
    with nn.layout_scope("NHWC"):
        explicit = nn.Conv2D(8, 3, layout="NHWC")
    assert explicit._kwargs["layout"] == "NHWC"
    plain = nn.Conv2D(8, 3)
    assert plain._kwargs["layout"] == "NCHW"
    assert nn.BatchNorm()._axis == 1


def _param_key(k):
    import re
    return re.sub(r"^[A-Za-z0-9]+\d+_", "", k)


def _clone_params(src_net, dst_net):
    vals = {_param_key(k): v.data().asnumpy()
            for k, v in src_net.collect_params().items()}
    for k, p in dst_net.collect_params().items():
        p.set_data(_nd(vals[_param_key(k)]))


def test_resnet_nhwc_matches_nchw_inference_and_training():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((2, 3, 32, 32)).astype(np.float32)
    nets = {}
    for layout in ("NCHW", "NHWC"):
        net = vision.get_resnet(1, 18, layout=layout)
        net.initialize()
        infer_shapes(net, (2, 3, 32, 32))
        nets[layout] = net
    _clone_params(nets["NCHW"], nets["NHWC"])
    outs = {}
    for layout, net in nets.items():
        net.hybridize()
        outs[layout] = net(_nd(x)).asnumpy()
    np.testing.assert_allclose(outs["NHWC"], outs["NCHW"], rtol=1e-4,
                               atol=1e-4)
    # training mode: batch-stat BN reduces over the right axes
    from mxnet_tpu import autograd
    for layout, net in nets.items():
        with autograd.train_mode():
            outs[layout] = net(_nd(x)).asnumpy()
    np.testing.assert_allclose(outs["NHWC"], outs["NCHW"], rtol=1e-3,
                               atol=1e-3)


def test_optimize_for_fuses_and_matches_direct_trace():
    """optimize_for('XLA') partitions conv+BN(+relu) on the hybridize
    path; the partition must actually fire (no fallback warning) and
    match the unfused output, in both layouts."""
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 3, 32, 32)).astype(np.float32)
    for layout in ("NCHW", "NHWC"):
        net = vision.get_resnet(1, 18, layout=layout)
        net.initialize()
        infer_shapes(net, (2, 3, 32, 32))
        net.hybridize()
        xin = _nd(x)
        base = net(xin).asnumpy()
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            net.optimize_for(xin)
            fused = net(xin).asnumpy()
        np.testing.assert_allclose(fused, base, rtol=1e-3, atol=1e-4,
                                   err_msg=layout)


def test_sg_conv_shape_infer_channel_last():
    """_sg_conv_shapes back-infers weight/bias/BN/sum shapes for
    channel-last fused nodes."""
    from mxnet_tpu.subgraph.xla_fuse import _sg_conv_shapes
    attrs = {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
             "num_filter": 8, "layout": "NHWC", "with_bn": True,
             "with_sum": True, "no_bias": True}
    shapes = _sg_conv_shapes([(2, 16, 16, 4)], attrs)
    assert shapes[1] == (8, 4, 3, 3)          # weight stays OIHW
    assert shapes[2:6] == [(8,)] * 4          # BN vectors
    assert shapes[6] == (2, 8, 8, 8)          # sum input NHWC


def test_mobilenet_nhwc_matches_nchw():
    """BASELINE config 2's second model family builds channel-last too."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1, 3, 32, 32)).astype(np.float32)
    for maker in (vision.mobilenet0_25, vision.mobilenet_v2_0_25):
        outs = {}
        nets = {}
        for layout in ("NCHW", "NHWC"):
            net = maker(layout=layout)
            net.initialize()
            infer_shapes(net, (1, 3, 32, 32))
            nets[layout] = net
        _clone_params(nets["NCHW"], nets["NHWC"])
        for layout, net in nets.items():
            net.hybridize()
            outs[layout] = net(_nd(x)).asnumpy()
        np.testing.assert_allclose(outs["NHWC"], outs["NCHW"], rtol=1e-4,
                                   atol=1e-4, err_msg=maker.__name__)


def test_nhwc_gradients_match_nchw():
    """The training path differentiates through NHWC conv/pool/BN;
    gradients must match the NCHW lowering parameter-for-parameter."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.gluon.block import _flatten

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((2, 3, 16, 16)), jnp.float32)
    grads = {}
    nets = {}
    for layout in ("NCHW", "NHWC"):
        net = vision.get_resnet(1, 18, classes=4, layout=layout)
        net.initialize()
        infer_shapes(net, (2, 3, 16, 16))
        nets[layout] = net
    _clone_params(nets["NCHW"], nets["NHWC"])
    for layout, net in nets.items():
        net.hybridize()
        plist = sorted(net.collect_params().items())
        pvals = tuple(p.data()._data for _, p in plist)
        _, in_spec = _flatten([_nd(np.zeros((2, 3, 16, 16),
                                            np.float32))])
        jfn, _o, _a = net._build_cached(plist, in_spec, training=True)
        k0 = jax.random.PRNGKey(0)

        def loss(pv):
            outs, _aux = jfn(pv, k0, x)
            return jnp.sum(outs[0] ** 2)

        g = jax.grad(loss)(pvals)
        grads[layout] = {_param_key(n): np.asarray(gv)
                         for (n, _p), gv in zip(plist, g)}
    for name in grads["NCHW"]:
        np.testing.assert_allclose(
            grads["NHWC"][name], grads["NCHW"][name], rtol=2e-2,
            atol=2e-3, err_msg=name)
