"""CustomOp escape hatch + Monitor + visualization tests
(ref: tests/python/unittest/test_operator.py test_custom_op,
test_monitor-style flows, visualization print_summary).
"""
import io
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


@mx.operator.register("sigmoid_custom")
class SigmoidProp(mx.operator.CustomOpProp):
    def __init__(self):
        super().__init__(need_top_grad=True)

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]], []

    def create_operator(self, ctx, in_shapes, in_dtypes):
        class SigmoidOp(mx.operator.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):
                x = in_data[0].asnumpy()
                self.assign(out_data[0], req[0],
                            mx.nd.array(1.0 / (1.0 + np.exp(-x))))

            def backward(self, req, out_grad, in_data, out_data,
                         in_grad, aux):
                y = out_data[0].asnumpy()
                g = out_grad[0].asnumpy()
                self.assign(in_grad[0], req[0],
                            mx.nd.array(g * y * (1 - y)))
        return SigmoidOp()


def test_custom_op_forward():
    x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
    out = nd.Custom(nd.array(x), op_type="sigmoid_custom")
    np.testing.assert_allclose(out.asnumpy(), 1 / (1 + np.exp(-x)),
                               rtol=1e-6)


def test_custom_op_gradient():
    x = nd.array(np.array([[0.5, -1.0, 2.0]], np.float32))
    x.attach_grad()
    with autograd.record():
        y = nd.Custom(x, op_type="sigmoid_custom")
        loss = y.sum()
    loss.backward()
    s = 1 / (1 + np.exp(-x.asnumpy()))
    np.testing.assert_allclose(x.grad.asnumpy(), s * (1 - s), rtol=1e-5)


def test_custom_op_inside_jit():
    import jax

    def f(d):
        return nd.Custom(mx.NDArray(d), op_type="sigmoid_custom")._data

    x = np.array([0.0, 1.0], np.float32)
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), 1 / (1 + np.exp(-x)),
                               rtol=1e-6)


def test_monitor_collects_internal_stats():
    from mxnet_tpu import sym

    data = sym.var("data")
    fc = sym.FullyConnected(data, name="fc", num_hidden=4)
    out = sym.Activation(fc, name="relu", act_type="relu")
    ex = out.bind(args={"data": nd.ones((2, 3)),
                        "fc_weight": nd.ones((4, 3)),
                        "fc_bias": nd.zeros((4,))}, grad_req="null")
    mon = mx.Monitor(interval=1, pattern=".*")
    mon.install(ex)
    mon.tic()
    ex.forward()
    stats = mon.toc()
    names = [n for _s, n, _v in stats]
    assert any("fc_output" in n for n in names), names
    assert any("relu_output" in n for n in names), names
    # value check: fc output = 3 for all entries -> |x|.mean() == 3
    val = [v for _s, n, v in stats if "fc_output" in n][0]
    assert "3." in val


def test_print_summary(capsys):
    from mxnet_tpu import sym

    data = sym.var("data")
    fc1 = sym.FullyConnected(data, name="fc1", num_hidden=8)
    act = sym.Activation(fc1, name="act", act_type="relu")
    fc2 = sym.FullyConnected(act, name="fc2", num_hidden=2)
    total = mx.viz.print_summary(fc2, shape={"data": (1, 4)})
    out = capsys.readouterr().out
    assert "fc1" in out and "fc2" in out
    # fc1: 4*8 + 8 = 40; fc2: 8*2 + 2 = 18
    assert total == 58


def test_custom_op_callback_stays_on_numpy():
    """The arrays a CustomOp sees inside the host callback are backed by
    host NumPy memory and assign() keeps them so: a device array built
    there is a program started from inside the program that is waiting
    for the callback — on a small thread pool that never returns (the
    numpy-ops example used to hang its test for the harness's whole
    timeout)."""
    seen = []

    @mx.operator.register("scale_numpy_only")
    class ScaleProp(mx.operator.CustomOpProp):
        def list_arguments(self):
            return ["data"]

        def create_operator(self, ctx, in_shapes, in_dtypes):
            class Scale(mx.operator.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):
                    seen.append(("fwd", type(in_data[0]._data),
                                 type(out_data[0]._data)))
                    self.assign(out_data[0], req[0],
                                in_data[0].asnumpy() * 3.0)
                    seen.append(("assigned", type(out_data[0]._data)))

                def backward(self, req, out_grad, in_data, out_data,
                             in_grad, aux):
                    seen.append(("bwd", type(out_grad[0]._data),
                                 type(in_grad[0]._data)))
                    self.assign(in_grad[0], req[0],
                                out_grad[0].asnumpy() * 3.0)
            return Scale()

    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with autograd.record():
        y = nd.Custom(x, op_type="scale_numpy_only")
    y.backward()
    np.testing.assert_allclose(y.asnumpy(), x.asnumpy() * 3.0)
    np.testing.assert_allclose(x.grad.asnumpy(), np.full((2, 3), 3.0))
    assert seen, "callbacks never ran"
    for entry in seen:
        assert all(t is np.ndarray for t in entry[1:]), entry
