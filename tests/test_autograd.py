"""Autograd semantics (ref: tests/python/unittest/test_autograd.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd
from mxnet_tpu.test_utils import assert_almost_equal


def test_basic_backward():
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, [2.0, 4.0, 6.0])


def test_chain_and_broadcast():
    x = nd.array(np.random.randn(3, 4).astype(np.float32))
    w = nd.array(np.random.randn(4, 2).astype(np.float32))
    x.attach_grad()
    w.attach_grad()
    with autograd.record():
        y = nd.dot(x, w)
        z = (nd.relu(y) * 2).sum()
    z.backward()
    mask = (x.asnumpy() @ w.asnumpy()) > 0
    expect_w = x.asnumpy().T @ (2 * mask)
    assert_almost_equal(w.grad, expect_w, rtol=1e-4, atol=1e-5)


def test_head_gradient():
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
    y.backward(nd.array([10.0, 100.0]))
    assert_almost_equal(x.grad, [30.0, 300.0])


def test_grad_req_add():
    x = nd.array([1.0, 2.0])
    x.attach_grad(grad_req="add")
    for _ in range(3):
        with autograd.record():
            y = (x * x).sum()
        y.backward()
    assert_almost_equal(x.grad, [6.0, 12.0])


def test_detach_blocks_grad():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
        z = y.detach() * x
    z.backward()
    assert_almost_equal(x.grad, [6.0])  # only d(6x)/dx; detached path constant


def test_blockgrad_op():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = nd.BlockGrad(x * 3) * x
    y.backward()
    assert_almost_equal(x.grad, [6.0])


def test_pause_scope():
    x = nd.array([1.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        with autograd.pause():
            c = x * 10  # not recorded
        z = y + c.detach()
    z.backward()
    assert_almost_equal(x.grad, [2.0])


def test_is_recording_training():
    assert not autograd.is_recording()
    with autograd.record():
        assert autograd.is_recording()
        assert autograd.is_training()
        with autograd.pause():
            assert not autograd.is_recording()
    with autograd.record(train_mode=False):
        assert not autograd.is_training()
    with autograd.train_mode():
        assert autograd.is_training()


def test_grad_function():
    x = nd.array([3.0])
    out = autograd.grad(
        [_f(x)], [x]) if False else None
    x.attach_grad()
    with autograd.record():
        y = x * x * x
    g = autograd.grad([y], [x])
    assert_almost_equal(g[0], [27.0])


def _f(x):
    return x * x


def test_second_order_grad():
    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * x * x
        g = autograd.grad([y], [x], create_graph=True, retain_graph=True)[0]
        z = g.sum()
    z.backward()
    # d/dx (3x^2) = 6x = 12
    assert_almost_equal(x.grad, [12.0])


def test_getitem_grad():
    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    x.attach_grad()
    with autograd.record():
        y = (x[0] * 2).sum()
    y.backward()
    assert_almost_equal(x.grad, [[2, 2, 2], [0, 0, 0]])


def test_dropout_and_rng_determinism():
    x = nd.ones((100,))
    x.attach_grad()
    with autograd.record():
        y = nd.Dropout(x, p=0.5)
        z = y.sum()
    z.backward()
    # grad equals the mask/keep_prob actually drawn in forward
    yv = None
    g = x.grad.asnumpy()
    assert set(np.unique(g)).issubset({0.0, 2.0})


def test_multi_output_heads():
    x = nd.array([1.0, 2.0])
    x.attach_grad()
    with autograd.record():
        y1 = x * 2
        y2 = x * 3
    autograd.backward([y1, y2])
    assert_almost_equal(x.grad, [5.0, 5.0])


def test_mark_variables():
    x = nd.array([1.0, 2.0])
    g = nd.zeros((2,))
    autograd.mark_variables([x], [g])
    with autograd.record():
        y = (x * x).sum()
    y.backward()
    assert_almost_equal(x.grad, [2.0, 4.0])


def test_custom_function():
    class Square(autograd.Function):
        def forward(self, x):
            self.save_for_backward(x)
            return x * x

        def backward(self, dy):
            x, = self.saved_tensors
            return dy * x * 2

    x = nd.array([3.0])
    x.attach_grad()
    sq = Square()
    with autograd.record():
        y = sq(x)
    y.backward()
    assert_almost_equal(x.grad, [6.0])


def test_softmax_output_backward():
    x = nd.array(np.random.randn(4, 3).astype(np.float32))
    label = nd.array([0, 1, 2, 1])
    x.attach_grad()
    with autograd.record():
        out = nd.SoftmaxOutput(x, label)
    out.backward()
    sm = np.exp(x.asnumpy() - x.asnumpy().max(-1, keepdims=True))
    sm /= sm.sum(-1, keepdims=True)
    onehot = np.eye(3, dtype=np.float32)[[0, 1, 2, 1]]
    assert_almost_equal(x.grad, sm - onehot, rtol=1e-4, atol=1e-5)


def test_training_flag_injection():
    x = nd.ones((50,))
    with autograd.record(train_mode=True):
        y = nd.Dropout(x, p=0.9)
    assert float(y.asnumpy().max()) > 1.5  # dropout active
    with autograd.record(train_mode=False):
        y = nd.Dropout(x, p=0.9)
    assert_almost_equal(y, x.asnumpy())  # identity in predict mode
    y = nd.Dropout(x, p=0.9)  # outside record: predict mode
    assert_almost_equal(y, x.asnumpy())


# -- the pullback a recorded hybridized call keeps, and when it is not used --
def _tanh_dense(hybridize):
    from mxnet_tpu.gluon import nn
    np.random.seed(3)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, activation="tanh", in_units=3))
    net.initialize()
    if hybridize:
        net.hybridize()
    return net


@pytest.fixture
def served(monkeypatch):
    """The nodes whose kept pullback a backward used, as it goes."""
    nodes, held_fn = [], autograd._held_fn
    monkeypatch.setattr(autograd, "_held_fn",
                        lambda node: nodes.append(node) or held_fn(node))
    return nodes


def test_create_graph_replays_the_hybridized_call_and_is_right(served):
    # the kept pullback is closed over concrete residuals and has no
    # derivative of its own: second order replays the node's closure
    got = []
    for hybridize in (True, False):
        net = _tanh_dense(hybridize)
        x = nd.array([[0.3, -0.2, 0.5]])
        x.attach_grad()
        with autograd.record():
            y = net(x).sum()
            g = autograd.grad([y], [x], create_graph=True,
                              retain_graph=True)[0]
            z = (g * g).sum()
        z.backward()
        got.append((g.asnumpy(), x.grad.asnumpy()))
    assert np.abs(got[0][1]).sum() > 0
    for a, b in zip(*got):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert served == []


@pytest.mark.parametrize("req_at_forward,replayed", [("null", 1),
                                                     ("write", 0)])
def test_grad_of_a_leaf_the_forward_did_not_differentiate(req_at_forward,
                                                          replayed, served):
    # autograd.grad turns a "null" request into "write" after the forward
    # ran: the kept pullback has no cotangent for that input, so the node
    # is replayed; a leaf that wanted its gradient all along is served
    want = None
    for hybridize in (False, True):
        net = _tanh_dense(hybridize)
        x = nd.array([[0.3, -0.2, 0.5]])
        x.attach_grad(grad_req=req_at_forward)
        with autograd.record():
            y = (net(x) ** 2).sum()
        if hybridize:
            node = autograd._st().tape[0]
            assert node.pullback[1][-1] is (req_at_forward != "null")
        g = autograd.grad([y], [x])[0].asnumpy()
        assert x._grad_req == req_at_forward
        if want is None:
            want = g
    assert np.abs(want).sum() > 0
    np.testing.assert_allclose(g, want, rtol=1e-6, atol=1e-7)
    assert len(served) == 1 - replayed


def test_backward_leaves_nothing_of_the_step_alive():
    import jax
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.BatchNorm(in_channels=16), nn.Dropout(0.5),
            nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    x = nd.array(np.random.randn(5, 8).astype(np.float32))
    y = nd.array(np.array([0, 1, 2, 3, 0], dtype=np.float32))
    alive = []
    for _ in range(6):
        with autograd.record():
            out = net(x)
            loss = loss_fn(out, y)
        node = autograd._st().tape[0]
        assert node.pullback is not None
        loss.backward()
        # the residuals went with the graph; parameters, gradients, this
        # step's outputs and the generator's key are what stays
        assert node.pullback is None and autograd._st().tape == []
        alive.append((len(jax.live_arrays()),
                      sum(a.nbytes for a in jax.live_arrays())))
    assert len(set(alive[1:])) == 1, alive
