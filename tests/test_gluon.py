"""Gluon API tests (ref: tests/python/unittest/test_gluon.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal


def test_parameter():
    p = gluon.Parameter("weight", shape=(3, 4))
    p.initialize(init="xavier")
    assert p.data().shape == (3, 4)
    assert p.grad().shape == (3, 4)
    p.set_data(nd.ones((3, 4)))
    assert p.data().asnumpy().sum() == 12


def test_parameter_deferred_init():
    dense = nn.Dense(5)
    dense.initialize()
    with pytest.raises(Exception):
        dense.weight.data()
    out = dense(nd.ones((2, 7)))
    assert out.shape == (2, 5)
    assert dense.weight.shape == (5, 7)


def test_block_naming_and_collect():
    net = nn.HybridSequential(prefix="model_")
    with net.name_scope():
        net.add(nn.Dense(4, in_units=3))
        net.add(nn.Dense(2, in_units=4))
    params = net.collect_params()
    names = list(params.keys())
    assert all(n.startswith("model_dense") for n in names), names
    assert len(names) == 4  # 2 weights + 2 biases
    sel = net.collect_params(".*weight")
    assert len(list(sel.keys())) == 2


def test_dense_forward_values():
    d = nn.Dense(3, in_units=2, use_bias=True)
    d.initialize(mx.init.One())
    x = nd.array([[1.0, 2.0]])
    out = d(x)
    assert_almost_equal(out, [[3.0, 3.0, 3.0]])


def test_sequential_train_converges():
    np.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu"), nn.Dense(2))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.5})
    X = nd.array(np.random.randn(128, 4).astype(np.float32))
    y = nd.array((np.random.randn(128, 4).astype(np.float32).sum(1) > 0)
                 .astype(np.float32)) if False else \
        nd.array((X.asnumpy().sum(1) > 0).astype(np.float32))
    lossfn = gluon.loss.SoftmaxCrossEntropyLoss()
    first = None
    for _ in range(40):
        with autograd.record():
            # per-sample losses; step(batch_size) applies the 1/N rescale
            loss = lossfn(net(X), y)
        loss.backward()
        trainer.step(128)
        if first is None:
            first = float(loss.mean().asscalar())
    assert float(loss.mean().asscalar()) < first * 0.5


def test_hybridize_consistency():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.LayerNorm(), nn.Dense(3))
    net.initialize()
    x = nd.random.normal(shape=(4, 6))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    np.testing.assert_allclose(eager, hybrid, rtol=1e-5, atol=1e-6)
    # second call hits the jit cache
    hybrid2 = net(x).asnumpy()
    np.testing.assert_allclose(hybrid, hybrid2)


def test_hybridize_backward():
    net = nn.Dense(1, in_units=3)
    net.initialize(mx.init.One())
    net.hybridize()
    x = nd.array([[1.0, 2.0, 3.0]])
    x.attach_grad()
    with autograd.record():
        y = net(x).sum()
    y.backward()
    assert_almost_equal(x.grad, [[1.0, 1.0, 1.0]])
    assert net.weight.data().grad is None or True


def test_hybridize_param_grads():
    net = nn.Dense(2, in_units=3, use_bias=False)
    net.initialize(mx.init.One())
    net.hybridize()
    x = nd.ones((4, 3))
    with autograd.record():
        y = net(x).sum()
    y.backward()
    g = net.weight.grad()
    assert_almost_equal(g, 4 * np.ones((2, 3)))


def test_batchnorm_running_stats_eager_and_hybrid():
    for hybrid in (False, True):
        bn = nn.BatchNorm(in_channels=3, momentum=0.5)
        bn.initialize()
        if hybrid:
            bn.hybridize()
        x = nd.array(np.random.randn(8, 3, 4, 4).astype(np.float32) * 2 + 1)
        with autograd.record():
            bn(x)
        rm = bn.running_mean.data().asnumpy()
        assert not np.allclose(rm, 0), f"hybrid={hybrid}: stats not updated"
        # inference path uses running stats
        out = bn(x)
        assert out.shape == x.shape


def test_conv_block_shapes():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(4, 3, padding=1), nn.MaxPool2D(),
            nn.Conv2D(8, 3, padding=1, strides=2), nn.GlobalAvgPool2D(),
            nn.Flatten(), nn.Dense(5))
    net.initialize()
    out = net(nd.zeros((2, 3, 16, 16)))
    assert out.shape == (2, 5)


def test_conv_transpose():
    net = nn.Conv2DTranspose(4, 2, strides=2, in_channels=3)
    net.initialize()
    out = net(nd.zeros((1, 3, 5, 5)))
    assert out.shape == (1, 4, 10, 10)


def test_embedding_layer():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    out = emb(nd.array([1, 2, 3]))
    assert out.shape == (3, 4)


def test_losses():
    pred = nd.array(np.random.randn(4, 5).astype(np.float32))
    label = nd.array([0, 1, 2, 3])
    l = gluon.loss.SoftmaxCrossEntropyLoss()(pred, label)
    logp = np.log(np.exp(pred.asnumpy() - pred.asnumpy().max(1, keepdims=True)).T
                  / np.exp(pred.asnumpy() - pred.asnumpy().max(1, keepdims=True)).sum(1)).T
    expect = -logp[np.arange(4), [0, 1, 2, 3]]
    assert_almost_equal(l, expect, rtol=1e-4, atol=1e-5)

    p2 = nd.array([[0.5], [2.0]])
    t2 = nd.array([[1.0], [1.0]])
    l2 = gluon.loss.L2Loss()(p2, t2)
    assert_almost_equal(l2, [0.5 * 0.25, 0.5 * 1.0])
    l1 = gluon.loss.L1Loss()(p2, t2)
    assert_almost_equal(l1, [0.5, 1.0])
    hl = gluon.loss.HuberLoss()(p2, t2)
    assert hl.shape == (2,)


def test_ctc_loss():
    # gluon convention: blank is the LAST class, padding is -1
    # (ref: gluon/loss.py:475 passes blank_label='last')
    pred = nd.array(np.random.uniform(-1, 1, (2, 20, 6)).astype(np.float32))
    label = nd.array([[1, 2, 3, -1], [2, 2, -1, -1]])
    loss = gluon.loss.CTCLoss()(pred, label)
    assert loss.shape == (2,)
    assert np.all(loss.asnumpy() > 0)
    # padding must actually mask: explicit label_lengths giving the same
    # effective labels must produce the same loss
    loss2 = gluon.loss.CTCLoss()(pred, nd.array([[1, 2, 3, 5], [2, 2, 5, 5]]),
                                 None, nd.array([3.0, 2.0]))
    np.testing.assert_allclose(loss.asnumpy(), loss2.asnumpy(), rtol=1e-5)


def test_rnn_layers():
    for layer, states in [(gluon.rnn.RNN(8, 2), 1),
                          (gluon.rnn.LSTM(8, 2), 2),
                          (gluon.rnn.GRU(8, 2), 1)]:
        layer.initialize()
        x = nd.random.normal(shape=(5, 3, 4))
        out = layer(x)
        assert out.shape == (5, 3, 8)
        begin = layer.begin_state(batch_size=3)
        out, new_states = layer(x, begin)
        assert len(new_states) == states


def test_rnn_cells_unroll():
    cell = gluon.rnn.LSTMCell(6, input_size=4)
    cell.initialize()
    x = nd.random.normal(shape=(2, 5, 4))  # NTC
    outputs, states = cell.unroll(5, x, layout="NTC")
    assert outputs.shape == (2, 5, 6)
    assert len(states) == 2


def test_sequential_rnn_cell():
    seq = gluon.rnn.SequentialRNNCell()
    seq.add(gluon.rnn.LSTMCell(4, input_size=3))
    seq.add(gluon.rnn.GRUCell(5, input_size=4))
    seq.initialize()
    states = seq.begin_state(batch_size=2)
    out, new_states = seq(nd.ones((2, 3)), states)
    assert out.shape == (2, 5)
    assert len(new_states) == 3


def test_save_load_parameters(tmp_path):
    f = str(tmp_path / "net.params")
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    net.save_parameters(f)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.load_parameters(f)
    x = nd.ones((1, 3))
    np.testing.assert_allclose(net(x).asnumpy(), net2(x).asnumpy())


def test_trainer_lr_and_states(tmp_path):
    net = nn.Dense(2, in_units=2)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.1, "momentum": 0.9})
    with autograd.record():
        loss = net(nd.ones((1, 2))).sum()
    loss.backward()
    tr.step(1)
    assert tr.learning_rate == 0.1
    tr.set_learning_rate(0.01)
    assert tr.learning_rate == 0.01
    f = str(tmp_path / "trainer.states")
    tr.save_states(f)
    tr.load_states(f)


def test_zoneout_dropout_cells():
    cell = gluon.rnn.DropoutCell(0.3)
    out, states = cell(nd.ones((2, 4)), [])
    assert out.shape == (2, 4)


def test_split_and_load():
    data = nd.array(np.arange(12).reshape(6, 2))
    parts = gluon.utils.split_data(data, 3)
    assert [p.shape for p in parts] == [(2, 2)] * 3
    loaded = gluon.utils.split_and_load(data, [mx.cpu(), mx.cpu()])
    assert len(loaded) == 2


def test_clip_global_norm():
    arrays = [nd.ones((2, 2)) * 3, nd.ones((3,)) * 4]
    total = gluon.utils.clip_global_norm(arrays, 1.0)
    new_total = np.sqrt(sum((a.asnumpy() ** 2).sum() for a in arrays))
    assert new_total < 1.01


def test_model_zoo_variants():
    for name in ("resnet18_v1", "resnet18_v2", "mobilenet0.25",
                 "mobilenetv2_0.25", "squeezenet1.0"):
        net = gluon.model_zoo.vision.get_model(name, classes=10)
        net.initialize()
        out = net(nd.random.uniform(shape=(1, 3, 64, 64)))
        assert out.shape == (1, 10), name


def test_custom_hybrid_block():
    class Residual(nn.HybridBlock):
        def __init__(self, units, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.dense = nn.Dense(units, flatten=False)

        def hybrid_forward(self, F, x):
            return F.relu(self.dense(x)) + x

    blk = Residual(6)
    blk.initialize()
    x = nd.random.normal(shape=(2, 6))
    out = blk(x)
    blk.hybridize()
    out2 = blk(x)
    np.testing.assert_allclose(out.asnumpy(), out2.asnumpy(), rtol=1e-5,
                               atol=1e-6)


def test_dataset_dataloader():
    X = np.random.randn(20, 3).astype(np.float32)
    y = np.arange(20, dtype=np.float32)
    ds = gluon.data.ArrayDataset(X, y)
    assert len(ds) == 20
    loader = gluon.data.DataLoader(ds, batch_size=6, shuffle=False)
    batches = list(loader)
    assert len(batches) == 4
    xb, yb = batches[0]
    assert xb.shape == (6, 3)
    loader2 = gluon.data.DataLoader(ds, batch_size=5, shuffle=True,
                                    num_workers=2)
    seen = sorted(float(v) for _, yb in loader2 for v in yb.asnumpy())
    assert seen == sorted(y.tolist())


def test_transforms():
    from mxnet_tpu.gluon.data.vision import transforms
    img = nd.array(np.random.randint(0, 255, (8, 8, 3)).astype(np.uint8))
    t = transforms.ToTensor()(img)
    assert t.shape == (3, 8, 8)
    assert float(t.asnumpy().max()) <= 1.0
    norm = transforms.Normalize([0.5, 0.5, 0.5], [0.2, 0.2, 0.2])(t)
    assert norm.shape == (3, 8, 8)
    r = transforms.Resize(4)(img)
    assert r.shape == (4, 4, 3)


def test_unroll_valid_length_states():
    """States must freeze at each sequence's last valid step
    (regression: padding used to contaminate returned states)."""
    from mxnet_tpu.gluon import rnn
    cell = rnn.RNNCell(4, input_size=3)
    cell.initialize()
    T, B = 6, 2
    x = nd.array(np.random.randn(B, T, 3).astype(np.float32))
    vl = nd.array([3.0, 6.0])
    out, states = cell.unroll(T, x, layout="NTC", valid_length=vl)
    # sequence 0: state after unrolling only its first 3 steps
    _, states3 = cell.unroll(3, nd.array(x.asnumpy()[:, :3]), layout="NTC")
    np.testing.assert_allclose(states[0].asnumpy()[0],
                               states3[0].asnumpy()[0], rtol=1e-5, atol=1e-6)
    # masked outputs are zero past valid_length
    assert np.all(out.asnumpy()[0, 3:] == 0)


def test_bidirectional_valid_length():
    """Reverse direction must start at the last VALID step, not padding."""
    from mxnet_tpu.gluon import rnn
    l, r = rnn.RNNCell(4, input_size=3), rnn.RNNCell(4, input_size=3)
    bi = rnn.BidirectionalCell(l, r)
    bi.initialize()
    T, B = 5, 2
    x = np.random.randn(B, T, 3).astype(np.float32)
    vl = nd.array([2.0, 5.0])
    out, _ = bi.unroll(T, nd.array(x), layout="NTC", valid_length=vl)
    # for seq 0 (len 2) the reverse pass over just the valid prefix must
    # match a bidirectional unroll of the truncated sequence
    bi2 = rnn.BidirectionalCell(l, r)
    out2, _ = bi2.unroll(2, nd.array(x[:, :2]), layout="NTC")
    np.testing.assert_allclose(out.asnumpy()[0, :2], out2.asnumpy()[0],
                               rtol=1e-5, atol=1e-6)


def test_zoneout_reset_between_unrolls():
    from mxnet_tpu.gluon import rnn
    cell = rnn.ZoneoutCell(rnn.RNNCell(4, input_size=3), zoneout_outputs=0.5)
    cell.initialize()
    x8 = nd.array(np.random.randn(8, 3, 3).astype(np.float32))
    x2 = nd.array(np.random.randn(2, 3, 3).astype(np.float32))
    with autograd.record():
        cell.unroll(3, x8, layout="NTC")
        # used to crash: stale _prev_output from the bs=8 batch
        cell.unroll(3, x2, layout="NTC")


def test_f1_mcc_local_global():
    from mxnet_tpu import metric
    m = metric.F1(average="micro")
    labels = nd.array([1.0, 1.0, 0.0, 0.0])
    preds = nd.array([[0.1, 0.9], [0.8, 0.2], [0.2, 0.8], [0.9, 0.1]])
    m.update(labels, preds)
    _, f1_a = m.get()
    m.reset_local()
    perfect_l = nd.array([1.0, 0.0])
    perfect_p = nd.array([[0.0, 1.0], [1.0, 0.0]])
    m.update(perfect_l, perfect_p)
    _, f1_local = m.get()
    assert f1_local == 1.0  # local window sees only the perfect batch
    _, f1_global = m.get_global()
    assert f1_local > f1_global > 0  # global still includes first batch
    mc = metric.MCC(average="micro")
    mc.update(perfect_l, perfect_p)
    _, v = mc.get()
    assert abs(v - 1.0) < 1e-9


def test_trainer_save_load_states():
    import tempfile, os
    net = nn.Dense(2, in_units=3)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 0.1})
    x = nd.array(np.random.randn(4, 3).astype(np.float32))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    tr.step(4)
    with tempfile.TemporaryDirectory() as d:
        fname = os.path.join(d, "trainer.states")
        tr.save_states(fname)
        assert os.path.getsize(fname) > 0
        tr2 = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.1})
        tr2.load_states(fname)
        s1 = tr._updaters.states
        s2 = tr2._updaters.states
        assert set(s1.keys()) == set(s2.keys()) and len(s1) > 0


def test_lbsgd_warmup():
    from mxnet_tpu import optimizer as opt
    o = opt.create("lbsgd", learning_rate=0.1, warmup_strategy="linear",
                   warmup_epochs=1, updates_per_epoch=10, batch_scale=4)
    w = nd.array(np.ones(4, np.float32))
    g = nd.array(np.ones(4, np.float32) * 0.1)
    state = o.create_state(0, w)
    lrs = []
    for _ in range(12):
        o.update(0, w, g, state)
        lrs.append(o._get_lr(0))
    assert lrs[-1] == pytest.approx(0.4)  # reaches batch_scale * lr
    assert lrs[0] < lrs[5] < lrs[-1]      # monotone warmup


def test_unroll_unmerged_valid_length():
    """Regression: merge_outputs=False + valid_length used to crash."""
    from mxnet_tpu.gluon import rnn
    cell = rnn.RNNCell(4, input_size=3)
    cell.initialize()
    x = nd.array(np.random.randn(2, 5, 3).astype(np.float32))
    outs, _ = cell.unroll(5, x, layout="NTC", merge_outputs=False,
                          valid_length=nd.array([2.0, 5.0]))
    assert isinstance(outs, list) and len(outs) == 5
    assert outs[0].shape == (2, 4)
    assert np.all(outs[3].asnumpy()[0] == 0)  # masked past valid_length


def test_model_store_local_resolution(tmp_path):
    """Pretrained weights resolve through the local store: plain
    {name}.params is accepted, hashed release names are sha1-verified,
    missing files raise the offline-placement error
    (ref: gluon/model_zoo/model_store.py)."""
    import pytest

    from mxnet_tpu.gluon.model_zoo import model_store, vision

    net = vision.mobilenet0_25()
    net.initialize()
    _ = net(mx.nd.ones((1, 3, 32, 32)))
    net.save_parameters(str(tmp_path / "mobilenet0.25.params"))

    # plain name resolves
    loaded = vision.get_model("mobilenet0.25", pretrained=True,
                              root=str(tmp_path))
    ref = net(mx.nd.ones((1, 3, 32, 32))).asnumpy()
    got = loaded(mx.nd.ones((1, 3, 32, 32))).asnumpy()
    np.testing.assert_allclose(got, ref, rtol=1e-5)

    # hashed name must sha1-verify (our re-export won't match)
    hashed = "mobilenet0.25-%s.params" % model_store.short_hash(
        "mobilenet0.25")
    (tmp_path / hashed).write_bytes(
        (tmp_path / "mobilenet0.25.params").read_bytes())
    with pytest.raises(Exception, match="checksum mismatch"):
        model_store.get_model_file("mobilenet0.25", str(tmp_path))

    # missing -> clear offline message
    with pytest.raises(Exception, match="no pretrained weights"):
        model_store.get_model_file("resnet50_v1", str(tmp_path))


def test_libinfo():
    import mxnet_tpu.libinfo as li
    feats = {f.name: f.enabled for f in li.features()}
    assert feats["NATIVE_CORE"]
    assert feats["NATIVE_COMM"]
    ev = li.env_vars()
    assert "MXNET_ENGINE_TYPE" in ev and len(ev["MXNET_ENGINE_TYPE"]) == 2
    assert any(p.endswith(".so") for p in li.find_lib_path())


# -- a recorded hybridized call runs its forward once and keeps the pullback --
class _WithIndex(gluon.HybridBlock):
    """A float output and an integer one: only the first is differentiated."""

    def __init__(self):
        super().__init__()
        with self.name_scope():
            self.dense = nn.Dense(4, in_units=5)

    def hybrid_forward(self, F, x):
        out = self.dense(x)
        return out, F.argmax(out, axis=1).astype("int32")


def _seq(*layers):
    def build():
        net = nn.HybridSequential()
        net.add(*[make() for make in layers])
        return net
    return build


_X = np.random.RandomState(0).randn(6, 5).astype(np.float32)
_X2 = np.random.RandomState(1).randn(6, 5).astype(np.float32)
_Y = np.array([0, 1, 2, 0, 1, 2], dtype=np.float32)


def _hybrid_loss(net, x):
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    return loss_fn(net(x), nd.array(_Y))


# name -> (the net, the heads from the net and the input, record's mode)
_RECORDED = {
    "batchnorm": (_seq(lambda: nn.Dense(8, activation="relu", in_units=5),
                       lambda: nn.BatchNorm(in_channels=8),
                       lambda: nn.Dense(3, in_units=8)),
                  lambda net, x: (net(x) ** 2).sum(), True),
    "dropout": (_seq(lambda: nn.Dense(16, in_units=5),
                     lambda: nn.Dropout(0.5),
                     lambda: nn.Dense(3, in_units=16)),
                lambda net, x: (net(x) ** 2).sum(), True),
    "called_twice": (_seq(lambda: nn.Dense(3, activation="tanh", in_units=5)),
                     lambda net, x: net(x).sum()
                     + (net(nd.array(_X2)) * net(x)).sum(), True),
    "hybrid_loss": (_seq(lambda: nn.Dense(8, activation="relu", in_units=5),
                         lambda: nn.Dense(3, in_units=8)),
                    _hybrid_loss, True),
    "predict_mode": (_seq(lambda: nn.Dense(8, in_units=5),
                          lambda: nn.BatchNorm(in_channels=8),
                          lambda: nn.Dropout(0.5)),
                     lambda net, x: (net(x) ** 2).sum(), False),
    "integer_output": (_WithIndex,
                       lambda net, x: (net(x)[0] ** 2).sum(), True),
    "conv_batchnorm": (_seq(lambda: nn.Conv2D(4, 3, padding=1, in_channels=2),
                            lambda: nn.BatchNorm(in_channels=4),
                            lambda: nn.Activation("relu"),
                            lambda: nn.Dense(3, in_units=60)),
                       lambda net, x: (net(x.reshape((1, 2, 3, 5)))
                                       ** 2).sum(),
                       True),
}


def _recorded_step(build, heads_of, train_mode, as_before, x_wants_grad):
    """One recorded step of a fresh net with the same weights each time:
    heads, gradients, aux states. ``as_before`` makes the call differentiate
    nothing, so it runs the plain program and backward replays the node's
    closure under jax.vjp: the path every recorded call took before."""
    np.random.seed(5)
    mx.random.seed(5)
    net = build()
    net.initialize()
    net.hybridize()
    x = nd.array(_X)
    if x_wants_grad:
        x.attach_grad()
    with pytest.MonkeyPatch.context() as patch:
        if as_before:
            patch.setattr(autograd, "_differentiated", lambda a: False)
        with autograd.record(train_mode=train_mode):
            heads = heads_of(net, x)
    kept = [n.pullback is not None for n in autograd._st().tape
            if n.op.name.startswith("cachedop_")]
    heads.backward()
    params = net.collect_params().values()
    got = [heads.asnumpy()]
    got += [p.grad().asnumpy() for p in params if p.grad_req != "null"]
    got += [p.data().asnumpy() for p in params if p.grad_req == "null"]
    if x_wants_grad:
        got.append(x.grad.asnumpy())
    return got, kept, net


@pytest.mark.parametrize("x_wants_grad", [False, True])
@pytest.mark.parametrize("case", sorted(_RECORDED))
def test_recorded_hybrid_call_equals_the_replay(case, x_wants_grad):
    build, heads_of, train_mode = _RECORDED[case]
    got, kept, net = _recorded_step(build, heads_of, train_mode, False,
                                    x_wants_grad)
    want, kept_before, _ = _recorded_step(build, heads_of, train_mode, True,
                                          x_wants_grad)
    assert kept and all(kept) and not any(kept_before)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    # every recorded call took the forward that writes the residuals: the
    # plain program of that mode was never run, so never compiled
    for jfn, _, _, recorded in net._cached_jit.values():
        assert jfn._cache_size() == 0
        assert all(fwd._cache_size() == 1 and bwd._cache_size() == 1
                   for _, (fwd, bwd), _ in recorded.values())


def test_recorded_call_differentiates_only_what_is_on_the_tape():
    np.random.seed(5)
    net = _RECORDED["batchnorm"][0]()
    net.initialize()
    net.hybridize()
    params = list(net.collect_params().values())
    params[0].grad_req = "null"            # a frozen leaf
    frozen = params[0].data().asnumpy()
    x = nd.array(_X)                       # the batch carries no entry
    with autograd.record():
        out = net(x)
    outs, diff, run = autograd._st().tape[-1].pullback
    by_name = dict(zip([n for n, _ in net._cached_plist] + ["x"], diff))
    assert by_name == {n: p.grad_req != "null"
                       for n, p in net._cached_plist} | {"x": False}
    assert sum(diff) == 5 and by_name[params[0].name] is False
    # the pullback has no output for the batch, the frozen leaf or the
    # running statistics: nothing computes a gradient nobody reads
    cts = run((nd.ones(out.shape)._data,))
    assert len(cts) == sum(diff)
    out.backward()
    np.testing.assert_array_equal(params[0].data().asnumpy(), frozen)
    with pytest.raises(Exception):
        params[0].grad()


def test_recorded_call_honours_add_and_a_second_backward():
    np.random.seed(5)
    net = _RECORDED["called_twice"][0]()
    net.initialize()
    net.hybridize()
    weight, bias = net.collect_params().values()
    weight.grad_req = "add"
    x = nd.array(_X)
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward(retain_graph=True)
    once_w, once_b = weight.grad().asnumpy(), bias.grad().asnumpy()
    assert np.abs(once_w).sum() > 0
    loss.backward()                         # the kept pullback, again
    np.testing.assert_allclose(weight.grad().asnumpy(), 2 * once_w,
                               rtol=1e-6)
    np.testing.assert_allclose(bias.grad().asnumpy(), once_b, rtol=1e-6)
    assert next(iter(net._cached_jit.values()))[0]._cache_size() == 0
    with pytest.raises(mx.MXNetError, match="already been freed"):
        loss.backward()


def test_dropout_mask_is_the_same_forward_and_backward():
    net = nn.HybridSequential()
    net.add(nn.Dropout(0.5))
    net.hybridize()
    x = nd.ones((64, 32))
    x.attach_grad()
    with autograd.record():
        y = net(x)
    y.backward()
    kept = y.asnumpy() != 0
    assert 0.2 < kept.mean() < 0.8
    np.testing.assert_array_equal(x.grad.asnumpy() != 0, kept)
    np.testing.assert_allclose(x.grad.asnumpy(), y.asnumpy())


def _conv_net(grad_req="write"):
    np.random.seed(5)
    mx.random.seed(5)
    build, heads_of, _ = _RECORDED["conv_batchnorm"]
    net = build()
    net.initialize()
    net.hybridize()
    for p in net.collect_params().values():
        if p.grad_req != "null":
            p.grad_req = grad_req
    return net, heads_of


def _grads(net):
    return [p.grad().asnumpy() for p in net.collect_params().values()
            if p.grad_req != "null"]


def test_recorded_conv_block_backward_twice_with_retain_graph():
    """A second ``backward`` runs the kept pullback again, over the
    residuals as the forward returned them (transposed, those the
    convolution made in another layout), and the gradients it adds are
    the replay's."""
    net, heads_of = _conv_net("add")
    with autograd.record():
        loss = heads_of(net, nd.array(_X))
    loss.backward(retain_graph=True)
    once = _grads(net)
    assert all(np.abs(g).sum() > 0 for g in once)
    loss.backward()
    for g, g1 in zip(_grads(net), once):
        np.testing.assert_allclose(g, 2 * g1, rtol=1e-6, atol=1e-7)
    want, _, _ = _recorded_step(*_RECORDED["conv_batchnorm"], True, False)
    for g1, w in zip(once, want[1:]):
        np.testing.assert_allclose(g1, w, rtol=1e-6, atol=1e-6)


def test_recorded_conv_block_with_create_graph_takes_the_replay():
    """``create_graph=True`` cannot use the kept pullback (it is closed
    over concrete residuals): the node's plain program is replayed, and
    gives the gradients the pullback gives."""
    net, heads_of = _conv_net()
    x = nd.array(_X)
    x.attach_grad()
    with autograd.record():
        loss = heads_of(net, x)
    kept, = [n.pullback for n in autograd._st().tape
             if n.op.name.startswith("cachedop_")]
    assert kept is not None
    (_, _, _, recorded), = net._cached_jit.values()
    (_, (_, bwd), _), = recorded.values()
    gx, = autograd.grad([loss], [x], create_graph=True, retain_graph=True)
    # the replay traced the plain program; the pullback never ran
    assert bwd._cache_size() == 0
    loss.backward()
    assert bwd._cache_size() == 1
    np.testing.assert_allclose(gx.asnumpy(), x.grad.asnumpy(),
                               rtol=1e-6, atol=1e-6)


def _relaid(program):
    series = mx.telemetry.snapshot()["metrics"]["mx_residuals_relaid"]
    return {s["labels"]["program"]: s["value"]
            for s in series["series"]}[program]


@pytest.mark.parametrize("case", ["batchnorm", "conv_batchnorm"])
def test_relaid_residuals_counter_reads_what_crosses_transposed(case):
    """The gauge a recorded call's compiled pair sets counts the residuals
    its forward returns transposed, because its operations made them in
    another layout than the default for their shape. On the CPU the dense
    block's forward makes every residual in the default layout, so it
    reads 0; the convolution's makes some otherwise. Whatever crosses,
    crosses in the default layout for its shape."""
    import jax
    from jax.experimental.layout import Layout

    np.random.seed(5)
    mx.random.seed(5)
    build, heads_of, _ = _RECORDED[case]
    net = build()
    net.initialize()
    net.hybridize()
    with autograd.record():
        heads_of(net, nd.array(_X)).backward()
    (_, _, _, recorded), = net._cached_jit.values()
    (_, (fwd, _), _), = recorded.values()
    x = nd.array(_X)
    if case == "conv_batchnorm":
        x = x.reshape((1, 2, 3, 5))
    _, _, computed = fwd(tuple(p.data()._data for _, p in net._cached_plist),
                         mx.random.next_key(), x._data)
    dev = jax.devices()[0]
    assert all(c.format.layout == Layout.from_pjrt_layout(
        dev.client.get_default_layout(c.dtype, c.shape, dev))
        for c in computed)
    if case == "batchnorm":
        assert _relaid(fwd.__name__) == 0
    else:
        assert _relaid(fwd.__name__) > 0


def test_recorded_conv_forward_plans_no_more_bytes_than_the_plain_one(
        monkeypatch):
    """The forward a recorded call keeps, with some residuals crossing
    transposed, plans no more bytes (outputs and temporaries) than the
    plain forward that returns every residual in its default layout; the
    plain pair, kept where nothing crosses transposed, computes the same
    gradients."""
    from mxnet_tpu.gluon import block as blk

    net, heads_of = _conv_net()
    with autograd.record():
        heads_of(net, nd.array(_X)).backward()
    (jfn, _, _, recorded), = net._cached_jit.values()
    (diff, (_, (fwd, _), (kept, _))), = recorded.items()
    assert _relaid(fwd.__name__) > 0
    want = _grads(net)
    args = (tuple(p.data()._data for _, p in net._cached_plist),
            mx.random.next_key(), nd.array(_X).reshape((1, 2, 3, 5))._data)
    monkeypatch.setattr(blk, "_axis_orders",
                        lambda hlo, first, residuals, device:
                        [None] * len(residuals))
    _, _, (plain, _) = net._build_recorded(jfn, diff, True, args)
    assert _relaid(fwd.__name__) == 0
    kf, pf = (c.memory_analysis() for c in (kept, plain))
    assert (kf.output_size_in_bytes + kf.temp_size_in_bytes
            <= pf.output_size_in_bytes + pf.temp_size_in_bytes)
    net._cached_jit.clear()
    with autograd.record():
        heads_of(net, nd.array(_X)).backward()
    for g, w in zip(_grads(net), want):
        np.testing.assert_allclose(g, w, rtol=1e-6, atol=1e-6)
