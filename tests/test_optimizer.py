"""Optimizer/metric/lr-scheduler/initializer tests
(ref: tests/python/unittest/test_optimizer.py etc.)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


def _quad_opt_steps(opt_name, steps=60, **kwargs):
    """Minimize f(w) = ||w - 3||^2 with the given optimizer."""
    opt = mx.optimizer.create(opt_name, **kwargs)
    updater = mx.optimizer.get_updater(opt)
    w = nd.zeros((4,))
    for _ in range(steps):
        grad = 2 * (w - 3)
        updater(0, grad, w)
    return w.asnumpy()


@pytest.mark.parametrize("name,kwargs", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 0.3}),
    ("nag", {"learning_rate": 0.05, "momentum": 0.9}),
    ("rmsprop", {"learning_rate": 0.1}),
    ("adagrad", {"learning_rate": 1.0}),
    ("adadelta", {"rho": 0.9, "epsilon": 1e-2}),
    ("adamax", {"learning_rate": 0.5}),
    ("nadam", {"learning_rate": 0.3}),
    ("ftml", {"learning_rate": 0.3}),
    ("signum", {"learning_rate": 0.1}),
])
def test_optimizers_converge(name, kwargs):
    w = _quad_opt_steps(name, **kwargs)
    assert np.abs(w - 3).max() < 0.5, f"{name}: {w}"


def test_sgd_exact_steps():
    opt = mx.optimizer.SGD(learning_rate=0.1)
    w = nd.array([1.0])
    g = nd.array([2.0])
    opt.update(0, w, g, None)
    assert_almost_equal(w, [0.8])


def test_sgd_momentum_math():
    opt = mx.optimizer.SGD(learning_rate=0.1, momentum=0.5)
    w = nd.array([1.0])
    state = opt.create_state(0, w)
    opt.update(0, w, nd.array([1.0]), state)   # mom=-0.1, w=0.9
    assert_almost_equal(w, [0.9], rtol=1e-6)
    opt.update(0, w, nd.array([1.0]), state)   # mom=-0.15, w=0.75
    assert_almost_equal(w, [0.75], rtol=1e-6)


def test_weight_decay_and_clip():
    opt = mx.optimizer.SGD(learning_rate=0.1, wd=0.1)
    w = nd.array([1.0])
    opt.update(0, w, nd.array([0.0]), None)
    assert_almost_equal(w, [0.99], rtol=1e-6)  # pure decay
    opt2 = mx.optimizer.SGD(learning_rate=1.0, clip_gradient=0.5)
    w2 = nd.array([0.0])
    opt2.update(0, w2, nd.array([10.0]), None)
    assert_almost_equal(w2, [-0.5], rtol=1e-6)


def test_multi_precision():
    opt = mx.optimizer.SGD(learning_rate=0.1, multi_precision=True,
                           momentum=0.9)
    w = nd.array(np.ones(4, np.float16))
    state = opt.create_state_multi_precision(0, w)
    assert isinstance(state, tuple) and state[0].dtype == np.float32
    opt.update_multi_precision(0, w, nd.array(np.ones(4, np.float16)), state)
    assert w.dtype == np.float16


def test_lr_mult_and_idx2name():
    opt = mx.optimizer.SGD(learning_rate=1.0,
                           param_idx2name={0: "a_weight", 1: "b_bias"})
    opt.set_lr_mult({"a_weight": 0.1})
    w0, w1 = nd.array([1.0]), nd.array([1.0])
    opt.update(0, w0, nd.array([1.0]), None)
    opt.update(1, w1, nd.array([1.0]), None)
    assert_almost_equal(w0, [0.9], rtol=1e-6)
    assert_almost_equal(w1, [0.0], rtol=1e-6)


def test_lr_schedulers():
    s = mx.lr_scheduler.FactorScheduler(step=10, factor=0.5, base_lr=1.0)
    assert s(1) == 1.0
    assert s(11) == 0.5
    assert s(21) == 0.25
    m = mx.lr_scheduler.MultiFactorScheduler(step=[5, 15], factor=0.1,
                                             base_lr=1.0)
    assert m(1) == 1.0
    assert m(6) == pytest.approx(0.1)
    assert m(16) == pytest.approx(0.01)
    p = mx.lr_scheduler.PolyScheduler(max_update=100, base_lr=1.0, pwr=1)
    assert p(0) == pytest.approx(1.0)
    assert p(50) == pytest.approx(0.5)
    c = mx.lr_scheduler.CosineScheduler(max_update=100, base_lr=1.0)
    assert c(0) == pytest.approx(1.0)
    assert c(100) == pytest.approx(0.0, abs=1e-6)
    w = mx.lr_scheduler.FactorScheduler(step=100, base_lr=1.0,
                                        warmup_steps=10, warmup_begin_lr=0.0)
    assert w(5) == pytest.approx(0.5)


def test_lr_scheduler_warmup_modes():
    # constant mode HOLDS the warmup lr (it used to silently become a
    # quadratic ramp, VERDICT r5 weak #5)
    k = mx.lr_scheduler.FactorScheduler(
        step=100, base_lr=1.0, warmup_steps=10, warmup_begin_lr=0.25,
        warmup_mode="constant")
    for step in (0, 3, 9):
        assert k(step) == pytest.approx(0.25)
    assert k(10) == pytest.approx(1.0)  # warmup over: base lr takes over
    # unknown modes raise instead of silently ramping
    with pytest.raises(ValueError, match="warmup_mode"):
        mx.lr_scheduler.CosineScheduler(max_update=100,
                                        warmup_mode="quadratic")


def test_enum_params_validated():
    """Audit siblings of the warmup_mode bug: every string-enum param
    must reject unknown values instead of silently picking a branch."""
    with pytest.raises(ValueError, match="rnd_type"):
        mx.init.Xavier(rnd_type="gaussiann")
    with pytest.raises(ValueError, match="factor_type"):
        mx.init.Xavier(factor_type="harmonic")
    with pytest.raises(ValueError, match="rand_type"):
        mx.init.Orthogonal(rand_type="gaussian")  # it's 'normal' here
    with pytest.raises(ValueError, match="average"):
        mx.metric.F1(average="weighted")


def test_metrics_accuracy():
    acc = mx.metric.Accuracy()
    pred = nd.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    label = nd.array([1, 0, 0])
    acc.update(label, pred)
    assert acc.get() == ("accuracy", pytest.approx(2 / 3))
    acc.reset()
    assert np.isnan(acc.get()[1])


def test_metrics_topk_f1_mse():
    topk = mx.metric.TopKAccuracy(top_k=2)
    pred = nd.array([[0.3, 0.4, 0.3], [0.1, 0.2, 0.7]])
    topk.update(nd.array([0, 0]), pred)
    assert topk.get()[1] == pytest.approx(0.5)

    mse = mx.metric.MSE()
    mse.update(nd.array([1.0, 2.0]), nd.array([1.5, 2.0]))
    assert mse.get()[1] == pytest.approx(0.125)

    f1 = mx.metric.F1()
    f1.update(nd.array([1, 0, 1, 1]), nd.array([[0.2, 0.8], [0.8, 0.2],
                                                [0.1, 0.9], [0.9, 0.1]]))
    assert 0 < f1.get()[1] <= 1

    comp = mx.metric.CompositeEvalMetric()
    comp.add(mx.metric.Accuracy())
    comp.add(mx.metric.CrossEntropy())
    comp.update(nd.array([1.0]), nd.array([[0.3, 0.7]]))
    names, values = comp.get()
    assert len(names) == 2


def test_metric_perplexity():
    pp = mx.metric.Perplexity()
    pred = nd.array([[0.25, 0.75], [0.5, 0.5]])
    pp.update(nd.array([1, 0]), pred)
    expect = np.exp(-(np.log(0.75) + np.log(0.5)) / 2)
    assert pp.get()[1] == pytest.approx(expect, rel=1e-4)


def test_initializers():
    for name, check in [
        ("zeros", lambda a: np.allclose(a, 0)),
        ("ones", lambda a: np.allclose(a, 1)),
        ("uniform", lambda a: np.abs(a).max() <= 0.07 + 1e-6),
        ("normal", lambda a: np.abs(a).std() < 0.1),
        ("xavier", lambda a: np.isfinite(a).all()),
    ]:
        w = nd.zeros((8, 8))
        mx.init.create(name)("test_weight", w)
        assert check(w.asnumpy()), name
    # orthogonal: W W^T = I * scale^2
    w = nd.zeros((4, 4))
    mx.init.Orthogonal(scale=1.0)("q_weight", w)
    a = w.asnumpy()
    np.testing.assert_allclose(a @ a.T, np.eye(4), atol=1e-5)
    # bias routing
    b = nd.ones((5,))
    mx.init.Xavier()("fc_bias", b)
    assert np.allclose(b.asnumpy(), 0)
    # LSTMBias: forget gate = 1
    b = nd.zeros((8,))
    mx.init.LSTMBias(forget_bias=1.0)("lstm_i2h_bias", b)
    expect = np.zeros(8)
    expect[2:4] = 1
    np.testing.assert_allclose(b.asnumpy(), expect)


def test_updater_states_roundtrip():
    opt = mx.optimizer.Adam()
    upd = mx.optimizer.get_updater(opt)
    w = nd.ones((3,))
    upd(0, nd.ones((3,)), w)
    blob = upd.get_states()
    upd2 = mx.optimizer.get_updater(mx.optimizer.Adam())
    upd2.set_states(blob)
    assert 0 in upd2.states


# -- one optimizer program a step -------------------------------------------
# Every built-in whose step is "host part, kernel" takes the tree program
# of Updater.update_tree; the per-index update() is the reference.
TREE_CASES = [
    ("sgd", {}),
    ("sgd", {"momentum": 0.9}),
    ("sgd", {"momentum": 0.9, "clip_gradient": 0.05}),
    ("nag", {"momentum": 0.9}),
    ("signum", {"wd_lh": 0.01}),
    ("adam", {}),
    ("adagrad", {}),
    ("rmsprop", {}),
    ("rmsprop", {"centered": True, "clip_weights": 0.9}),
    ("adadelta", {}),
    ("ftrl", {}),
    ("adamax", {}),
    ("nadam", {}),
    ("ftml", {}),
    ("dcasgd", {"momentum": 0.5}),
    ("lbsgd", {"momentum": 0.9}),
    ("test", {}),
]
TREE_SHAPES = [(3, 4), (7,), (2, 3, 5), (1,), (6, 2)]


def _tree_opt(name, kwargs):
    # an lr that changes every step and a wd, so a stale scalar shows
    return mx.optimizer.create(
        name, learning_rate=0.05, wd=1e-3,
        lr_scheduler=mx.lr_scheduler.FactorScheduler(step=1, factor=0.9),
        param_idx2name={i: "p%d_weight" % i
                        for i in range(len(TREE_SHAPES))}, **kwargs)


def _state_arrays(state):
    if state is None:
        return []
    if isinstance(state, tuple):
        return [a for s in state for a in _state_arrays(s)]
    return [state.asnumpy()]


def _run_steps(updater, weights, grads_by_step, tree):
    for step, grads in enumerate(grads_by_step):
        # a short last batch: rescale_grad moves between steps
        updater.optimizer.rescale_grad = 1.0 / (4 - step)
        triples = [(i, nd.array(g), w)
                   for i, (g, w) in enumerate(zip(grads, weights))]
        if tree:
            updater.update_tree(triples)
        else:
            for i, g, w in triples:
                updater(i, g, w)


@pytest.mark.parametrize("name,kwargs", TREE_CASES,
                         ids=["%s-%s" % (n, "-".join(k) or "plain")
                              for n, k in TREE_CASES])
def test_tree_program_matches_per_index_update(name, kwargs):
    rng = np.random.RandomState(7)
    start = [rng.randn(*s).astype(np.float32) for s in TREE_SHAPES]
    grads = [[rng.randn(*s).astype(np.float32) for s in TREE_SHAPES]
             for _ in range(3)]
    ran = {}
    for tree in (True, False):
        upd = mx.optimizer.get_updater(_tree_opt(name, kwargs))
        assert upd.optimizer._fuses()
        weights = [nd.array(w) for w in start]
        _run_steps(upd, weights, grads, tree)
        ran[tree] = (upd, weights)
    (a, wa), (b, wb) = ran[True], ran[False]
    assert a.optimizer.num_update == b.optimizer.num_update
    assert a.optimizer._index_update_count == b.optimizer._index_update_count
    for x, y, was in zip(wa, wb, start):
        assert_almost_equal(x, y, rtol=1e-6, atol=1e-7)
        assert np.abs(x.asnumpy() - was).max() > 0
    assert a.states.keys() == b.states.keys()
    for i in a.states:
        for x, y in zip(_state_arrays(a.states[i]),
                        _state_arrays(b.states[i])):
            np.testing.assert_allclose(x, y, rtol=1e-6, atol=1e-7)


def test_tree_program_multi_precision_matches_per_index():
    rng = np.random.RandomState(3)
    start = [rng.randn(*s).astype(np.float16) for s in TREE_SHAPES]
    grads = [[rng.randn(*s).astype(np.float16) for s in TREE_SHAPES]
             for _ in range(3)]
    ran = {}
    for tree in (True, False):
        upd = mx.optimizer.get_updater(
            _tree_opt("sgd", {"momentum": 0.9, "multi_precision": True}))
        weights = [nd.array(w) for w in start]
        _run_steps(upd, weights, grads, tree)
        ran[tree] = (upd, weights)
    (a, wa), (b, wb) = ran[True], ran[False]
    for i, (x, y) in enumerate(zip(wa, wb)):
        assert x.dtype == np.float16
        master = a.states[i][0]
        assert master.dtype == np.float32
        # the half-precision weight is the master, cast
        np.testing.assert_array_equal(
            x.asnumpy(), master.asnumpy().astype(np.float16))
        for p, q in zip(_state_arrays(a.states[i]),
                        _state_arrays(b.states[i])):
            np.testing.assert_allclose(p, q, rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(x.asnumpy().astype(np.float32),
                                   y.asnumpy().astype(np.float32),
                                   rtol=2e-3, atol=1e-4)


def test_updater_states_roundtrip_after_tree_steps_and_resume():
    rng = np.random.RandomState(5)
    start = [rng.randn(*s).astype(np.float32) for s in TREE_SHAPES]
    grads = [[rng.randn(*s).astype(np.float32) for s in TREE_SHAPES]
             for _ in range(3)]
    upd = mx.optimizer.get_updater(_tree_opt("adam", {}))
    weights = [nd.array(w) for w in start]
    _run_steps(upd, weights, grads[:2], tree=True)
    blob = upd.get_states(dump_optimizer=True)
    resumed = mx.optimizer.get_updater(mx.optimizer.create("sgd"))
    resumed.set_states(blob)
    assert type(resumed.optimizer) is type(upd.optimizer)
    assert resumed.states.keys() == upd.states.keys()
    for i in upd.states:
        assert isinstance(resumed.states[i], tuple)     # Adam's (m, v)
        for x, y in zip(_state_arrays(upd.states[i]),
                        _state_arrays(resumed.states[i])):
            np.testing.assert_array_equal(x, y)
    weights2 = [nd.array(w.asnumpy()) for w in weights]
    for u, ws in ((upd, weights), (resumed, weights2)):
        u.optimizer.rescale_grad = 0.5
        u.update_tree([(i, nd.array(g), w)
                       for i, (g, w) in enumerate(zip(grads[2], ws))])
    assert resumed.optimizer.num_update == upd.optimizer.num_update == 3
    for x, y in zip(weights, weights2):
        np.testing.assert_array_equal(x.asnumpy(), y.asnumpy())


# -- what fuses and what falls back, through gluon's Trainer ----------------
@pytest.fixture
def programs(monkeypatch):
    """Every call of a jitted optimizer program while the test runs, as
    (program name, leaves it was handed): the dispatches of a step."""
    from mxnet_tpu.optimizer import optimizer as opt_mod
    calls = []
    real = opt_mod._step_program

    def counting(cls, fields, name, rows=False):
        fn = real(cls, fields, name, rows)

        def call(*args):
            calls.append((fn.__name__, 1 if rows else len(args[0])))
            return fn(*args)
        call._cache_size = fn._cache_size
        return call

    monkeypatch.setattr(opt_mod, "_step_program", counting)
    return calls


def _small_trainer(optimizer, params=None, prefix="net_"):
    from mxnet_tpu import autograd, gluon
    net = gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.BatchNorm(),
                gluon.nn.Dense(3))
    net.initialize(mx.init.Xavier(rnd_type="gaussian"))
    x = nd.array(np.random.RandomState(1).randn(4, 5).astype(np.float32))
    y = nd.array([0, 1, 2, 0])
    net(x)
    trainer = gluon.Trainer(net.collect_params(), optimizer,
                            params if params is not None else
                            {"learning_rate": 0.1, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def backward():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()

    leaves = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    return net, trainer, backward, leaves


def _indices(trainer):
    return [i for i, p in enumerate(trainer._params) if p.grad_req != "null"]


def test_trainer_step_is_one_program_over_the_tree(programs):
    _, trainer, backward, leaves = _small_trainer("sgd")
    for _ in range(2):
        backward()
        trainer.step(4)
    assert programs == [("_step_mom", len(leaves))] * 2


def test_row_sparse_leaf_goes_per_index_and_the_rest_fuses(programs,
                                                           monkeypatch):
    from mxnet_tpu.ndarray import sparse
    from mxnet_tpu.profiling import health
    # gluon writes dense gradients only, and the health probe reads
    # nothing else: a hand-placed row-sparse one is stepped without it
    monkeypatch.setattr(health, "step_probe", lambda step=None: None)
    _, trainer, backward, leaves = _small_trainer("sgd")
    backward()
    emb = leaves[0].data()                       # dense0 weight, (8, 5)
    dense_grad = emb.grad.asnumpy()
    emb.grad = sparse.RowSparseNDArray(dense_grad[[1, 6]], [1, 6], emb.shape)
    before = emb.asnumpy()
    trainer.step(4)
    assert sorted(programs) == [("_step_mom", len(leaves) - 1),
                                ("_step_mom_rows", 1)]
    moved = np.abs(emb.asnumpy() - before).max(axis=1) > 0
    assert moved.tolist() == [i in (1, 6) for i in range(8)]


def test_own_update_goes_per_index(programs):
    seen = []

    class Mine(mx.optimizer.SGD):
        def update(self, index, weight, grad, state):
            seen.append(index)
            super().update(index, weight, grad, state)

    _, trainer, backward, leaves = _small_trainer(
        Mine(learning_rate=0.1, momentum=0.9), params={})
    assert not trainer.optimizer._fuses()
    backward()
    trainer.step(4)
    assert seen == _indices(trainer) and len(seen) == len(leaves)
    assert programs == [("_step_mom", 1)] * len(leaves)


def test_sgld_goes_per_index_in_order(programs, monkeypatch):
    seen = []
    real = mx.optimizer.SGLD.update

    def update(self, index, weight, grad, state):
        seen.append(index)
        real(self, index, weight, grad, state)

    monkeypatch.setattr(mx.optimizer.SGLD, "update", update)
    mx.random.seed(3)
    net, trainer, backward, leaves = _small_trainer(
        "sgld", params={"learning_rate": 0.01})
    before = [p.data().asnumpy() for p in leaves]
    backward()
    trainer.step(4)
    assert seen == _indices(trainer) and programs == []
    assert all(np.abs(p.data().asnumpy() - b).max() > 0
               for p, b in zip(leaves, before))


def test_no_second_compilation_when_the_scalars_move(programs):
    _, trainer, backward, _ = _small_trainer("sgd")
    backward()
    trainer.step(4)
    program = trainer.optimizer._program()
    compiled = program._cache_size()
    for batch, lr in ((3, 0.05), (4, 0.2), (1, 0.01)):
        backward()
        trainer.set_learning_rate(lr)
        trainer.step(batch)
    assert program._cache_size() == compiled
    # and under a schedule that changes lr every step
    _, trainer, backward, _ = _small_trainer("adam", params={
        "learning_rate": 0.01,
        "lr_scheduler": mx.lr_scheduler.FactorScheduler(step=1, factor=0.5)})
    backward()
    trainer.step(4)
    program = trainer.optimizer._program()
    compiled = program._cache_size()
    lrs = []
    for _ in range(3):
        backward()
        trainer.step(4)
        lrs.append(trainer.learning_rate)
    assert len(set(lrs)) == 3
    assert program._cache_size() == compiled
    assert [n for _, n in programs] == [programs[0][1]] * len(programs)


def test_pre_update_weights_stay_readable():
    # weights are not donated: the health probe and the benchmark's
    # driver hold p.data()._data across a step. The optimizer's state is
    # (PR 27): only the Updater holds it, and it is updated in place
    _, trainer, backward, leaves = _small_trainer("sgd")
    backward()
    trainer.step(4)             # momentum exists from here on
    held = [(p.data()._data, p.data().asnumpy()) for p in leaves]
    moms = [trainer._updaters.states[i]._data
            for i in sorted(trainer._updaters.states)]
    backward()
    trainer.step(4)
    for (arr, was), p in zip(held, leaves):
        assert not arr.is_deleted()
        np.testing.assert_array_equal(np.asarray(arr), was)
        assert np.abs(p.data().asnumpy() - was).max() > 0
    assert all(m.is_deleted() for m in moms)
    assert all(np.isfinite(trainer._updaters.states[i].asnumpy()).all()
               for i in trainer._updaters.states)


def test_probe_update_ratio_is_the_per_leaf_loop_s():
    from mxnet_tpu.profiling import health
    health.reset()
    health.set_enabled(True)
    health.set_norms_enabled(True)
    try:
        net, trainer, backward, leaves = _small_trainer("sgd")
        start = [p.data().asnumpy() for p in leaves]
        backward()
        grads = [p.grad().asnumpy() for p in leaves]
        trainer.step(4)
        by_tree = health.flush()["norms"]["by_group"]
        assert len(by_tree) == 3 and all(
            0 < g["update_ratio"] for g in by_tree.values())
        # the loop Trainer._update used to be, on the same numbers
        health.reset()
        upd = mx.optimizer.get_updater(mx.optimizer.create(
            "sgd", learning_rate=0.1, momentum=0.9, rescale_grad=0.25))
        probe = health.step_probe()
        with health.updater_covered():
            for i, (p, w, g) in enumerate(zip(leaves, start, grads)):
                w, g = nd.array(w), nd.array(g)
                old = w._data
                upd(i, g, w)
                probe.add(p.name, w, g, weight_before=old)
        probe.commit()
        by_leaf = health.flush()["norms"]["by_group"]
        assert by_leaf.keys() == by_tree.keys()
        for grp in by_tree:
            for key in ("update_ratio", "weight_norm", "grad_norm"):
                assert by_tree[grp][key] == pytest.approx(
                    by_leaf[grp][key], rel=1e-5)
    finally:
        health.reset()
        health.set_enabled(True)
