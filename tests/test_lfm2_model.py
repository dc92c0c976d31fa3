"""The gluon LFM2-MoE decoder against the plain reference
(``benchmark/reference/lfm2_moe.py``) at a tiny size in float32: logits,
loss, every leaf's gradient, three Adam steps; the configuration's keys;
the tied head; the buffer that takes no gradient; the counter."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.ndarray import NDArray

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.lib import flops_lm  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402

CFG = {"vocab_size": 96, "hidden_size": 64,
       "layer_types": ["conv", "full_attention", "conv"],
       "num_dense_layers": 1, "intermediate_size": 96,
       "moe_intermediate_size": 48, "num_experts": 8,
       "num_experts_per_tok": 2, "num_attention_heads": 2,
       "num_key_value_heads": 1, "head_dim": 32, "conv_L_cache": 3,
       "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
       "use_expert_bias": True, "routed_scaling_factor": 1.0,
       "rope_parameters": {"rope_theta": 1000000.0}}
OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
       "wd": 0.0}
LEAVES = [n for n, _, _ in ref.leaves(CFG)]
B, T = 2, 32


def build(cfg=CFG, seed=5, held=None, dtype="float32"):
    """(net, {reference name: Parameter}, reference params)."""
    net = gluon.model_zoo.get_model("lfm2_moe", config=cfg, held=held,
                                    dtype=dtype)
    rcfg = dict(cfg, held=list(held)) if held else cfg
    made = ref.init_params(seed, rcfg)
    leaves = [p for n, p in net.collect_params().items()
              if not n.endswith("expert_tokens")]
    spec = ref.leaves(rcfg)
    assert len(spec) == len(leaves)
    for (name, shape, _), p in zip(spec, leaves):
        assert tuple(p.shape) == tuple(shape), (name, p.name)
        p.set_data(NDArray(made[name].astype(p.dtype)))
    net.initialize()
    net.hybridize()
    return net, dict(zip([n for n, _, _ in spec], leaves)), made


def batch(seed=0):
    ids = np.random.default_rng(seed).integers(0, 96, (B, T)).astype(np.int32)
    return ids, np.roll(ids, -1, 1)


@pytest.fixture(scope="module")
def stepped():
    """One recorded step of the program and the reference's gradient."""
    net, leaves, made = build()
    ids, labels = batch()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    with autograd.record():
        logits = net(mx.nd.array(ids))
        loss = loss_fn(logits, mx.nd.array(labels.astype(np.float32)))
    loss.backward()
    want_loss, want_grad, _ = ref.batch_grad(
        ref.make_grad(CFG), made, jnp.asarray(ids), jnp.asarray(labels))
    return {"net": net, "leaves": leaves, "made": made, "ids": ids,
            "logits": logits, "loss": float(loss.mean().asscalar()),
            "want_loss": want_loss, "want_grad": want_grad}


def test_logits_and_loss(stepped):
    assert stepped["logits"].dtype == np.float32
    for b in range(B):
        want, _ = ref.forward(stepped["made"],
                              jnp.asarray(stepped["ids"][b]), CFG)
        np.testing.assert_allclose(stepped["logits"].asnumpy()[b], want,
                                   rtol=1e-4, atol=1e-5)
    assert stepped["loss"] == pytest.approx(stepped["want_loss"], rel=1e-5)


@pytest.mark.parametrize("name", ref.trainable(CFG))
def test_gradient_of_every_leaf(stepped, name):
    got = stepped["leaves"][name].grad().asnumpy() / B
    np.testing.assert_allclose(got, stepped["want_grad"][name], rtol=2e-3,
                               atol=1e-7)


def test_three_adam_steps():
    net, leaves, params = build(seed=9)
    trainer = gluon.Trainer(net.collect_params(), "adam", dict(OPT))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    grad, adam = ref.make_grad(CFG), ref.make_adam(OPT)
    names = ref.trainable(CFG)
    m, v = ({n: jnp.zeros_like(params[n]) for n in names} for _ in range(2))
    for i in range(3):
        ids, labels = batch(i)
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(ids)),
                           mx.nd.array(labels.astype(np.float32)))
        loss.backward()
        trainer.step(B)
        want, g, _ = ref.batch_grad(grad, params, jnp.asarray(ids),
                                    jnp.asarray(labels))
        assert float(loss.mean().asscalar()) == pytest.approx(want, rel=2e-5)
        params, m, v = adam(params, g, m, v, float(i + 1))
    begin = ref.init_params(9, CFG)
    for n in names:
        start = np.asarray(begin[n])
        np.testing.assert_allclose(leaves[n].data().asnumpy() - start,
                                   np.asarray(params[n]) - start,
                                   rtol=0.05, atol=2e-5, err_msg=n)


def test_the_buffer_takes_no_gradient_and_no_step():
    net, leaves, _ = build(seed=3)
    bias = leaves["layer1.moe.expert_bias"]
    assert bias.grad_req == "null" and bias.dtype == "float32"
    before = bias.data().asnumpy().copy()
    trainer = gluon.Trainer(net.collect_params(), "adam", dict(OPT))
    ids, labels = batch()
    with autograd.record():
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(
            net(mx.nd.array(ids)), mx.nd.array(labels.astype(np.float32)))
    loss.backward()
    trainer.step(B)
    np.testing.assert_array_equal(bias.data().asnumpy(), before)
    index = {p.name: i for i, p in
             enumerate(net.collect_params().values())}
    assert index[bias.name] not in trainer._updaters.states
    assert index[net.expert_tokens.name] not in trainer._updaters.states
    with pytest.raises(mx.MXNetError):
        bias.grad()


def test_counter_counts_every_visit_once_a_step():
    net, _, _ = build(seed=4)
    ids, labels = batch()
    read = lambda: net.expert_tokens.data().asnumpy()
    assert read().sum() == 0
    for step in (1, 2):
        with autograd.record():
            loss = gluon.loss.SoftmaxCrossEntropyLoss()(
                net(mx.nd.array(ids)),
                mx.nd.array(labels.astype(np.float32)))
        loss.backward()         # the forward runs again inside: counted once
        counts = read()
        assert counts.shape == (2, 8) and counts.dtype == np.int32
        assert (counts.sum(1) == step * B * T * 2).all()


@pytest.mark.parametrize("held", [(0, 4), (4, 4), (6, 2)])
def test_a_share_of_the_model_equals_the_reference_given_the_same_share(held):
    cfg = dict(CFG, num_experts=held[1], published_num_experts=8)
    net, _, made = build(cfg, seed=6, held=held)
    ids, _ = batch(2)
    got = net(mx.nd.array(ids)).asnumpy()
    rcfg = dict(cfg, held=list(held))
    for b in range(B):
        want, _ = ref.forward(made, jnp.asarray(ids[b]), rcfg)
        np.testing.assert_allclose(got[b], want, rtol=1e-4, atol=1e-5)


def test_bfloat16_leaves_under_multi_precision_adam():
    net, leaves, _ = build(seed=8, dtype="bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            dict(OPT, multi_precision=True))
    ids, labels = batch()
    start = {n: p.data().asnumpy().astype(np.float32)
             for n, p in leaves.items()}
    for _ in range(2):
        with autograd.record():
            logits = net(mx.nd.array(ids))
            loss = gluon.loss.SoftmaxCrossEntropyLoss()(
                logits, mx.nd.array(labels.astype(np.float32)))
        loss.backward()
        trainer.step(B)
    assert logits.dtype == np.float32
    index = {p.name: i for i, p in
             enumerate(net.collect_params().values())}
    for n in ref.trainable(CFG):
        p = leaves[n]
        assert p.data().dtype == jnp.bfloat16 and \
            p.grad().dtype == jnp.bfloat16
        master, (mean, var) = trainer._updaters.states[index[p.name]]
        assert master.dtype == mean.dtype == var.dtype == np.float32
        # the master moved by about 2 x lr where the 16-bit weight may
        # not have moved at all
        moved = np.abs(master.asnumpy() - start[n]).max()
        assert 1e-4 < moved < 1e-3, (n, moved)


def test_published_keys_are_read_and_the_zoo_finds_the_model():
    assert isinstance(gluon.model_zoo.get_model("lfm2_moe", config=CFG),
                      gluon.model_zoo.text.LFM2MoE)
    assert gluon.model_zoo.get_model("resnet18_v1") is not None
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.lfm2_moe(dict(CFG, conv_bias=True))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.lfm2_moe(CFG, held=(6, 4))
    with pytest.raises(ValueError):
        gluon.model_zoo.text.get_model("no_such_model")
    flat = dict(CFG, rope_theta=1e6)
    del flat["rope_parameters"]
    net = gluon.model_zoo.text.lfm2_moe(flat)
    names = [n for n in net.collect_params()]
    assert len(names) == len(LEAVES) + 1        # + the counter's buffer
    assert net.collect_params()[names[0]].shape == (96, 64)


def test_embedding_and_dense_share_one_parameter_under_hybridize():
    """The tied head of the usual gluon kind: ``Dense(params=
    embedding.params)`` under one hybridized parent is ONE leaf, and its
    gradient is the sum of both uses."""
    class Tied(gluon.HybridBlock):
        def __init__(self):
            super().__init__()
            with self.name_scope():
                self.embed = gluon.nn.Embedding(12, 8, prefix="tok_")
                self.head = gluon.nn.Dense(12, use_bias=False, in_units=8,
                                           flatten=False, prefix="tok_",
                                           params=self.embed.params)

        def hybrid_forward(self, F, ids):
            return self.head(self.embed(ids))

    net = Tied()
    net.initialize(mx.init.Normal(0.5))
    net.hybridize()
    assert len(list(net.collect_params().keys())) == 1
    w = net.embed.weight
    assert net.head.weight is w
    ids = mx.nd.array(np.array([[1, 2, 3, 1]], np.int32))
    with autograd.record():
        loss = (net(ids) ** 2).sum()
    loss.backward()
    e = jnp.asarray(w.data().asnumpy())
    want = jax.grad(lambda e: ((e[jnp.asarray([1, 2, 3, 1])] @ e.T) ** 2)
                    .sum())(e)
    np.testing.assert_allclose(w.grad().asnumpy(), want, rtol=1e-4,
                               atol=1e-5)


# -- lib/flops_lm against a hand count ----------------------------------------

def test_train_flops_against_a_hand_count():
    """LFM2-24B-A2B's cut: 186.1 M multiply-accumulates a token forward."""
    cfg = {"vocab_size": 8192, "hidden_size": 2048, "num_dense_layers": 1,
           "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
           "intermediate_size": 11776, "moe_intermediate_size": 1536,
           "num_experts": 8, "published_num_experts": 64, "held": [0, 8],
           "num_experts_per_tok": 4, "num_attention_heads": 32,
           "num_key_value_heads": 8, "conv_L_cache": 3}
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048            # 16,783,360
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512                 # 10,485,760
    dense = 3 * 2048 * 11776                                # 72,351,744
    expert_layer = 2048 * 64 + (4 * 8 / 64) * 3 * 2048 * 1536
    head = 2048 * 8192
    per_token = 4 * conv + attn + dense + 4 * expert_layer + head
    assert flops_lm.token_macs(cfg) == per_token == 186146816.0
    seq = 4096
    attention = 2 * 32 * 64 * seq * (seq + 1) // 2
    assert flops_lm.attention_macs(cfg, seq) == attention
    assert flops_lm.attention_fwd_flops(cfg, 2, seq) == 4 * attention
    assert flops_lm.train_flops(cfg, 2, seq) == \
        6 * 2 * (seq * per_token + attention)
    # 9.6 TFLOP a step at 8,192 tokens
    assert flops_lm.train_flops(cfg, 2, seq) == pytest.approx(9.56e12,
                                                              rel=5e-3)


# -- what the model leans on in gluon ------------------------------------------

def test_recompute_keeps_the_input_alone_and_gives_the_same_gradient():
    class Net(gluon.HybridBlock):
        def __init__(self, remat):
            super().__init__()
            self._remat = remat
            with self.name_scope():
                self.a = gluon.nn.Dense(32, in_units=8, flatten=False)
                self.b = gluon.nn.Dense(8, in_units=32, flatten=False)

        def hybrid_forward(self, F, x):
            inner = lambda v: self.b(F.Activation(self.a(v),
                                                  act_type="tanh"))
            if self._remat:
                return x + gluon.utils.recompute(inner, x)
            return x + inner(x)

    x = mx.nd.array(np.random.default_rng(0).standard_normal((4, 6, 8))
                    .astype(np.float32))
    grads, kept = [], []
    for remat in (False, True):
        net = Net(remat)
        net.initialize(mx.init.Xavier(rnd_type="gaussian"))
        if grads:
            for p, q in zip(net.collect_params().values(), first):
                p.set_data(q.data())
        first = list(net.collect_params().values())
        net.hybridize()
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        grads.append([p.grad().asnumpy() for p in first])
        jfn = next(iter(net._cached_jit.values()))[0]
        pv = tuple(p.data()._data for _, p in net._cached_plist)
        _, vjp = jax.vjp(lambda pv: jfn(pv, jax.random.PRNGKey(0),
                                        x._data)[0][0], pv)
        kept.append(sum(l.size for l in jax.tree_util.tree_leaves(vjp)
                        if hasattr(l, "size")))
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    assert kept[1] < kept[0]            # the 32-wide activation is not kept
    # outside a trace it is the plain call
    np.testing.assert_array_equal(
        gluon.utils.recompute(lambda v: v * 2, x).asnumpy(),
        (x * 2).asnumpy())


# -- the benchmark's harness on the tiny language-model cell -------------------

def test_harness_runs_the_tiny_lm_cell_end_to_end(tmp_path):
    """``benchmark/run.py`` on ``benchmark/tests/tiny_lm`` with the look
    for a chip left out: the driver builds the model from its
    configuration, trains it through gluon, and the last line says
    ``correct`` against the plain reference with nothing compiled inside
    the window. (``benchmark/tests/test_harness_lm.py`` holds the traced
    run, the controls and the planted faults.)"""
    import io
    import json
    import shutil

    from benchmark import run as harness

    bench_dir = os.path.join(REPO, "benchmark")
    tiny = os.path.join(bench_dir, "tests", "tiny_lm")
    root = tmp_path / "root"
    (root / "benchmark").mkdir(parents=True)
    shutil.copy(os.path.join(tiny, "BENCHMARK.json"), root)
    for d in ("configs", "workloads"):
        shutil.copytree(os.path.join(tiny, d), root / "benchmark" / d)
    for d in ("drivers", "lib", "reference", "layer_metrics"):
        os.symlink(os.path.join(bench_dir, d), root / "benchmark" / d)
    os.symlink(os.path.join(REPO, "mxnet_tpu"), root / "mxnet_tpu")
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(str(root), "tiny_lm_train", 2 ** 31 + 5, 1.5, 0,
                          gate=False, peaks_kind="TPU v5 lite", out=out,
                          err=err)
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert rc == 0 and result["correct"], err.getvalue()
    assert set(result["metrics"]) == {"train_img_per_s", "setup_s"}
    assert result["checks"]["window_compiles"]["value"] == 0
    assert result["checks"]["move_gap"]["value"] < 1e-3


def test_reference_weights_start_on_the_storage_grid():
    """Both sides start equal only if the reference's float32 weights are
    exactly what the program's 16-bit ones hold."""
    made = ref.init_params(12, CFG)
    for name, _, kind in ref.leaves(CFG):
        v = np.asarray(made[name])
        assert v.dtype == np.float32
        rounded = np.asarray(made[name].astype(jnp.bfloat16)
                             .astype(jnp.float32))
        assert (v == rounded).all() == (kind != "bias"), name
