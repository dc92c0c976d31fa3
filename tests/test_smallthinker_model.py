"""The gluon SmallThinker decoder against the plain reference
(``benchmark/reference/smallthinker.py``) at a tiny size in float32: logits,
loss, every leaf's gradient, three Adam steps; each planted fault moves the
reference; the shares of the expert layer add up to the uncut layer; the
configuration's keys."""
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

from benchmark.reference import smallthinker as ref  # noqa: E402

# one period: a full layer without positions, three window layers with
# rotary positions; 14 heads over 2 K/V heads are groups of 7; the
# sequence is nearly three windows long
CFG = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 4,
       "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
       "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
       "sliding_window_size": 24, "rope_theta": 1.5e6, "rope_scaling": None,
       "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 16,
       "moe_num_active_primary_experts": 3,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "tie_word_embeddings": False}
OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
       "wd": 0.0}
B, T = 2, 70
FAULTS = ["no_window", "rope_all", "router_after", "silu_experts", "top5",
          "half_batch"]


def build(cfg=CFG, seed=5, held=None, dtype="float32"):
    """(net, {reference name: Parameter}, reference params)."""
    net = gluon.model_zoo.get_model("smallthinker", config=cfg, held=held,
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
            "labels": labels, "logits": logits,
            "loss": float(loss.mean().asscalar()),
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
    want = np.asarray(stepped["want_grad"][name])
    np.testing.assert_allclose(got, want, rtol=2e-3,
                               atol=1e-5 * np.abs(want).max() + 1e-9)


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
                                   rtol=0.05, atol=3e-5, err_msg=n)
    # the counter: every visit of every layer, once a step
    counts = net.expert_tokens.data().asnumpy()
    assert counts.shape == (4, 16) and counts.dtype == np.int32
    assert (counts.sum(1) == 3 * B * T * 3).all()


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_moves_the_reference(stepped, fault):
    """A fault the comparison could not see would guard nothing: each
    moves the loss and some leaf's gradient well past rounding (at 64
    wide attention adds a hundredth to the residual stream, so the loss
    moves little; the attention's own gradients move whole)."""
    ids, labels = jnp.asarray(stepped["ids"]), jnp.asarray(stepped["labels"])
    loss, grad, sels = ref.batch_grad(ref.make_grad(CFG, fault=fault),
                                      stepped["made"], ids, labels)
    assert abs(loss - stepped["want_loss"]) > 1e-7 * stepped["want_loss"]
    moved = max(
        float(jnp.linalg.norm(grad[n] - stepped["want_grad"][n]) /
              (jnp.linalg.norm(stepped["want_grad"][n]) + 1e-12))
        for n in ref.trainable(CFG))
    assert moved > 0.02, (fault, moved)
    assert sels[0].shape == (B * T, 3 - (fault == "top5"))


def test_the_faults_touch_what_their_names_say():
    made = ref.init_params(5, CFG)
    ids = jnp.asarray(batch()[0][0])
    base, sels = ref.forward(made, ids, CFG)
    diff = lambda fault: np.abs(np.asarray(
        ref.forward(made, ids, CFG, fault=fault)[0] - base)).max(1)
    # inside the first window a window layer sees every earlier key
    assert (diff("no_window")[:24] < 1e-5).all()
    assert diff("no_window")[24:].max() > 1e-3
    # position 0 is not rotated whatever the layout
    assert diff("rope_all")[0] < 1e-5 and diff("rope_all")[1:].max() > 1e-4
    # the first layer's router reads the same array either way only if
    # attention did nothing: its selections differ
    after = ref.forward(made, ids, CFG, fault="router_after")[1]
    assert (np.asarray(after[0]) != np.asarray(sels[0])).any()
    assert ref.seen(5, 2).tolist() == [
        [True, False, False, False, False], [True, True, False, False, False],
        [False, True, True, False, False], [False, False, True, True, False],
        [False, False, False, True, True]]


# -- the expert layer's shares --------------------------------------------------

@pytest.mark.parametrize("shares", [4, 16, 2])
def test_the_shares_routed_parts_add_up_to_the_uncut_layer(shares):
    """The deployment's four shares (and other cuts): each routes on the
    router's input over all 16 experts and multiplies the experts' input
    by those it holds; the parts add up to the reference's uncut layer."""
    from mxnet_tpu.gluon.model_zoo.text.lfm2_moe import SparseExperts

    made = ref.init_params(7, CFG)
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    r = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    flat, flat_r = n.reshape(-1, 64), r.reshape(-1, 64)
    whole, sel = ref.routed_ff(made, "layer0.", flat_r, flat, CFG, None,
                               (0, 16))
    router = {"experts": 16, "k": 3, "norm_topk": True, "scale": 1.0,
              "use_bias": False, "score": "softmax"}
    per, parts = 16 // shares, []
    for s in range(shares):
        layer = SparseExperts(64, 32, (s * per, per), router, "float32",
                              act="relu", prefix="moe%d_" % s)
        sl = slice(s * per, (s + 1) * per)
        for name, p in zip(["w1", "w3", "w2", "router"],
                           layer.collect_params().values()):
            value = made["layer0.moe." + name]
            if name != "router":
                value = value[sl]
            assert tuple(p.shape) == value.shape, name
            p.set_data(NDArray(value))
        out, counts = layer(NDArray(n), NDArray(r))
        parts.append(out._data.reshape(-1, 64))
        np.testing.assert_array_equal(
            counts._data, np.bincount(np.asarray(sel).ravel(), minlength=16))
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-6)
    # a share alone is the reference given the same share
    want, _ = ref.routed_ff(made, "layer0.", flat_r, flat, CFG, None,
                            (0, per))
    np.testing.assert_allclose(parts[0], want, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-4 or shares == 1


@pytest.mark.parametrize("held", [(0, 4), (12, 4)])
def test_a_share_of_the_model_equals_the_reference_given_the_same_share(held):
    cfg = dict(CFG, moe_num_primary_experts=held[1],
               published_num_experts=16)
    net, _, made = build(cfg, seed=6, held=held)
    ids, _ = batch(2)
    got = net(mx.nd.array(ids)).asnumpy()
    rcfg = dict(cfg, held=list(held))
    for b in range(B):
        want, _ = ref.forward(made, jnp.asarray(ids[b]), rcfg)
        np.testing.assert_allclose(got[b], want, rtol=1e-4, atol=1e-5)


# -- the configuration -----------------------------------------------------------

def test_bfloat16_leaves_under_multi_precision_adam():
    net, leaves, _ = build(seed=8, dtype="bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            dict(OPT, multi_precision=True))
    ids, labels = batch()
    for _ in range(2):
        with autograd.record():
            logits = net(mx.nd.array(ids))
            loss = gluon.loss.SoftmaxCrossEntropyLoss()(
                logits, mx.nd.array(labels.astype(np.float32)))
        loss.backward()
        trainer.step(B)
    assert logits.dtype == np.float32
    assert np.isfinite(float(loss.mean().asscalar()))
    index = {p.name: i for i, p in
             enumerate(net.collect_params().values())}
    for n in ref.trainable(CFG):
        p = leaves[n]
        assert p.data().dtype == jnp.bfloat16 and \
            p.grad().dtype == jnp.bfloat16
        master, (mean, var) = trainer._updaters.states[index[p.name]]
        assert master.dtype == mean.dtype == var.dtype == np.float32


def test_published_keys_are_read_and_the_zoo_finds_the_model():
    net = gluon.model_zoo.get_model("smallthinker", config=CFG)
    assert isinstance(net, gluon.model_zoo.text.SmallThinker)
    assert [(layer.attn._rotary, layer.attn._window)
            for layer in net.layers] == [(False, 0)] + [(True, 24)] * 3
    assert [layer._scope for layer in net.layers] == \
        ["smallthinker.attn.full"] + ["smallthinker.attn.window"] * 3
    assert all(layer.sparse and layer.ff._act == "relu" and
               layer.ff.router._attrs["score"] == "softmax"
               for layer in net.layers)
    other = gluon.model_zoo.text.smallthinker(dict(
        CFG, num_hidden_layers=2, rope_layout=[1, 0],
        sliding_window_layout=[0, 1],
        moe_primary_router_apply_softmax=False))
    assert [(layer.attn._rotary, layer.attn._window)
            for layer in other.layers] == [(True, 0), (False, 24)]
    assert other.layers[0].ff.router._attrs["score"] == "sigmoid"
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.smallthinker(dict(CFG, rope_layout=[0, 1]))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.smallthinker(dict(CFG,
                                               tie_word_embeddings=True))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.smallthinker(CFG, held=(12, 8))
    names = list(net.collect_params())
    assert len(names) == len(ref.leaves(CFG)) + 1     # + the counter
    # the head is untied: a leaf of its own
    assert net.head is not net.embed and net.head.shape == (96, 64)


def test_the_benchmark_s_configuration_builds_at_its_published_widths():
    """``benchmark/configs/smallthinker-21b-a3b.json``: 559,290,880
    parameters on this share, none allocated here."""
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        cfg = json.load(f)
    net = gluon.model_zoo.get_model("smallthinker", config=cfg,
                                    held=cfg["held"], dtype=cfg["dtype"])
    shapes = {n: p.shape for n, p in net.collect_params().items()
              if not n.endswith("expert_tokens")}
    assert sum(int(np.prod(s)) for s in shapes.values()) == \
        cfg["parameters"] == 559290880
    assert [tuple(s) for _, s, _ in ref.leaves(cfg)] == \
        [tuple(s) for s in shapes.values()]
    assert cfg["parameter_bytes_16_per_parameter"] == 16 * cfg["parameters"]
    assert net.expert_tokens.shape == (4, 64)
    # what the accepted readers read repeats the model's own keys
    assert cfg["num_experts"] == cfg["moe_num_primary_experts"] == \
        cfg["held"][1]
    assert cfg["num_experts_per_tok"] == \
        cfg["moe_num_active_primary_experts"]
    assert cfg["moe_intermediate_size"] == cfg["moe_ffn_hidden_size"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"]


def test_reference_weights_start_on_the_storage_grid():
    made = ref.init_params(12, CFG)
    for name, _, kind in ref.leaves(CFG):
        v = np.asarray(made[name])
        assert v.dtype == np.float32
        assert (v == np.asarray(made[name].astype(jnp.bfloat16)
                                .astype(jnp.float32))).all(), name
        if kind == "norm1":
            assert abs(v.mean() - 1.0) < 0.05
