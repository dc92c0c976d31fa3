"""The gluon Laguna decoder against the plain reference
(``benchmark/reference/laguna.py``) at a tiny size in float32: logits,
loss, every leaf's gradient, three Adam steps; each planted fault moves the
reference; YaRN's frequencies against their formula and the rotary op's
older callers unchanged; the shares of the expert layer, with the shared
expert counted once, add up to the uncut layer; the configuration's
keys."""
import json
import math
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops import nn as ops_nn

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import laguna as ref  # noqa: E402

# a leading dense full layer, a window layer and a full layer that route;
# 6, 9 and 6 heads over 3 K/V heads (groups of 2 and 3); YaRN over half
# the full layers' 16 lanes with a ramp that has all three parts (low 0,
# high 2); the sequence is over three windows long
ROPE = {"full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                           "factor": 8, "original_max_position_embeddings":
                           4096, "beta_slow": 1, "beta_fast": 32,
                           "attention_factor": 1.26,
                           "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}}
CFG = {"vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
       "num_hidden_layers": 3, "num_attention_heads": 6,
       "num_attention_heads_per_layer": [6, 9, 6],
       "num_key_value_heads": 3, "head_dim": 16, "rms_norm_eps": 1e-6,
       "num_experts": 16, "num_experts_per_tok": 3,
       "moe_intermediate_size": 32, "shared_expert_intermediate_size": 24,
       "norm_topk_prob": True, "tie_word_embeddings": False,
       "attention_bias": False, "gating": "per-head", "sliding_window": 12,
       "rope_parameters": ROPE,
       "layer_types": ["full_attention", "sliding_attention",
                       "full_attention"],
       "mlp_layer_types": ["dense", "sparse", "sparse"],
       "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
       "moe_apply_router_weight_on_input": False}
OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
       "wd": 0.0}
B, T = 2, 40
FAULTS = ["no_window", "no_yarn", "no_head_gate", "sigmoid_router",
          "no_routed_scale", "top9", "half_batch"]


def build(cfg=CFG, seed=5, held=None, dtype="float32"):
    """(net, {reference name: Parameter}, reference params)."""
    net = gluon.model_zoo.get_model("laguna", config=cfg, held=held,
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
    # the counter: the two sparse layers' every visit, once a step
    counts = net.expert_tokens.data().asnumpy()
    assert counts.shape == (2, 16) and counts.dtype == np.int32
    assert (counts.sum(1) == 3 * B * T * 3).all()


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_moves_the_reference(stepped, fault):
    """A fault the comparison could not see would guard nothing: each
    moves the loss and some leaf's gradient well past rounding."""
    ids, labels = jnp.asarray(stepped["ids"]), jnp.asarray(stepped["labels"])
    loss, grad, sels = ref.batch_grad(ref.make_grad(CFG, fault=fault),
                                      stepped["made"], ids, labels)
    assert abs(loss - stepped["want_loss"]) > 1e-7 * stepped["want_loss"]
    moved = max(
        float(jnp.linalg.norm(grad[n] - stepped["want_grad"][n]) /
              (jnp.linalg.norm(stepped["want_grad"][n]) + 1e-12))
        for n in ref.trainable(CFG))
    assert moved > 0.02, (fault, moved)
    assert len(sels) == 2
    assert sels[0].shape == (B * T, 3 - (fault == "top9"))


def test_the_faults_touch_what_their_names_say():
    made = ref.init_params(5, CFG)
    ids = jnp.asarray(batch()[0][0])
    base, sels = ref.forward(made, ids, CFG)
    diff = lambda fault: np.abs(np.asarray(
        ref.forward(made, ids, CFG, fault=fault)[0] - base)).max(1)
    # inside the first window a window layer sees every earlier key
    assert (diff("no_window")[:12] < 1e-5).all()
    assert diff("no_window")[12:].max() > 1e-3
    # position 0 sees one key, whatever its score's scale; later ones move
    assert diff("no_yarn")[0] < 1e-6 and diff("no_yarn")[1:].min() > 1e-6
    # a sigmoid's top-k is the softmax's: the same experts, other weights
    after = ref.forward(made, ids, CFG, fault="sigmoid_router")[1]
    assert (np.asarray(after[0]) == np.asarray(sels[0])).all()
    assert diff("sigmoid_router").max() > 1e-4


# -- the rotary op ------------------------------------------------------------

def test_yarn_frequencies_follow_the_formula():
    """At the published full layers' numbers (theta 5e5, 64 rotated lanes,
    8,192 original positions, beta 32 / 1) the ramp runs from frequency 9
    to 18; the op's rotation is the formula's, written out here in float64,
    the attention factor on cos and sin alike."""
    assert ops_nn.yarn_bounds(500000.0, 64, 8192, 32.0, 1.0) == (9, 18)
    c = lambda r: 64 * math.log(8192 / (2 * math.pi * r)) / \
        (2 * math.log(500000))
    assert (math.floor(c(32)), math.ceil(c(1))) == (9, 18)
    rope = dict(ROPE["full_attention"], factor=128,
                original_max_position_embeddings=8192,
                attention_factor=1.4852030263919618)
    from mxnet_tpu.gluon.model_zoo.text.laguna import rotary_attrs

    attrs = rotary_attrs(rope, 128)
    assert attrs == {"theta": 500000.0, "rotary_dim": 64,
                     "yarn_factor": 128.0, "yarn_original": 8192,
                     "beta_fast": 32.0, "beta_slow": 1.0,
                     "attention_factor": 1.4852030263919618}
    x = np.random.default_rng(1).standard_normal((1, 50, 2, 128))
    got = ops_nn.rotary_embedding(jnp.asarray(x, jnp.float32), **attrs)
    j = np.arange(32)
    f = 500000.0 ** (-2.0 * j / 64)
    e = 1 - np.clip((j - 9) / 9, 0, 1)
    freq = f * (1 - e) / 128 + f * e
    ang = np.arange(50)[:, None] * freq
    ang = np.concatenate([ang, ang], -1)[None, :, None]
    cos, sin = (f(ang) * 1.4852030263919618 for f in (np.cos, np.sin))
    y = x[..., :64]
    half = np.concatenate([-y[..., 32:], y[..., :32]], -1)
    want = np.concatenate([y * cos + half * sin, x[..., 64:]], -1)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    # the reference writes the same frequencies out on its own
    r, freq_ref, factor = ref.frequencies(rope, 128)
    assert r == 64 and factor == 1.4852030263919618
    np.testing.assert_allclose(np.asarray(freq_ref), freq, rtol=1e-6)


def _rotary_before_yarn(data, theta=10000.0, offset=0, rotary_dim=0):
    """The op as it was before YaRN's parameters, line for line."""
    t, d = data.shape[1], rotary_dim or data.shape[3]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv_freq
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    x = data[..., :d].astype(jnp.float32)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    out = (x * jnp.cos(ang) + half * jnp.sin(ang)).astype(data.dtype)
    if d == data.shape[3]:
        return out
    return jnp.concatenate([out, data[..., d:]], axis=-1)


@pytest.mark.parametrize("attrs,dtype", [
    ({"theta": 1.5e6}, jnp.bfloat16),                  # smallthinker
    ({"theta": 1e7, "rotary_dim": 64}, jnp.bfloat16),  # qwen3_next
    ({"theta": 1e6}, jnp.float32),                     # lfm2_moe
    ({"theta": 1e4, "offset": 7, "rotary_dim": 8}, jnp.float32)])
def test_existing_rotary_callers_give_the_same_numbers_bit_for_bit(attrs,
                                                                   dtype):
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 33, 3, 128)),
                    dtype)
    got = jax.jit(lambda a: ops_nn.rotary_embedding(a, **attrs))(x)
    want = jax.jit(lambda a: _rotary_before_yarn(a, **attrs))(x)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


# -- the expert layer's shares ------------------------------------------------

@pytest.mark.parametrize("shares", [4, 2])
def test_the_shares_routed_parts_and_the_shared_expert_once_add_up(shares):
    """The 16 experts in shares of 16 / ``shares``: each share routes over
    all 16 and multiplies by those it holds beside the whole shared
    expert; the shares' routed parts plus the shared expert counted once
    are the reference's uncut layer."""
    from mxnet_tpu.gluon.model_zoo.text.laguna import SharedSparseExperts

    made = ref.init_params(7, CFG)
    rng = np.random.default_rng(2)
    n = jnp.asarray(rng.standard_normal((2, 24, 64)), jnp.float32)
    flat = n.reshape(-1, 64)
    routed, sel = ref.routed_ff(made, "layer1.", flat, CFG, None, (0, 16))
    shared = ref.shared_ff(made, "layer1.", flat)
    router = {"experts": 16, "k": 3, "norm_topk": True, "scale": 2.5,
              "use_bias": False, "score": "softmax"}
    per, outs = 16 // shares, []
    for s in range(shares):
        layer = SharedSparseExperts(64, 32, 24, (s * per, per), router,
                                    "float32", prefix="moe%d_" % s)
        names = ["w1", "w3", "w2", "router", "shared.w1", "shared.w3",
                 "shared.w2"]
        for name, p in zip(names, layer.collect_params().values()):
            value = made["layer1.moe." + name]
            if name in ("w1", "w3", "w2"):
                value = value[s * per:(s + 1) * per]
            assert tuple(p.shape) == value.shape, name
            p.set_data(NDArray(value))
        out, counts = layer(NDArray(n))
        outs.append(out._data.reshape(-1, 64))
        np.testing.assert_array_equal(
            counts._data, np.bincount(np.asarray(sel).ravel(), minlength=16))
    np.testing.assert_allclose(sum(outs) - (shares - 1) * shared,
                               routed + shared, rtol=2e-5, atol=2e-6)
    # a share alone is the reference given the same share
    want, _ = ref.routed_ff(made, "layer1.", flat, CFG, None, (0, per))
    np.testing.assert_allclose(outs[0], want + shared, rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(outs[0] - routed - shared).max()) > 1e-4


@pytest.mark.parametrize("held", [(0, 4), (12, 4)])
def test_a_share_of_the_model_equals_the_reference_given_the_same_share(held):
    cfg = dict(CFG, num_experts=held[1], published_num_experts=16)
    net, _, made = build(cfg, seed=6, held=held)
    ids, _ = batch(2)
    got = net(mx.nd.array(ids)).asnumpy()
    rcfg = dict(cfg, held=list(held))
    for b in range(B):
        want, _ = ref.forward(made, jnp.asarray(ids[b]), rcfg)
        np.testing.assert_allclose(got[b], want, rtol=1e-4, atol=1e-5)


# -- the configuration ---------------------------------------------------------

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
    net = gluon.model_zoo.get_model("laguna", config=CFG)
    assert isinstance(net, gluon.model_zoo.text.Laguna)
    assert [layer.attn._window for layer in net.layers] == [0, 12, 0]
    assert [layer._scope for layer in net.layers] == [
        "laguna.attn.full", "laguna.attn.window", "laguna.attn.full"]
    assert [layer.sparse for layer in net.layers] == [False, True, True]
    assert [layer.attn.q_proj.shape[0] // 16 for layer in net.layers] == \
        [6, 9, 6]
    assert net.layers[0].attn._rope["yarn_factor"] == 8.0
    assert net.layers[1].attn._rope == {"theta": 10000.0, "rotary_dim": 16}
    router = net.layers[1].ff.router._attrs
    assert router["score"] == "softmax" and router["scale"] == 2.5
    assert router["k"] == 3 and net.layers[1].ff.routed._act == "silu"
    for bad in ({"tie_word_embeddings": True}, {"attention_bias": True},
                {"moe_router_logit_softcapping": 30.0},
                {"moe_apply_router_weight_on_input": True},
                {"gating": "per-channel"},
                {"mlp_layer_types": ["dense", "sparse"]},
                {"layer_types": ["full_attention", "chunked", "full"]}):
        with pytest.raises(mx.MXNetError):
            gluon.model_zoo.text.laguna(dict(CFG, **bad))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.laguna(dict(CFG, rope_parameters=dict(
            ROPE, full_attention=dict(ROPE["full_attention"],
                                      rope_type="longrope"))))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.laguna(CFG, held=(12, 8))
    names = list(net.collect_params())
    assert len(names) == len(ref.leaves(CFG)) + 1     # + the counter
    assert net.head is not net.embed and net.head.shape == (96, 64)


def test_the_benchmark_s_configuration_builds_at_its_published_widths():
    """``benchmark/configs/laguna-s-2.1.json``: 811,017,216 parameters on
    this share, none allocated here."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-s-2.1.json")) as f:
        cfg = json.load(f)
    net = gluon.model_zoo.get_model("laguna", config=cfg,
                                    held=cfg["held"], dtype=cfg["dtype"])
    shapes = {n: p.shape for n, p in net.collect_params().items()
              if not n.endswith("expert_tokens")}
    assert sum(int(np.prod(s)) for s in shapes.values()) == \
        cfg["parameters"] == 811017216
    assert [tuple(s) for _, s, _ in ref.leaves(cfg)] == \
        [tuple(s) for s in shapes.values()]
    assert cfg["parameter_bytes_16_per_parameter"] == 16 * cfg["parameters"]
    assert net.expert_tokens.shape == (4, 256)
    # what the accepted readers read repeats the model's own keys
    assert cfg["num_experts"] == cfg["held"][1] == 8
    assert cfg["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert cfg["num_dense_layers"] == cfg["mlp_layer_types"].count("dense")
    assert cfg["mlp_only_layers"] == [0]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 5
    assert set(cfg["reduced"]) <= set(cfg["published"])


def test_reference_weights_start_on_the_storage_grid():
    made = ref.init_params(12, CFG)
    for name, _, kind in ref.leaves(CFG):
        v = np.asarray(made[name])
        assert v.dtype == np.float32
        assert (v == np.asarray(made[name].astype(jnp.bfloat16)
                                .astype(jnp.float32))).all(), name
        if kind == "norm1":
            assert abs(v.mean() - 1.0) < 0.05


def test_the_gradient_a_backward_replaces_is_let_go_before_it_runs(
        monkeypatch):
    """``autograd.backward`` lets go of each ``write`` leaf's old gradient
    before the backward program is traced and run, and nothing else holds
    it after the optimizer's step: the device can give its memory to the
    new one (the Laguna-S-2.1 step fits on a chip only so). The NDArray a
    parameter hands out stays the same object and takes the new value."""
    import gc
    import weakref

    net, leaves, _ = build(seed=4, dtype="bfloat16")
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            dict(OPT, multi_precision=True))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    ids, labels = batch()
    old, gone = {}, []
    vjp = jax.vjp

    def watched(*args, **kwargs):         # the backward's first act
        if old and not gone:
            gc.collect()
            gone.append(all(r() is None for r in old.values()))
        return vjp(*args, **kwargs)

    monkeypatch.setattr(jax, "vjp", watched)
    for step in range(2):
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(ids)),
                           mx.nd.array(labels.astype(np.float32)))
        if step:
            grads = {n: leaves[n].grad() for n in leaves}
            old = {n: weakref.ref(g._data) for n, g in grads.items()}
        loss.backward()
        trainer.step(B)
        mx.nd.waitall()
    assert gone == [True]
    for n, p in leaves.items():
        assert p.grad() is grads[n] and p.grad()._data is not None
        assert np.isfinite(p.grad().asnumpy().astype(np.float32)).all()
