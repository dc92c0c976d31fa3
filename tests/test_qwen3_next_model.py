"""The gluon Qwen3-Next decoder against the plain reference
(``benchmark/reference/qwen3_next.py``) at a tiny size in float32: logits,
loss, every leaf's gradient, three Adam steps; the chunked rule against the
token recurrence; the partial rotary encoding; the shares of the expert
layer with the shared expert counted once; the configuration's keys."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.ops.nn import gated_delta_rule

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.reference import qwen3_next as ref  # noqa: E402

CFG = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 4,
       "full_attention_interval": 4, "num_attention_heads": 4,
       "num_key_value_heads": 1, "head_dim": 32,
       "partial_rotary_factor": 0.25, "rope_theta": 1e7,
       "linear_num_key_heads": 2, "linear_num_value_heads": 4,
       "linear_key_head_dim": 16, "linear_value_head_dim": 16,
       "linear_conv_kernel_dim": 4, "moe_intermediate_size": 32,
       "shared_expert_intermediate_size": 32, "num_experts": 16,
       "num_experts_per_tok": 3, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "decoder_sparse_step": 1,
       "mlp_only_layers": []}
OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.95, "epsilon": 1e-8,
       "wd": 0.0}
B, T = 2, 70            # 70 tokens are no multiple of the rule's chunk


def build(cfg=CFG, seed=5, held=None, dtype="float32"):
    """(net, {reference name: Parameter}, reference params)."""
    net = gluon.model_zoo.get_model("qwen3_next", config=cfg, held=held,
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


# -- the chunked rule against the token recurrence ------------------------------

def _rule_inputs(t, g_scale, seed=0, b=2, hk=2, hv=4, dk=16, dv=8):
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (arr(b, t, hk, dk), arr(b, t, hk, dk), arr(b, t, hv, dv),
            -g_scale * jnp.asarray(rng.random((b, t, hv)), jnp.float32),
            jnp.asarray(rng.random((b, t, hv)), jnp.float32))


def _recurrence(q, k, v, g, beta):
    hv, dk = v.shape[2], q.shape[3]
    unit = lambda x: x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q) / dk ** 0.5, hv // q.shape[2], axis=2)
    k = jnp.repeat(unit(k), hv // k.shape[2], axis=2)
    return jnp.stack([ref.delta_rule(q[i], k[i], v[i], g[i], beta[i])
                      for i in range(q.shape[0])])


# decay near 1 (g about -0.005), ordinary, and near 0 (g down to -20);
# T = 1, inside one chunk, a whole chunk, and past a chunk's end
@pytest.mark.parametrize("g_scale", [0.01, 1.0, 20.0])
@pytest.mark.parametrize("t", [1, 7, 32, 45, 100])
def test_chunked_rule_against_the_token_recurrence(t, g_scale):
    args = _rule_inputs(t, g_scale, seed=t)
    with jax.default_matmul_precision("highest"):
        got = gated_delta_rule(*args, chunk=32)
        want = _recurrence(*args)
    assert got.shape == want.shape == (2, t, 4, 8)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("g_scale", [0.01, 20.0])
def test_chunked_rule_gradients_against_the_token_recurrence(g_scale):
    args = _rule_inputs(45, g_scale, seed=3)
    head = jnp.cos(jnp.arange(8.0))
    loss = lambda fn: lambda *a: jnp.sum(fn(*a) * head)
    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss(lambda *a: gated_delta_rule(*a, chunk=16)),
                       argnums=range(5))(*args)
        want = jax.grad(loss(_recurrence), argnums=range(5))(*args)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))


def test_rule_through_the_registered_op_and_autograd():
    args = [mx.nd.array(np.asarray(a)) for a in _rule_inputs(20, 0.5)]
    for a in args:
        a.attach_grad()
    with autograd.record():
        out = mx.nd.GatedDeltaRule(*args, chunk=8)
        loss = (out * out).sum()
    loss.backward()
    want = jax.grad(lambda *a: (_recurrence(*a) ** 2).sum(),
                    argnums=range(5))(*[a._data for a in args])
    for a, w in zip(args, want):
        np.testing.assert_allclose(a.grad.asnumpy(), w, rtol=2e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))


def test_rule_in_bfloat16_keeps_its_type_and_stays_close():
    args = _rule_inputs(100, 0.3, seed=4)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + list(args[3:])
    got = gated_delta_rule(*low)
    assert got.dtype == jnp.bfloat16
    want = _recurrence(*[a.astype(jnp.float32) for a in low])
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < \
        0.03 * float(jnp.abs(want).max())


# -- attention's parts -----------------------------------------------------------

def test_partial_rotary_leaves_the_other_lanes_untouched():
    """Qwen3-Next's head: 256 lanes, the first 64 rotated."""
    x = np.random.default_rng(1).standard_normal((1, 9, 2, 256)) \
        .astype(np.float32)
    got = mx.nd.RotaryEmbedding(mx.nd.array(x), theta=1e7,
                                rotary_dim=64).asnumpy()
    np.testing.assert_array_equal(got[..., 64:], x[..., 64:])
    want = ref._rope(jnp.asarray(x[0]), 1e7, 64)
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
    whole = mx.nd.RotaryEmbedding(mx.nd.array(x[..., :64]),
                                  theta=1e7).asnumpy()
    np.testing.assert_allclose(got[..., :64], whole, rtol=1e-6, atol=1e-7)
    assert np.abs(got[:, 1:, :, :64] - x[:, 1:, :, :64]).max() > 0.1


# -- the expert layer's shares --------------------------------------------------

@pytest.mark.parametrize("shares", [16, 4, 2])
def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer(shares):
    """The routed parts of all the shares, plus the shared expert counted
    ONCE (every chip computes it alike), are the reference's uncut
    layer."""
    from mxnet_tpu.gluon.model_zoo.text.qwen3_next import SharedSparseExperts

    made = ref.init_params(7, CFG)
    n = jnp.asarray(np.random.default_rng(2).standard_normal((2, 24, 64)),
                    jnp.float32)
    flat = n.reshape(-1, 64)
    routed, _ = ref.routed_ff(made, "layer0.", flat, CFG, None, (0, 16))
    shared = ref.shared_ff(made, "layer0.", flat)
    router = {"experts": 16, "k": 3, "norm_topk": True, "scale": 1.0,
              "use_bias": False, "score": "softmax"}
    per, parts = 16 // shares, []
    for s in range(shares):
        layer = SharedSparseExperts(64, 32, 32, (s * per, per), router,
                                    "float32", prefix="moe%d_" % s)
        names = ["shared_gate", "w1", "w3", "w2", "router", "shared.w1",
                 "shared.w3", "shared.w2"]
        sl = slice(s * per, (s + 1) * per)
        for name, p in zip(names, layer.collect_params().values()):
            value = made["layer0.moe." + name]
            if name in ("w1", "w3", "w2"):
                value = value[sl]
            assert tuple(p.shape) == value.shape, name
            p.set_data(NDArray(value))
        out, counts = layer(NDArray(n))
        parts.append(out._data.reshape(-1, 64) - shared)
        assert int(counts._data.sum()) == 48 * 3
    np.testing.assert_allclose(sum(parts) + shared, routed + shared,
                               rtol=2e-5, atol=2e-6)
    # a share alone is the reference given the same share
    want, _ = ref.routed_ff(made, "layer0.", flat, CFG, None, (0, per))
    np.testing.assert_allclose(parts[0], want, rtol=2e-5, atol=2e-6)


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
    net = gluon.model_zoo.get_model("qwen3_next", config=CFG)
    assert isinstance(net, gluon.model_zoo.text.Qwen3Next)
    assert [type(layer.mixer).__name__ for layer in net.layers] == \
        ["GatedDeltaNet"] * 3 + ["GatedAttention"]
    assert all(layer.sparse and layer.ff.router is layer.ff.routed.router
               for layer in net.layers)
    listed = gluon.model_zoo.text.qwen3_next(
        dict(CFG, layer_types=["full_attention", "linear_attention"]))
    assert [type(layer.mixer).__name__ for layer in listed.layers] == \
        ["GatedAttention", "GatedDeltaNet"]
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.qwen3_next(dict(CFG, mlp_only_layers=[0]))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.qwen3_next(CFG, held=(12, 8))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.qwen3_next(dict(CFG, layer_types=["conv"]))
    names = list(net.collect_params())
    assert len(names) == len(ref.leaves(CFG)) + 1     # + the counter
    # the head is untied: a leaf of its own
    assert net.head is not net.embed and net.head.shape == (96, 64)


def test_reference_weights_start_on_the_storage_grid():
    made = ref.init_params(12, CFG)
    for name, _, kind in ref.leaves(CFG):
        v = np.asarray(made[name])
        assert v.dtype == np.float32
        assert (v == np.asarray(made[name].astype(jnp.bfloat16)
                                .astype(jnp.float32))).all(), name
        if kind == "a_log":
            assert (0 <= v).all() and (v <= np.log(16) + 0.01).all()
        if kind == "dt_bias":
            step = np.log1p(np.exp(v))
            assert (0.0009 < step).all() and (step < 0.11).all()
        if kind == "norm0":
            assert abs(v.mean()) < 0.05
