"""The gluon DeepSeek-V3 decoder (Moonlight-16B-A3B's ``model_type``)
against the plain reference (``benchmark/reference/deepseek_v3.py``) at a
tiny size with the published ratios (scores over 24 lanes, 16 without
position and 8 rotary, values over 16, a latent of 32) in float32: logits,
loss, every leaf's gradient; each planted fault moves the reference past
those tolerances; the rotary op's interleaved pairs are the modeling
code's; the selection-only bias changes the selection and never the
weights; the shares of the expert layer, with the shared experts counted
once, add up to the uncut layer; the refusals; the configuration's keys.

Tolerances: the program and the reference compute the same float32
mathematics in other orders (the attention a block of queries at a time
here, the routed experts sorted there), so logits agree to 1e-4 relative
and gradients to 2e-3 of their leaf's largest entry."""
import json
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

from benchmark.reference import deepseek_v3 as ref  # noqa: E402

# a leading dense layer and one that routes 3 of 16 experts beside two
# shared ones; 4 heads; 40 tokens are 5 query blocks of the reference's 8
CFG = {"vocab_size": 96, "hidden_size": 64, "intermediate_size": 96,
       "num_hidden_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 4, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
       "q_lora_rank": None, "rope_theta": 50000, "rms_norm_eps": 1e-5,
       "first_k_dense_replace": 1, "moe_layer_freq": 1,
       "n_routed_experts": 16, "num_experts_per_tok": 3,
       "moe_intermediate_size": 16, "n_shared_experts": 2,
       "norm_topk_prob": True, "routed_scaling_factor": 2.446,
       "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
       "topk_group": 1, "hidden_act": "silu", "tie_word_embeddings": False,
       "attention_bias": False}
B, T = 2, 40
FAULTS = ["no_latent_norm", "rotate_half_pairs", "scale_128",
          "bias_in_weights", "softmax_router", "no_routed_scale",
          "one_shared_expert"]


def build(cfg=CFG, seed=5, held=None, dtype="float32"):
    """(net, {reference name: Parameter}, reference params)."""
    net = gluon.model_zoo.get_model("deepseek_v3", config=cfg, held=held,
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
    """One recorded step of the program, the reference's gradient."""
    net, leaves, made = build()
    ids, labels = batch()
    with autograd.record():
        logits = net(mx.nd.array(ids))
        loss = gluon.loss.SoftmaxCrossEntropyLoss()(
            logits, mx.nd.array(labels.astype(np.float32)))
    loss.backward()
    grads = {n: p.grad().asnumpy() / B for n, p in leaves.items()
             if n in ref.trainable(CFG)}
    want_loss, want_grad, _ = ref.batch_grad(
        ref.make_grad(CFG), made, jnp.asarray(ids), jnp.asarray(labels))
    return {"net": net, "leaves": leaves, "made": made, "ids": ids,
            "labels": labels, "logits": logits.asnumpy(), "grads": grads,
            "loss": float(loss.mean().asscalar()),
            "want_loss": want_loss, "want_grad": want_grad}


def test_loss_and_the_counter(stepped):
    assert stepped["logits"].dtype == np.float32
    assert stepped["loss"] == pytest.approx(stepped["want_loss"], rel=1e-5)
    # the selection's bias is a buffer that takes no gradient (and no
    # optimizer step: benchmark/tests/test_harness_moonlight.py)
    assert stepped["leaves"]["layer1.moe.expert_bias"].grad_req == "null"
    # the counter: the routed layer's every visit
    counts = stepped["net"].expert_tokens.data().asnumpy()
    assert counts.shape == (1, 16) and (counts.sum(1) == 3 * B * T).all()


@pytest.mark.parametrize("name", ref.trainable(CFG))
def test_gradient_of_every_leaf(stepped, name):
    want = np.asarray(stepped["want_grad"][name])
    np.testing.assert_allclose(stepped["grads"][name], want, rtol=2e-3,
                               atol=2e-3 * np.abs(want).max() + 1e-9)


@pytest.fixture(scope="module")
def faulted(stepped):
    """The reference's logits of the batch, and those of every planted
    fault, in one compiled call."""
    @jax.jit
    def run(params, ids):
        with jax.default_matmul_precision("highest"):
            return {fault or "none": jax.vmap(
                lambda i: ref.forward(params, i, CFG, fault=fault)[0])(ids)
                for fault in [None] + FAULTS}

    return {k: np.asarray(v) for k, v in
            run(stepped["made"], jnp.asarray(stepped["ids"])).items()}


def test_logits_of_the_batch(stepped, faulted):
    np.testing.assert_allclose(stepped["logits"], faulted["none"],
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("fault", FAULTS)
def test_each_planted_fault_fails_the_tolerances(faulted, fault):
    """A fault the comparison could not see would guard nothing: each
    moves the logits past the tolerance the program meets, by half as much
    again at least (the faintest, ``bias_in_weights``, moves the routed
    weights by some 1 %: twice the tolerance here)."""
    base = faulted["none"]
    over = np.abs(faulted[fault] - base) / (1e-5 + 1e-4 * np.abs(base))
    assert over.max() > 1.5, (fault, over.max())


def test_the_bias_changes_the_selection_and_never_the_weights(stepped):
    made, n = stepped["made"], "layer1."
    x = jnp.asarray(np.random.default_rng(4).standard_normal((200, 64)),
                    jnp.float32)
    sel, w = ref.select(made, n, x, CFG)
    zero = dict(made, **{n + "moe.expert_bias": jnp.zeros(16)})
    sel0, w0 = ref.select(zero, n, x, CFG)
    assert (np.asarray(sel) != np.asarray(sel0)).any(axis=1).mean() > 0.05
    # where the selection agrees, so do the weights: read from s alone
    same = (np.asarray(sel) == np.asarray(sel0)).all(axis=1)
    np.testing.assert_allclose(np.asarray(w)[same], np.asarray(w0)[same],
                               rtol=1e-6)
    s = jax.nn.sigmoid(x @ made[n + "moe.router"].T)
    want = jnp.take_along_axis(s, sel, 1)
    np.testing.assert_allclose(
        w, 2.446 * want / want.sum(1, keepdims=True), rtol=1e-5)
    # the program's router: the same selection and weights
    from mxnet_tpu.parallel.moe import route

    psel, pgate, _ = route(x, made[n + "moe.router"],
                           made[n + "moe.expert_bias"], k=3, scale=2.446)
    assert (np.asarray(psel) == np.asarray(sel)).all()
    np.testing.assert_allclose(pgate, w, rtol=1e-5)


def test_the_interleaved_rotary_pairs_are_the_modeling_code_s():
    """DeepSeek-V3's ``apply_rotary_pos_emb``: ``x.view(d/2, 2).T`` then
    rotate-half, written out here in float64 with the pairs (2i, 2i + 1)
    turned by frequency i; the default layout is untouched."""
    x = np.random.default_rng(1).standard_normal((1, 9, 2, 8))
    got = np.asarray(ops_nn.rotary_embedding(
        jnp.asarray(x, jnp.float32), theta=50000.0, interleaved=True))
    f = 50000.0 ** (-np.arange(0, 8, 2) / 8)
    ang = np.arange(9)[None, :, None, None] * f
    even, odd = x[..., 0::2], x[..., 1::2]
    want = np.concatenate([even * np.cos(ang) - odd * np.sin(ang),
                           odd * np.cos(ang) + even * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    plain = ops_nn.rotary_embedding(jnp.asarray(x, jnp.float32),
                                    theta=50000.0)
    assert not np.allclose(np.asarray(plain), got)


def test_the_shares_routed_parts_and_the_shared_experts_once_add_up():
    """The 16 experts in 2 shares of 8 (the cut's 8-way job at a small
    size has each chip hold 8): each share routes over all 16 and
    multiplies by those it holds beside the whole shared experts; the
    shares' routed parts plus the shared experts counted once are the
    reference's uncut layer."""
    from mxnet_tpu.gluon.model_zoo.text.laguna import SharedSparseExperts

    made = ref.init_params(7, CFG)
    n = jnp.asarray(np.random.default_rng(2).standard_normal((2, 24, 64)),
                    jnp.float32)
    flat = n.reshape(-1, 64)
    routed, sel = ref.routed_ff(made, "layer1.", flat, CFG, None, (0, 16))
    shared = ref.shared_ff(made, "layer1.", flat, CFG)
    router = {"experts": 16, "k": 3, "norm_topk": True, "scale": 2.446,
              "use_bias": True, "score": "sigmoid"}
    outs = []
    for s in range(2):
        layer = SharedSparseExperts(64, 16, 32, (s * 8, 8), router,
                                    "float32", prefix="moe%d_" % s)
        names = ["w1", "w3", "w2", "router", "expert_bias", "shared.w1",
                 "shared.w3", "shared.w2"]
        for name, p in zip(names, layer.collect_params().values()):
            value = made["layer1.moe." + name]
            if name in ("w1", "w3", "w2"):
                value = value[s * 8:(s + 1) * 8]
            assert tuple(p.shape) == value.shape, name
            p.set_data(NDArray(value))
        layer.hybridize()
        out, counts = layer(NDArray(n))
        outs.append(out._data.reshape(-1, 64))
        np.testing.assert_array_equal(
            counts._data, np.bincount(np.asarray(sel).ravel(), minlength=16))
    np.testing.assert_allclose(outs[0] + outs[1] - shared, routed + shared,
                               rtol=2e-5, atol=2e-6)
    assert float(jnp.abs(outs[0] - routed - shared).max()) > 1e-4


def test_published_keys_are_read_and_refusals_raise():
    net = gluon.model_zoo.get_model("deepseek_v3", config=CFG)
    assert isinstance(net, gluon.model_zoo.text.DeepseekV3)
    assert [layer.sparse for layer in net.layers] == [False, True]
    assert {layer._scope for layer in net.layers} == {"moonlight.attn.mla"}
    assert net.layers[0]._mlp_scope == "moonlight.dense_mlp"
    assert net.layers[1].ff._shared_scope == "moonlight.shared_expert"
    router = net.layers[1].ff.router._attrs
    assert router == {"k": 3, "norm_topk": True, "scale": 2.446,
                      "score": "sigmoid"}
    assert net.layers[1].ff.shared.w1.shape == (32, 64)
    attn = net.layers[0].attn
    assert attn.q_proj.shape == (4 * 24, 64)
    assert attn.kv_a_proj_with_mqa.shape == (40, 64)
    assert attn.kv_b_proj.shape == (4 * 32, 32)
    assert attn.o_proj.shape == (64, 64)
    for bad in ({"q_lora_rank": 1536}, {"n_group": 8},
                {"rope_scaling": {"type": "yarn", "factor": 40}},
                {"attention_bias": True}, {"tie_word_embeddings": True},
                {"topk_method": "greedy"}, {"scoring_func": "softmax"},
                {"hidden_act": "gelu"}):
        with pytest.raises(mx.MXNetError):
            gluon.model_zoo.text.deepseek_v3(dict(CFG, **bad))
    with pytest.raises(mx.MXNetError):
        gluon.model_zoo.text.deepseek_v3(CFG, held=(12, 8))
    # after the leading dense layer, every other one routes where
    # moe_layer_freq is 2 (the modeling code's ``i % freq == 0``)
    net2 = gluon.model_zoo.text.deepseek_v3(dict(CFG, moe_layer_freq=2,
                                                 num_hidden_layers=3))
    assert [layer.sparse for layer in net2.layers] == [False, False, True]


def test_the_benchmark_s_configuration_builds_at_its_published_widths():
    """``benchmark/configs/moonlight-16b-a3b.json``: 668,890,112 parameters
    on this share, none allocated here; every number of the published
    configuration is kept but the three the cut reduces."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "moonlight-16b-a3b.json")) as f:
        cfg = json.load(f)
    net = gluon.model_zoo.get_model("deepseek_v3", config=cfg,
                                    held=cfg["held"], dtype=cfg["dtype"])
    shapes = {n: p.shape for n, p in net.collect_params().items()
              if not n.endswith("expert_tokens")
              and not n.endswith("expert_bias")}
    assert sum(int(np.prod(s)) for s in shapes.values()) == \
        cfg["parameters"] == 668890112
    assert [tuple(s) for n, s, k in ref.leaves(cfg) if k != "bias"] == \
        [tuple(s) for s in shapes.values()]
    assert cfg["parameter_bytes_16_per_parameter"] == 16 * cfg["parameters"]
    assert net.expert_tokens.shape == (5, 64)
    assert cfg["n_routed_experts"] == cfg["held"][1] == 8
    assert cfg["published_num_experts"] == cfg["published"][
        "n_routed_experts"] == 64
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"] == 1
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 6
    assert set(cfg["reduced"]) == set(cfg["published"])
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"]) == (128, 64, 128, 512)
