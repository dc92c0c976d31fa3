"""The routed expert layer (``parallel/moe.py``): top-k routing without
drops, an expert layer that is told which experts it holds, the grouped
product over the experts held. Each case against ``jax.numpy`` written
out: a dense loop over the experts with a mask."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu import parallel as par
from mxnet_tpu.parallel import moe

T, D, F, E = 48, 16, 24, 8


def make(seed=3, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)
    return {"x": arr(T, D), "router": arr(E, D) * 0.5,
            "bias": jnp.asarray(rng.standard_normal(E) * 0.1, jnp.float32),
            "w1": arr(E, D, F) / 4, "w3": arr(E, D, F) / 4,
            "w2": arr(E, F, D) / 4}


def dense_layer(p, k, held=(0, E), norm=True, bias=True, score="sigmoid"):
    """The layer written out: scores over all the experts, top-k with the
    bias in the selection only, the held experts' part of the sum."""
    logits = p["x"] @ p["router"].T
    if score == "softmax":
        e = jnp.exp(logits - logits.max(1, keepdims=True))
        s = e / e.sum(1, keepdims=True)
    else:
        s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(s + (p["bias"] if bias else 0.0), k)
    w = jnp.take_along_axis(s, sel, 1)
    if norm:
        w = w / (w.sum(1, keepdims=True) + (1e-6 if score == "sigmoid"
                                            else 0.0))
    out = jnp.zeros_like(p["x"])
    for e in range(held[0], held[0] + held[1]):
        we = jnp.where(sel == e, w, 0.0).sum(1)
        h = p["x"] @ p["w1"][e]
        y = (h / (1 + jnp.exp(-h)) * (p["x"] @ p["w3"][e])) @ p["w2"][e]
        out = out + we[:, None] * y
    return out, sel


def layer(p, k, held=(0, E), **kw):
    first, count = held
    sl = slice(first, first + count)
    return moe.moe_ffn(p["x"], p["router"], p["w1"][sl], p["w3"][sl],
                       p["w2"][sl], expert_bias=p["bias"], k=k,
                       first=first, **kw)


@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("norm", [True, False])
def test_whole_layer_against_the_dense_loop(k, norm):
    p = make()
    got, counts = layer(p, k, norm_topk=norm)
    want, sel = dense_layer(p, k, norm=norm)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(sel).ravel(), minlength=E))
    assert int(counts.sum()) == T * k          # no visit is dropped


@pytest.mark.parametrize("share", range(4))
def test_a_share_equals_the_dense_loop_given_the_same_share(share):
    p, held = make(), (2 * share, 2)
    got, _ = layer(p, 4, held=held)
    want, _ = dense_layer(p, 4, held=held)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("shares", [8, 4, 2])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """What shares 0 … n-1 of one expert layer give adds up to the whole
    layer; every share routes over all the experts and counts alike."""
    p, per = make(5), E // shares
    whole, _ = dense_layer(p, 4)
    parts = [layer(p, 4, held=(i * per, per)) for i in range(shares)]
    np.testing.assert_allclose(sum(o for o, _ in parts), whole, rtol=2e-5,
                               atol=2e-5)
    for _, counts in parts:
        np.testing.assert_array_equal(counts, parts[0][1])


@pytest.mark.parametrize("k", [1, 3])
@pytest.mark.parametrize("norm", [True, False])
def test_softmax_scores_against_the_dense_loop(k, norm):
    """``score="softmax"``: the softmax over ALL the experts, then top-k,
    then (``norm``) the selected weights divided by their sum."""
    p = make(12)
    got, counts = layer(p, k, norm_topk=norm, score="softmax")
    want, sel = dense_layer(p, k, norm=norm, score="softmax")
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(sel).ravel(), minlength=E))
    _, gate, _ = moe.route(p["x"], p["router"], k=k, norm_topk=norm,
                           score="softmax")
    if norm:
        np.testing.assert_allclose(gate.sum(1), 1.0, rtol=1e-6)
    else:       # the unnormalised weights are a part of one softmax
        assert float(gate.sum(1).max()) <= 1.0 + 1e-6


def test_softmax_scores_shares_gradients_and_the_registered_op():
    p = make(13)
    whole, _ = dense_layer(p, 3, bias=False, score="softmax")
    parts = [moe.moe_ffn(p["x"], p["router"], p["w1"][i:i + 2],
                         p["w3"][i:i + 2], p["w2"][i:i + 2], k=3, first=i,
                         score="softmax")[0] for i in range(0, E, 2)]
    np.testing.assert_allclose(sum(parts), whole, rtol=2e-5, atol=2e-5)
    names = ["x", "router", "w1"]
    loss = lambda fn: lambda *a: (fn(dict(p, **dict(zip(names, a))), 3,
                                     held=(2, 4), score="softmax")[0]
                                  ** 2).sum()
    got = jax.grad(loss(layer), (0, 1, 2))(*[p[n] for n in names])
    want = jax.grad(loss(dense_layer), (0, 1, 2))(*[p[n] for n in names])
    for n, a, w in zip(names, got, want):
        np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5, err_msg=n)
    sel, gate, counts = mx.nd.MoERoute(
        mx.nd.array(np.asarray(p["x"])), mx.nd.array(np.asarray(p["router"])),
        k=3, score="softmax")
    _, want_gate, want_counts = moe.route(p["x"], p["router"], k=3,
                                          score="softmax")
    np.testing.assert_allclose(gate.asnumpy(), want_gate, rtol=1e-6)
    np.testing.assert_array_equal(counts.asnumpy(), want_counts)
    with pytest.raises(ValueError):
        moe.route(p["x"], p["router"], k=3, score="tanh")


def test_gradients_against_the_dense_loop():
    p = make(7)
    names = ["x", "router", "w1", "w3", "w2"]

    def loss(fn):
        return lambda *a: (fn(dict(p, **dict(zip(names, a))), 4,
                              held=(2, 4))[0] ** 2).sum()

    got = jax.grad(loss(layer), tuple(range(5)))(*[p[n] for n in names])
    want = jax.grad(loss(dense_layer), tuple(range(5)))(
        *[p[n] for n in names])
    for n, a, w in zip(names, got, want):
        np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5, err_msg=n)


@pytest.mark.parametrize("target", [0, 5])
def test_no_drop_under_a_planted_skew(target):
    """Every token is sent to one expert: the router's rows are nought
    but the target's, and the bias tips the rest. All T visits arrive and
    all are computed."""
    p = make(9)
    p["router"] = jnp.zeros((E, D)).at[target].set(0.0)
    p["bias"] = jnp.zeros(E).at[target].set(1.0)
    got, counts = layer(p, 1)
    assert int(counts[target]) == T and int(counts.sum()) == T
    want, _ = dense_layer(p, 1)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(got).sum()) > 0


def test_two_routings_reuse_one_compiled_program():
    """Static shapes: the visits' buffer holds tokens x k rows whatever
    the routing, so a routing that changes does not compile again."""
    fn = jax.jit(lambda p: layer(p, 4, held=(0, 4)))
    a, b = make(1), make(2)
    b["bias"] = b["bias"].at[0].set(5.0)       # all tokens visit expert 0
    (_, ca), (_, cb) = fn(a), fn(b)
    assert fn._cache_size() == 1
    assert int(cb[0]) == T and int(ca[0]) < T


def test_rows_past_the_last_group_give_no_gradient():
    """Held experts that no token selects: their weights' gradients are
    nought and the tokens' are finite."""
    p = make(4)
    p["bias"] = jnp.zeros(E).at[6].set(9.0).at[7].set(9.0)
    g = jax.grad(lambda w1, x: (layer(dict(p, w1=w1, x=x), 2,
                                      held=(0, 4))[0] ** 2).sum(),
                 (0, 1))(p["w1"], p["x"])
    assert float(jnp.abs(g[0][:4]).max()) == 0.0
    assert bool(jnp.isfinite(g[1]).all())


def test_registered_ops_through_autograd():
    p = make(8)
    nd = {n: mx.nd.array(np.asarray(v)) for n, v in p.items()}
    for n in ("x", "router", "w1"):
        nd[n].attach_grad()
    with autograd.record():
        sel, gate, counts = mx.nd.MoERoute(nd["x"], nd["router"],
                                           nd["bias"], k=4)
        out = mx.nd.MoEExperts(nd["x"], sel, gate, nd["w1"][2:6],
                               nd["w3"][2:6], nd["w2"][2:6], first=2)
        loss = (out * out).sum()
    loss.backward()
    want, _ = dense_layer(p, 4, held=(2, 4))
    np.testing.assert_allclose(out.asnumpy(), want, rtol=2e-5, atol=2e-5)
    assert sel.dtype == np.int32 and counts.asnumpy().sum() == 4 * T
    g = jax.grad(lambda x: (dense_layer(dict(p, x=x), 4,
                                        held=(2, 4))[0] ** 2).sum())(p["x"])
    np.testing.assert_allclose(nd["x"].grad.asnumpy(), g, rtol=2e-4,
                               atol=2e-5)


def test_expert_parallel_over_a_mesh_matches_local():
    """Experts split over an ``ep`` axis of four devices, two a device:
    each computes its own part and one psum adds the parts."""
    mesh = par.create_mesh({"ep": 4}, devices=jax.devices()[:4])
    p = make(6)

    def fn(x, router, w1, w3, w2):
        return moe.moe_ffn_ep(x, router, w1, w3, w2, axis_name="ep", k=2)

    got, counts = par.shard_map(
        fn, mesh=mesh,
        in_specs=(P(), P(), P("ep"), P("ep"), P("ep")),
        out_specs=(P(), P()), check_vma=False)(
            p["x"], p["router"], p["w1"], p["w3"], p["w2"])
    want, _ = dense_layer(p, 2, bias=False)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert int(counts.sum()) == 2 * T


# -- the ladder: the visits' buffer is a rung long ------------------------
K2, HELD = 2, (2, 4)                       # 96 visit rows: rungs 24, 48, 96
RUNGS = moe.ladder(T * K2)
NAMES = ["x", "router", "w1", "w3", "w2"]


def plant_live(p, k, held, live):
    """``p`` with an ``expert_bias`` that lifts the held experts alike
    until exactly ``live`` of the T x k visits go to them: the count rises
    by single visits with the lift, so a bisection finds it."""
    first, count = held
    lift = jnp.zeros(E).at[first:first + count].set(1.0)

    def visits(c):
        _, sel = dense_layer(dict(p, bias=c * lift), k)
        return int(((sel >= first) & (sel < first + count)).sum())

    lo, hi = -2.0, 2.0
    assert visits(lo) == 0 and visits(hi) == T * min(k, count)
    for _ in range(60):
        mid = (lo + hi) / 2
        n = visits(mid)
        if n == live:
            return dict(p, bias=mid * lift)
        lo, hi = (mid, hi) if n < live else (lo, mid)
    raise AssertionError("no lift gives %d live visits" % live)


def test_the_ladder_and_the_rule():
    """The rule is public: the shortest rung that holds the live count,
    and tokens x k for a buffer past a half full. A count per layer reads
    as an array."""
    assert RUNGS == (24, 48, 96)
    assert moe.ladder(8192 * 10) == (20480, 40960, 81920)
    assert moe.ladder(7) == (2, 4, 7) and moe.ladder(1) == (1,)
    for rows in (96, 8192 * 10, 7):
        steps = moe.ladder(rows)
        assert steps[-1] == rows
        for live in range(rows + 1) if rows < 100 else (
                0, 8600, 20480, 20481, 40960, 40961, rows):
            got = moe.rung_rows(live, rows)
            assert got == min(c for c in steps if c >= live)
    np.testing.assert_array_equal(
        moe.rung_rows(np.array([0, 24, 25, 49, 96]), 96),
        [24, 24, 48, 96, 96])


@pytest.mark.parametrize(
    "live", [c + d for c in RUNGS for d in (-1, 0, 1) if c + d <= T * K2])
def test_layer_and_gradients_round_every_rung(live):
    """A live count just under, exactly at and one over each rung's
    length: the value and all five gradients against the dense loop."""
    p = plant_live(make(21), K2, HELD, live)
    _, gate, counts = moe.route(p["x"], p["router"], p["bias"], k=K2)
    assert int(counts[HELD[0]:sum(HELD)].sum()) == live
    got, _ = layer(p, K2, held=HELD)
    want, _ = dense_layer(p, K2, held=HELD)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda *a: (fn(dict(p, **dict(zip(NAMES, a))), K2,
                              held=HELD)[0] ** 2).sum()

    args = [p[n] for n in NAMES]
    got = jax.grad(loss(layer), tuple(range(5)))(*args)
    want = jax.grad(loss(dense_layer), tuple(range(5)))(*args)
    for n, a, w in zip(NAMES, got, want):
        np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5, err_msg=n)


def test_the_shortest_rung_and_the_full_buffer_share_one_program():
    fn = jax.jit(lambda p: layer(p, K2, held=HELD))
    few, all_ = (plant_live(make(s), K2, HELD, n)
                 for s, n in ((22, RUNGS[0] - 3), (23, T * K2)))
    for p in (few, all_):
        got, _ = fn(p)
        want, _ = dense_layer(p, K2, held=HELD)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert fn._cache_size() == 1


def _long_float_arrays(jaxpr, rows, inside_longest=False):
    """Shapes of the floating arrays of ``rows`` rows by some width that
    ``jaxpr`` makes anywhere but inside the last branch of a conditional
    (the longest rung's)."""
    found = []
    for eqn in jaxpr.eqns:
        if not inside_longest:
            found += [v.aval.shape for v in eqn.outvars
                      if len(v.aval.shape) == 2 and v.aval.shape[0] == rows
                      and jnp.issubdtype(v.aval.dtype, jnp.floating)]
        for name, value in eqn.params.items():
            subs = value if isinstance(value, (tuple, list)) else [value]
            for i, sub in enumerate(subs):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    last = eqn.primitive.name == "cond" and \
                        name == "branches" and i == len(subs) - 1
                    found += _long_float_arrays(sub, rows,
                                                inside_longest or last)
    return found


def test_no_full_length_array_outside_the_longest_rung():
    """Differentiated by JAX's own rule a ``switch`` makes every branch
    return every other's residuals, zeros at their full shapes: the
    layer's derivative is its own, and its gradient's program holds no
    tokens x k rows by a width outside the longest rung's branch."""
    p = make(24)
    sel, gate, _ = moe.route(p["x"], p["router"], p["bias"], k=K2)
    sl = slice(HELD[0], sum(HELD))
    args = (p["x"], gate, p["w1"][sl], p["w3"][sl], p["w2"][sl])

    def loss(x, gate, w1, w3, w2):
        return (moe.experts_held(x, sel, gate, w1, w3, w2,
                                 first=HELD[0]) ** 2).sum()

    ours = jax.make_jaxpr(jax.grad(loss, tuple(range(5))))(*args)
    assert _long_float_arrays(ours.jaxpr, T * K2) == []
    # the reader does see them where JAX's rule is left to differentiate
    def plain(x, gate, w1, w3, w2):
        return (moe._on_rung(moe._rung, x, sel, gate, w1, w3, w2,
                             HELD[0]) ** 2).sum()

    theirs = jax.make_jaxpr(jax.grad(plain, tuple(range(5))))(*args)
    assert _long_float_arrays(theirs.jaxpr, T * K2)


def test_rung_scopes_under_the_model_s_scope():
    """Through ``SparseExperts`` every rung's operations carry
    ``rows.<length>`` inside ``lfm2.moe.experts``, so a capture says which
    rung a layer took."""
    import importlib
    import re
    lfm2 = importlib.import_module("mxnet_tpu.gluon.model_zoo.text.lfm2_moe")
    block = lfm2.SparseExperts(
        D, F, HELD, dict(experts=E, k=K2, norm_topk=True, scale=1.0,
                         use_bias=True), "float32")
    block.initialize()
    block.hybridize()
    x = mx.nd.array(np.asarray(make(25)["x"]).reshape(2, T // 2, D))
    block(x)
    (jitted, *_), = block._cached_jit.values()
    pvals = tuple(p.data()._data for _, p in block._cached_plist)
    text = jitted.lower(pvals, jax.random.PRNGKey(0),
                        x._data).compile().as_text()
    seen = set(re.findall(r'op_name="[^"]*lfm2\.moe\.experts/[^"]*?'
                          r'(rows\.\d+)/', text))
    assert seen == {"rows.%d" % c for c in RUNGS}


def test_gradients_over_a_mesh_where_first_is_traced():
    """Under ``moe_ffn_ep`` ``first`` is the device's index times its
    experts, a traced value: an argument of the layer's own derivative
    that takes no cotangent."""
    mesh = par.create_mesh({"ep": 4}, devices=jax.devices()[:4])
    p = make(26)

    def fn(*args):
        return moe.moe_ffn_ep(*args, axis_name="ep", k=K2)[0]

    over = par.shard_map(
        fn, mesh=mesh, in_specs=(P(), P(), P("ep"), P("ep"), P("ep")),
        out_specs=P(), check_vma=False)
    args = [p[n] for n in NAMES]
    got = jax.grad(lambda *a: (over(*a) ** 2).sum(), tuple(range(5)))(*args)
    want = jax.grad(
        lambda *a: (dense_layer(dict(p, **dict(zip(NAMES, a))), K2,
                                bias=False)[0] ** 2).sum(),
        tuple(range(5)))(*args)
    for n, a, w in zip(NAMES, got, want):
        np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5, err_msg=n)


# -- the experts' gate and a router that reads another input ---------------------

def relu_layer(p, k, route_on=None, held=(0, E)):
    """The layer written out with ReLU in the gate, softmax scores, the
    router reading ``route_on`` where given."""
    r = p["x"] if route_on is None else route_on
    logits = r @ p["router"].T
    _, sel = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(jnp.take_along_axis(logits, sel, 1), -1)
    out = jnp.zeros_like(p["x"])
    for e in range(held[0], held[0] + held[1]):
        we = jnp.where(sel == e, w, 0.0).sum(1)
        y = (jnp.maximum(p["x"] @ p["w1"][e], 0) * (p["x"] @ p["w3"][e])) \
            @ p["w2"][e]
        out = out + we[:, None] * y
    return out, sel


@pytest.mark.parametrize("held", [(0, E), (2, 2), (6, 2)])
def test_relu_gate_value_and_gradients_against_the_dense_loop(held):
    p = make(31)
    sl = slice(held[0], sum(held))
    names = ("x", "router", "w1", "w3", "w2")

    def ours(x, router, w1, w3, w2):
        return moe.moe_ffn(x, router, w1[sl], w3[sl], w2[sl], k=K2,
                           first=held[0], score="softmax", act="relu")[0]

    def theirs(*a):
        return relu_layer(dict(zip(names, a)), K2, held=held)[0]

    args = [p[n] for n in names]
    np.testing.assert_allclose(ours(*args), theirs(*args), rtol=2e-5,
                               atol=2e-5)
    got = jax.grad(lambda *a: (ours(*a) ** 2).sum(), tuple(range(5)))(*args)
    want = jax.grad(lambda *a: (theirs(*a) ** 2).sum(),
                    tuple(range(5)))(*args)
    for n, a, w in zip(names, got, want):
        np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5, err_msg=n)
    # the gate matters: silu on the same arguments is another number
    silu = moe.moe_ffn(p["x"], p["router"], p["w1"][sl], p["w3"][sl],
                       p["w2"][sl], k=K2, first=held[0], score="softmax")[0]
    assert float(jnp.abs(silu - ours(*args)).max()) > 1e-3
    with pytest.raises(ValueError):
        moe.experts_held(p["x"], None, None, p["w1"], p["w3"], p["w2"],
                         act="gelu")


def test_a_router_that_reads_another_input_than_the_experts():
    """``route_on``: the selection, the weights and the router's gradient
    come from it; the products and their gradients from ``x``."""
    p = make(32)
    r = jnp.asarray(np.random.default_rng(33).standard_normal((T, D)),
                    jnp.float32)
    names = ("x", "router", "w1", "w3", "w2")

    def ours(r, x, router, w1, w3, w2):
        return moe.moe_ffn(x, router, w1, w3, w2, k=K2, score="softmax",
                           act="relu", route_on=r)

    def theirs(r, *a):
        return relu_layer(dict(zip(names, a)), K2, route_on=r)

    args = [r] + [p[n] for n in names]
    (got, counts), (want, sel) = ours(*args), theirs(*args)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(sel).ravel(), minlength=E))
    _, sel_x = relu_layer(p, K2)
    assert (np.asarray(sel) != np.asarray(sel_x)).any()
    g_got = jax.grad(lambda *a: (ours(*a)[0] ** 2).sum(),
                     tuple(range(6)))(*args)
    g_want = jax.grad(lambda *a: (theirs(*a)[0] ** 2).sum(),
                      tuple(range(6)))(*args)
    for n, a, w in zip(("route_on",) + names, g_got, g_want):
        np.testing.assert_allclose(a, w, rtol=2e-4, atol=2e-5, err_msg=n)
    assert float(jnp.abs(g_got[0]).max()) > 0     # the router's input


def test_sparse_experts_block_routes_on_its_second_input():
    """``SparseExperts(n, r)`` through gluon: the hybridized block and
    autograd, ReLU in the gate."""
    import importlib
    lfm2 = importlib.import_module("mxnet_tpu.gluon.model_zoo.text.lfm2_moe")
    p = make(34)
    block = lfm2.SparseExperts(
        D, F, (0, E), dict(experts=E, k=K2, norm_topk=True, scale=1.0,
                           use_bias=False, score="softmax"), "float32",
        act="relu")
    block.initialize()
    for name, param in zip(("w1", "w3", "w2", "router"),
                           block.collect_params().values()):
        param.set_data(mx.nd.array(np.asarray(p[name])))
    block.hybridize()
    r = np.random.default_rng(35).standard_normal((2, T // 2, D)) \
        .astype(np.float32)
    n, rr = mx.nd.array(np.asarray(p["x"]).reshape(2, T // 2, D)), \
        mx.nd.array(r)
    n.attach_grad()
    rr.attach_grad()
    with autograd.record():
        out, counts = block(n, rr)
        loss = (out * out).sum()
    loss.backward()
    want, sel = relu_layer(p, K2, route_on=jnp.asarray(r).reshape(T, D))
    np.testing.assert_allclose(out.asnumpy().reshape(T, D), want, rtol=2e-5,
                               atol=2e-5)
    assert int(counts.asnumpy().sum()) == T * K2
    g = jax.grad(lambda x, q: (relu_layer(dict(p, x=x), K2, route_on=q)[0]
                               ** 2).sum(), (0, 1))(
        p["x"], jnp.asarray(r).reshape(T, D))
    np.testing.assert_allclose(n.grad.asnumpy().reshape(T, D), g[0],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(rr.grad.asnumpy().reshape(T, D), g[1],
                               rtol=2e-4, atol=2e-5)
    # one input: the block routes on what it multiplies, as before
    alone, _ = block(n)
    np.testing.assert_allclose(alone.asnumpy().reshape(T, D),
                               relu_layer(p, K2)[0], rtol=2e-5, atol=2e-5)
