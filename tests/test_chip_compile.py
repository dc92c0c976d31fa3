"""The Pallas kernels of the main path, compiled for a described TPU.

The TPU's compiler is installed without a chip attached: it compiles
for a ``v5e:2x2`` topology that is described, not present
(on-chip-measurement guide section 2.3). Interpret mode cannot see what
Mosaic refuses — the paged kernel passed every interpret-mode test
while the chip's compiler refused it at every shape — so each kernel is
compiled here at the widths the chip smoke runs, in compiled mode, and
must come out as a ``tpu_custom_call``. Nothing runs: these say nothing
about results or times.

Only the worker that is handed this file loads the TPU library, and
only once a test has started: the topology is described inside a
fixture, never at import, and every compile happens in this process.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.ops import pallas_kernels as pk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chip(topo):
    """shape, dtype -> ShapeDtypeStruct on the described chip, with the
    persistent compile cache off while this file runs: an entry written
    for a described device cannot be read back without one, and the
    next compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _kernel_names(text):
    return [line.split(" = ")[0].split("%")[-1].split(".")[0]
            for line in text.splitlines() if "tpu_custom_call" in line]


def _is_kernel(lowered):
    assert _kernel_names(lowered.compile().as_text())


def _backward_is_a_kernel(q, kv, scale, window=0):
    """The gradient of ``_flash_diff`` at the forward's blocks: the forward
    kernel and the backward kernel, each a ``tpu_custom_call``, the
    backward's under a name of its own (``_flash_call`` is the name the
    benchmark counts forward FLOPs by), and no XLA loop."""
    def loss(q, k, v):
        out = pk._flash_diff(q, k, v, True, scale, 256, 512, False, window)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    names = sorted(_kernel_names(text))
    assert len(names) == 2 and names[0] == "_flash_bwd_call"
    assert "_flash_call" in names[1]
    assert " while(" not in text and "dynamic-update-slice(" not in text


@pytest.mark.parametrize("bh,t,d", [(16, 4096, 128), (8, 8192, 128)])
def test_flash_compiles(chip, bh, t, d):
    q = chip((bh, t, d), jnp.bfloat16)
    _is_kernel(pk._flash_call.lower(
        q, q, q, causal=True, scale=d ** -0.5, block_q=256, block_k=512,
        interpret=False))


def test_flash_backward_compiles_at_the_longest_keys_the_dispatch_admits(
        chip):
    """24,576 keys of 128 lanes in bfloat16 fill ``VMEM_BUDGET_BYTES``, the
    dispatch's line for the kernels; the backward kernel keeps K, V and
    float32 ``dk``, ``dv`` of that length (some 76 MB) and asks Mosaic for
    the scope that takes. (The forward kernel itself, at Mosaic's default
    scope of 16 MiB, compiles to 14,336 keys of 128 lanes and is refused
    from 16,384: ROADMAP B9.)"""
    q = chip((2, 24576, 128), jnp.bfloat16)
    kv = chip((1, 24576, 128), jnp.bfloat16)
    assert 2 * 24576 * 128 * 2 == pk.VMEM_BUDGET_BYTES
    text = pk._flash_bwd_call.lower(
        q, kv, kv, q, chip((2, 1, 24576), jnp.float32), q, causal=True,
        scale=128 ** -0.5, block_q=512, block_k=512,
        interpret=False).compile().as_text()
    assert _kernel_names(text) == ["_flash_bwd_call"]


def test_flash_compiles_grouped_heads_at_64_lanes(chip):
    """LFM2-24B-A2B's attention at the benchmark's batch: 64 query heads
    (2 x 32) of 64 lanes over 16 K/V heads, 4,096 tokens; the K/V head is
    picked by the index map, and inside a jitted program the kernel keeps
    the name ``benchmark/layer_metrics/flash_attn_roofline_pct.py`` finds
    it by."""
    q = chip((64, 4096, 64), jnp.bfloat16)
    kv = chip((16, 4096, 64), jnp.bfloat16)
    _is_kernel(pk._flash_call.lower(
        q, kv, kv, causal=True, scale=0.125, block_q=256, block_k=512,
        interpret=False))

    def mx_attn(q, k, v):
        with jax.named_scope("lfm2.attn"):
            return pk._flash_call(q, k, v, causal=True, scale=0.125,
                                  block_q=256, block_k=512, interpret=False)

    text = jax.jit(mx_attn).lower(q, kv, kv).compile().as_text()
    assert _kernel_names(text) == ["_flash_call"]
    _backward_is_a_kernel(q, kv, 0.125)


def test_expert_layer_compiles_to_grouped_kernels_by_their_name(chip):
    """The held experts' products at the published widths (8 experts,
    2,048 x 1,536, 8,192 tokens x top-4 = 32,768 visit rows): XLA:TPU
    makes ``lax.ragged_dot`` a grouped kernel of its own, named
    ``ragged-dot…`` — the name ``moe_grouped_roofline_pct`` reads — in the
    forward and in both gradients, on every rung of the ladder: each
    rung's nine kernels work on rows of that rung's length."""
    from mxnet_tpu.parallel import moe

    x = chip((8192, 2048), jnp.bfloat16)
    sel = chip((8192, 4), jnp.int32)
    gate = chip((8192, 4), jnp.float32)
    w13 = chip((8, 2048, 1536), jnp.bfloat16)
    w2 = chip((8, 1536, 2048), jnp.bfloat16)

    def loss(x, gate, w1, w3, w2, sel):
        out = moe.experts_held(x, sel, gate, w1, w3, w2, first=0)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, gate, w13, w13, w2, sel).compile().as_text()
    products = [line for line in text.splitlines()
                if "tpu_custom_call" in line and
                line.split(" = ")[0].split("%")[-1].startswith(
                    "ragged-dot-none")]
    rungs = moe.ladder(8192 * 4)
    assert rungs == (8192, 16384, 32768)
    # a rung: three forward (run again in the backward switch), six backward
    assert len(products) == 9 * len(rungs)
    for rows in rungs:
        assert sum("[%d," % rows in line for line in products) == 9, rows


def test_flash_compiles_at_256_lanes_over_2_kv_heads(chip):
    """Qwen3-Next's attention at the benchmark's batch: 32 query heads
    (2 x 16) of 256 lanes over 4 K/V heads (2 x 2), 4,096 tokens: K and V
    of one head are 4 MiB, inside ``VMEM_BUDGET_BYTES``, so the dispatch
    takes the kernel; the backward is a kernel too at that width, over
    groups of 8."""
    q = chip((32, 4096, 256), jnp.bfloat16)
    kv = chip((4, 4096, 256), jnp.bfloat16)
    assert 2 * 4096 * 256 * 2 <= pk.VMEM_BUDGET_BYTES
    _is_kernel(pk._flash_call.lower(
        q, kv, kv, causal=True, scale=256 ** -0.5, block_q=256, block_k=512,
        interpret=False))
    _backward_is_a_kernel(q, kv, 256 ** -0.5)


def test_flash_compiles_with_a_window_at_128_lanes_over_groups_of_7(chip):
    """SmallThinker-21BA3B's attention at the benchmark's batch: 28 query
    heads of 128 lanes over 4 K/V heads (groups of 7, no power of two),
    one sequence of 8,192 tokens, a window of 4,096: K and V of a program
    are 4 MiB, inside ``VMEM_BUDGET_BYTES``; the K loop starts at a block
    computed from the program's index. The backward's kernel takes the
    same window (and none, as the full layer does); beside K and V it
    keeps ``dk``, ``dv`` of the head in float32 (8 MiB) and asks Mosaic
    for the scope that takes."""
    q = chip((28, 8192, 128), jnp.bfloat16)
    kv = chip((4, 8192, 128), jnp.bfloat16)
    assert 2 * 8192 * 128 * 2 <= pk.VMEM_BUDGET_BYTES
    for window in (4096, 0):
        _is_kernel(pk._flash_call.lower(
            q, kv, kv, causal=True, scale=128 ** -0.5, block_q=256,
            block_k=512, interpret=False, window=window))
        _backward_is_a_kernel(q, kv, 128 ** -0.5, window)


def test_flash_compiles_with_a_512_key_window_over_groups_of_9_and_6(chip):
    """Laguna-S-2.1's attention at the benchmark's batch: one sequence of
    4,096 tokens, 128 lanes over 8 K/V heads, 72 query heads (groups of 9)
    in the window layers with a window of 512 keys, one K block; 48
    (groups of 6) in the full layers. Forward and backward kernels."""
    kv = chip((8, 4096, 128), jnp.bfloat16)
    for heads, window in ((72, 512), (48, 0)):
        q = chip((heads, 4096, 128), jnp.bfloat16)
        _is_kernel(pk._flash_call.lower(
            q, kv, kv, causal=True, scale=128 ** -0.5, block_q=256,
            block_k=512, interpret=False, window=window))
        _backward_is_a_kernel(q, kv, 128 ** -0.5, window)


def test_latent_attention_compiles_at_192_lane_scores_over_128_lane_values(
        chip):
    """Moonlight-16B-A3B's latent attention at the benchmark's batch: 16
    heads, one sequence of 8,192 tokens, q and k of 192 lanes (the whole
    last dimension of their blocks), v of 128. Both kernels are
    ``tpu_custom_call``s; the backward keeps K, V, ``dk`` and ``dv`` of the
    head at their own widths (66 MiB of scope asked: 192 lanes take two
    lane tiles) and writes ``dv`` at 128 lanes."""
    q = chip((16, 8192, 192), jnp.bfloat16)
    v = chip((16, 8192, 128), jnp.bfloat16)
    _is_kernel(pk._flash_call.lower(
        q, q, v, causal=True, scale=192 ** -0.5, block_q=256, block_k=512,
        interpret=False))

    def loss(q, k, v):
        out = pk._flash_diff(q, k, v, True, 192 ** -0.5, 256, 512, False, 0)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, v).compile().as_text()
    names = sorted(_kernel_names(text))
    assert len(names) == 2 and names[0] == "_flash_bwd_call"
    assert " while(" not in text
    assert pk._bwd_vmem_bytes(8192, 192, 512, 512, 2, 128) * 3 // 2 < \
        100 * 2 ** 20


def _recorded_pair(chip, net, plist, in_spec, ins):
    """``net``'s two programs of a recorded training call
    (``_build_recorded``: the forward that writes the residuals, and the
    pullback over them, as the call keeps them), compiled for the described
    chip from shapes alone; the inputs ``ins``
    are not differentiated. -> (compiled forward, compiled backward, the
    forward's outputs, the residuals it computed)."""
    jfn, _, _ = net._build_cached(plist, in_spec, True)
    diff = tuple(p.grad_req != "null" for _, p in plist) + (False,) * len(ins)
    args = (tuple(chip(p.shape, p.dtype) for _, p in plist),
            chip((2,), jnp.uint32), *ins)
    _, (fwd, _), (forward, backward) = net._build_recorded(
        jfn, diff, True, args)
    outs, _, computed = jax.eval_shape(fwd, *args)
    return forward, backward, outs, computed


def _step_plan(chip, monkeypatch, config, seq, kernels):
    """``config`` (a file under ``benchmark/configs``) through gluon's own
    two programs of a recorded step (``_recorded_pair``), one sequence of
    ``seq`` tokens, compiled for the described chip from shapes alone; the
    forward holds ``kernels`` flash kernels, one a layer. -> (parameters,
    what the forward holds beside the state: its outputs and temporaries;
    what the backward holds: the residuals, the logits' cotangent and its
    temporaries; the new gradients it writes)."""
    import json

    import mxnet_tpu as mx  # noqa: F401
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import block as blk
    from mxnet_tpu.ndarray import NDArray

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", config)) as f:
        cfg = json.load(f)
    net = gluon.model_zoo.get_model(cfg["model"], config=cfg,
                                    held=cfg["held"], dtype=cfg["dtype"])
    net.hybridize()
    plist = sorted(net.collect_params().items())
    _, in_spec = blk._flatten([NDArray(jnp.zeros((1, 1), jnp.int32))])
    # the flash kernel's dispatch asks for the backend: steer it here,
    # not through an option of the program
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    forward, backward, outs, computed = _recorded_pair(
        chip, net, plist, in_spec, (chip((1, seq), jnp.int32),))
    found = [line for line in forward.as_text().splitlines()
             if "tpu_custom_call" in line and
             line.split(" = ")[0].split("%")[-1].startswith("_flash_call")]
    assert len(found) == kernels
    size = lambda arrays: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                              for a in arrays)
    mf, mb = forward.memory_analysis(), backward.memory_analysis()
    parameters = sum(int(np.prod(p.shape)) for _, p in plist
                     if p.grad_req != "null")
    assert parameters == cfg["parameters"]
    return (parameters, mf.output_size_in_bytes + mf.temp_size_in_bytes,
            size(computed) + size(outs) + mb.temp_size_in_bytes,
            mb.output_size_in_bytes)


def test_laguna_step_compiles_and_fits_at_the_published_widths(
        chip, monkeypatch):
    """``benchmark/configs/laguna-s-2.1.json``, one sequence of 4,096
    tokens: 16 bytes a parameter of state (bfloat16 weight and gradient,
    float32 master, Adam's two) beside the larger program's share, under
    15.9 GB of the chip's 16.9. The backward's new gradients take the place
    of the ones ``autograd.backward`` lets go before it runs, so they add
    nothing to the state (held twice they were 16.7 GB: chip-free
    compile)."""
    parameters, forward, backward, grads = _step_plan(
        chip, monkeypatch, "laguna-s-2.1.json", 4096, 5)
    assert 16 * parameters == 12976275456
    # bfloat16, every leaf (and the output tuple's few bytes)
    assert 0 <= grads - 2 * parameters < 2 ** 20
    plan = 16 * parameters + max(forward, backward)
    assert plan < 15.9e9 < 16.9e9, (forward, backward)


def test_smallthinker_step_compiles_and_fits_at_the_published_widths(
        chip, monkeypatch):
    """``benchmark/configs/smallthinker-21b-a3b.json``, one sequence of
    8,192 tokens. The plan: 16 bytes a parameter of state (bfloat16 weight
    and gradient, float32 master, Adam's two) beside the larger of what
    the forward holds and what the backward holds with its new gradients:
    13.2 GB of the chip's 16.9 (chip-free compile, PR 34; the chip's own
    peak, with the loss block, Adam's program and the next launch
    waiting, is in PERF.md)."""
    parameters, forward, backward, grads = _step_plan(
        chip, monkeypatch, "smallthinker-21b-a3b.json", 8192, 4)
    beside = max(forward, backward + grads)
    plan = 16 * parameters + beside
    assert 16 * parameters == 8948654080
    assert beside < 5.5e9, beside          # 4.2 GB planned (PR 34)
    assert plan < 15.5e9 < 16.9e9, plan    # ISSUE 34's line for this cut


def test_gated_delta_rule_compiles_at_the_published_widths(chip):
    """Qwen3-Next's rule at the benchmark's batch: 2 x 4,096 tokens, 16 key
    heads and 32 value heads of 128 lanes in bfloat16, forward and
    gradient, through the kernels' own differentiable entry (the public
    dispatch asks for the backend, which is the CPU here; its shape rule
    is asserted beside). The two kernels come out as ``tpu_custom_call``s
    under the names the device trace shows, and neither the scan's loop nor
    ``triangular_solve`` is left: a shape that fell back to the XLA path
    would fail here, where interpret mode stays green. The gradient holds
    the state before each chunk and each chunk's inverse (268 + 67 MB)
    where the scan held a group's arrays (0.56 GB planned), never a state
    per token (17 GB)."""
    qk = chip((2, 4096, 16, 128), jnp.bfloat16)
    v = chip((2, 4096, 32, 128), jnp.bfloat16)
    gb = chip((2, 4096, 32), jnp.float32)
    assert pk.delta_rule_tiles(128, 128, 64)
    assert not pk.delta_rule_tiles(16, 128, 64)

    def rule(q, k, v, g, beta):
        return pk.delta_rule(q, k, v, g, beta, interpret=False)

    def loss(*args):
        return rule(*args).astype(jnp.float32).sum()

    fwd = jax.jit(rule).lower(qk, qk, v, gb, gb).compile()
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, v, gb, gb).compile()
    assert _kernel_names(fwd.as_text()) == ["_gdn_fwd_call"]
    assert sorted(_kernel_names(bwd.as_text())) == ["_gdn_bwd_call",
                                                    "_gdn_fwd_call"]
    for text in (fwd.as_text(), bwd.as_text()):
        assert " while(" not in text and "triangular-solve" not in text
    assert fwd.memory_analysis().temp_size_in_bytes < 2 ** 29
    assert bwd.memory_analysis().temp_size_in_bytes < 2 ** 30


_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


def _entry(text):
    """The instructions of the entry computation of compiled HLO ``text``:
    ``{name: the rest of its line}``, and the ROOT's."""
    lines = text[text.index("\nENTRY "):].splitlines()[1:]
    lines = lines[:next(i for i, l in enumerate(lines) if l.startswith("}"))]
    made, root = {}, ""
    for line in lines:
        name, _, rest = line.strip().partition(" = ")
        if name.startswith("ROOT "):
            root = rest
        made[name.split()[-1].lstrip("%")] = rest
    return made, root


def _returned_copies(text):
    """Bytes of each ``copy`` whose result the entry's ROOT tuple of
    compiled HLO ``text`` returns."""
    made, root = _entry(text)
    operands = re.sub(r"/\*.*?\*/", "", root.split("tuple(", 1)[-1])
    copies = []
    for name in operands.split(")")[0].split(","):
        rest = made.get(name.strip().lstrip("%"), "")
        if " copy(" in rest:
            dtype, dims = rest.split("{")[0].rstrip("]").split("[")
            copies.append(_BYTES[dtype] * int(np.prod(
                [int(d) for d in dims.split(",") if d])))
    return copies


def _estimated_cycles(text):
    """The compiler's estimate of the entry computation's cycles."""
    made, _ = _entry(text)
    return sum(int(c) for rest in made.values()
               for c in re.findall(r'"estimated_cycles":"(\d+)"', rest))


def test_resnet50_residuals_cross_in_the_layouts_the_forward_makes(chip):
    """ResNet-50 v1 at the benchmark's batch of 64, through gluon's two
    programs of a recorded step (``_recorded_pair``). With every residual
    returned in its default layout, 149 copies (4.64 GiB) relay them
    before the forward's result, 29.9 M of its 110.4 M estimated cycles,
    and its temporaries plan 1.85 GiB. The pair kept returns 39 of them
    (3.88 GiB) transposed, in the default layout of the axis order they
    were made in: 0.77 GiB of copies are left, the four 64 x 64 x 112 x
    112 activations, whose 64 channels the convolution pads to 128 as no
    default layout does; the forward's estimate falls to 76.6 M cycles and
    its temporaries to 1.18 GiB, its outputs stay 10.71 GiB."""
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import block as blk
    from mxnet_tpu.ndarray import NDArray

    net = gluon.model_zoo.vision.get_model("resnet50_v1", classes=1000)
    net.initialize(mx.init.Xavier())
    blk.infer_shapes(net, (64, 3, 224, 224))
    net.hybridize()
    plist = sorted(net.collect_params().items())
    _, in_spec = blk._flatten([NDArray(jnp.zeros((1, 1), jnp.float32))])
    forward, _, _, computed = _recorded_pair(
        chip, net, plist, in_spec, (chip((64, 3, 224, 224), jnp.float32),))
    assert len(computed) == 523
    gauges = mx.telemetry.snapshot()["metrics"]
    relaid = [{s["labels"]["program"]: s["value"]
               for s in gauges[name]["series"]}["mx_resnetv1_train_fwd"]
              for name in ("mx_residuals_relaid", "mx_residual_bytes_relaid")]
    assert relaid == [39, 4161798144], relaid
    text = forward.as_text()
    returned = _returned_copies(text)
    assert sum(returned) < 2 ** 30, (len(returned), sum(returned))
    assert _estimated_cycles(text) < 90e6
    mf = forward.memory_analysis()
    assert mf.temp_size_in_bytes < 1.5 * 2 ** 30
    assert mf.output_size_in_bytes + mf.temp_size_in_bytes < 12 * 2 ** 30


# h16 x d128 is the smoke's decoder; h4 x d16 is GenerativeDecoder's
# default. paged_attention's rule is one line — on the chip every head
# shape takes the kernel — so the smallest shipped shape compiles too
@pytest.mark.parametrize("h,d,dtype", [
    (16, 128, jnp.bfloat16), (16, 128, jnp.float32), (4, 16, jnp.float32)])
def test_paged_compiles(chip, h, d, dtype):
    b, bt, blocks, width = 8, 16, 256, 6
    pool = chip((blocks, bt, h, d), dtype)
    _is_kernel(pk._paged_call.lower(
        chip((b, h, d), dtype), pool, pool,
        chip((b, width), jnp.int32), chip((b,), jnp.int32),
        scale=d ** -0.5, interpret=False))


def test_paged_kernel_keeps_its_name_inside_a_decode_program(chip):
    """``benchmark/layer_metrics/paged_attn_roofline_pct.py`` finds the
    kernel's device events by the instruction name the jitted wrapper
    gives them. An outer jit that slices a layer out of the pool and
    calls ``paged_attention`` as ``_decode_impl`` does still names every
    ``tpu_custom_call`` ``_paged_call.N``."""
    h, d, b, bt, blocks, width, layers = 16, 128, 8, 16, 256, 6, 2

    def mx_decode(q, k_cache, v_cache, tables, lens):
        for li in range(layers):
            q = pk.paged_attention(q, k_cache[li], v_cache[li], tables,
                                   lens, interpret=False)
        return q

    pool = chip((layers, blocks, bt, h, d), jnp.bfloat16)
    text = jax.jit(mx_decode).lower(
        chip((b, h, d), jnp.bfloat16), pool, pool,
        chip((b, width), jnp.int32), chip((b,), jnp.int32)
    ).compile().as_text()
    assert text.startswith("HloModule jit_mx_decode")
    kernels = [line.split(" = ")[0].split("%")[-1]
               for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(kernels) == layers
    assert all(k.startswith("_paged_call") for k in kernels), kernels


def test_paged_interpret_parity_at_chip_width():
    """The rewritten kernel at (h16, d128, bt16) against the gather
    reference — on the CPU, no topology needed."""
    rng = np.random.default_rng(0)
    b, h, d, bt, blocks, width = 3, 16, 128, 16, 12, 4
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((blocks, bt, h, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((blocks, bt, h, d)), jnp.float32)
    tables = jnp.asarray(rng.integers(0, blocks, (b, width)), jnp.int32)
    lens = jnp.asarray([1, 17, width * bt], jnp.int32)
    got = pk.paged_attention(q, k, v, tables, lens, force=True,
                             interpret=True)
    want = pk._paged_gather_reference(q, k, v, tables, lens, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


_SGD = (("clip", -1.0), ("lr", 0.05), ("momentum", 0.9), ("rescale", 1.0),
        ("wd", 1e-4))
_ADAM = (("beta1", 0.9), ("beta2", 0.999), ("clip", -1.0), ("eps", 1e-8),
         ("lr", 1e-3), ("rescale", 1.0), ("wd", 0.0))


# ResNet-50 weights as the dispatcher reshapes them to (rows, 128) f32
@pytest.mark.parametrize("weight", [(512, 512, 3, 3), (1000, 2048),
                                    (256, 64, 1, 1)])
@pytest.mark.parametrize("kind,hyper,n", [("sgd_mom", _SGD, 3),
                                          ("adam", _ADAM, 4)])
def test_fused_optimizer_compiles(chip, kind, hyper, n, weight):
    a = chip((int(np.prod(weight)) // 128, 128), jnp.float32)
    _is_kernel(pk._fused_opt_call.lower(kind, (a,) * n, hyper, False))


# a ResNet-50 bs128 stage-1 accumulator (128*56*56*256 / 128) and the
# smallest row count the dispatcher tiles
@pytest.mark.parametrize("rows", [8, 802816])
def test_int8_epilogue_compiles(chip, rows):
    _is_kernel(pk._int8_epilogue_call.lower(
        chip((rows, 128), jnp.int32), chip((), jnp.float32),
        chip((), jnp.float32), relu=True, interpret=False))
