"""``lib/flops_smallthinker`` against hand counts at the published widths,
its banded pairs against a brute-force mask, and ``lib/attn_events`` on a
hand-made capture."""
import numpy as np
import pytest

from conftest import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmark.lib import attn_events
from benchmark.lib import flops_smallthinker as fs

CFG = {"vocab_size": 18992, "hidden_size": 2560, "num_hidden_layers": 4,
       "num_attention_heads": 28, "num_key_value_heads": 4, "head_dim": 128,
       "rope_layout": [0, 1, 1, 1], "sliding_window_layout": [0, 1, 1, 1],
       "sliding_window_size": 4096, "moe_ffn_hidden_size": 768,
       "moe_num_primary_experts": 16, "published_num_experts": 64,
       "held": [0, 16], "moe_num_active_primary_experts": 6}


@pytest.mark.parametrize("seq,window", [(1, 1), (7, 3), (64, 64), (64, 65),
                                        (100, 1), (100, 33), (513, 256),
                                        (64, 0)])
def test_banded_pairs_against_a_brute_force_mask(seq, window):
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    mask = j <= i
    if window:
        mask &= j > i - window
    assert fs.seen_pairs(seq, window) == int(mask.sum())


def test_pairs_at_the_cell_s_sizes():
    # the cell: 8,192 tokens, a window of 4,096: three quarters of the
    # causal pairs
    assert fs.seen_pairs(8192) == 33558528
    assert fs.seen_pairs(8192, 4096) == 25167872
    assert fs.seen_pairs(8192, 4096) / fs.seen_pairs(8192) == \
        pytest.approx(0.75, abs=1e-3)
    # at 4,096 tokens the window masks nothing
    assert fs.seen_pairs(4096, 4096) == fs.seen_pairs(4096)
    assert fs.windows(CFG) == [0, 4096, 4096, 4096]


def test_token_macs_against_a_hand_count():
    """The cut of SmallThinker-21BA3B: 168.5 M multiply-accumulates a
    token forward beside attention's pairs; 13.0 TFLOP a step."""
    experts = 2560 * 64 + (6 * 16 / 64) * 3 * 2560 * 768     # 9,011,200
    assert fs.expert_layer_macs(CFG) == experts == 9011200.0
    attn = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560        # 20,971,520
    assert attn == 20971520
    per_token = 4 * (attn + experts) + 2560 * 18992
    assert fs.token_macs(CFG) == per_token == 168550400.0
    seq = 8192
    full = 2 * 28 * 128 * 33558528
    band = 2 * 28 * 128 * 25167872
    assert fs.attention_macs(CFG, seq) == full
    assert fs.attention_macs(CFG, seq, 4096) == band
    assert fs.attention_fwd_flops(CFG, 1, seq, 4096) == 2 * band
    assert fs.train_flops(CFG, 1, seq) == \
        6 * (seq * per_token + full + 3 * band)
    assert fs.train_flops(CFG, 1, seq) == pytest.approx(12.97e12, rel=2e-3)
    # attention's share of a layer's products, by kind: 59 of 119 M a
    # token in the full layer, 44 of 104 in a window layer
    per_layer = attn + experts
    assert full / seq / (per_layer + full / seq) == pytest.approx(0.495,
                                                                  abs=5e-3)
    assert band / seq / (per_layer + band / seq) == pytest.approx(0.424,
                                                                  abs=5e-3)
    assert fs.train_flops(CFG, 2, seq) == 2 * fs.train_flops(CFG, 1, seq)


def _ctx(scope_events, device_events):
    window = ("bench.window", 0, 10_000, None)
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            window, ("trainer_step", 100, 200, None),
            ("trainer_step", 300, 400, None)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": device_events}]}]
    return {"planes": planes, "cfg": CFG,
            "run": {"batch": 1, "seq": 8192, "scope_events": scope_events}}


def test_kernel_events_take_the_kind_of_the_scope_they_run_under():
    w, f = attn_events.SCOPES["window"], attn_events.SCOPES["full"]
    device = [("fusion.1", 1000, 1100, "x"),           # full's projections
              ("_flash_call.1", 1100, 1500, "x"),      # full's kernel
              ("fusion.2", 2000, 2100, "x"),           # a window layer's
              ("_flash_call.2", 2101, 2400, "x"),
              ("_flash_call.3", 3000, 3300, "x"),      # scoped itself
              ("_flash_call.9", 20_000, 20_100, "x")]  # outside the window
    # the kernel carries no scope of its own in the first two layers: the
    # operation that starts last before it names its layer
    scoped = {f: [(1000, 1100), (1500, 1600)],
              w: [(2000, 2100), (2400, 2500), (2999, 3300)]}
    ctx = _ctx(scoped, device)
    got = attn_events.kernel_events(ctx)
    assert got == {"full": [(1100, 1500)],
                   "window": [(2101, 2400), (3000, 3300)]}
    # the union per step: full 1000..1600 (kernel between its scoped
    # operations), window 2000..2100, 2101..2500 and 2999..3300, over two
    # steps
    assert attn_events.kind_ms(ctx, "full") == pytest.approx(600 / 2 / 1e6)
    assert attn_events.kind_ms(ctx, "window") == pytest.approx(
        (100 + 399 + 301) / 2 / 1e6)
    # a run without the digest, or of a model without these scopes
    assert attn_events.kernel_events(_ctx(None, device)) is None
    assert attn_events.kernel_events(
        _ctx({"lfm2.attn": [(1000, 1100)]}, device)) is None
    assert attn_events.kind_ms(_ctx(None, device), "full") is None
