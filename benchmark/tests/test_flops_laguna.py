"""``lib/flops_laguna`` against hand counts at the published widths of the
cut, and ``lib/attn_kinds`` on a hand-made capture: the reading of
``lib/attn_events`` with the scopes handed in."""
import json
import os

import pytest

from conftest import ROOT

from benchmark.lib import attn_events, attn_kinds
from benchmark.lib import flops_laguna as fl

with open(os.path.join(ROOT, "benchmark", "configs",
                       "laguna-s-2.1.json")) as f:
    CFG = json.load(f)


def test_shapes_by_layer_kind():
    assert fl.heads(CFG) == [48, 72, 72, 72, 48]
    assert fl.windows(CFG) == [0, 512, 512, 512, 0]
    assert fl.kind_shape(CFG, "full") == (48, 0)
    assert fl.kind_shape(CFG, "window") == (72, 512)
    # 4,096 tokens: a window of 512 keeps 23 % of the causal pairs
    assert fl.seen_pairs(4096) == 8390656
    assert fl.seen_pairs(4096, 512) == 131328 + 3584 * 512 == 1966336
    assert 1 - 1966336 / 8390656 == pytest.approx(0.766, abs=1e-3)


def test_token_macs_against_a_hand_count():
    """The cut of Laguna-S-2.1: 482.3 M multiply-accumulates a token
    forward beside attention's pairs, 559.1 M with them; 13.74 TFLOP a
    step of 4,096 tokens."""
    d, hd = 3072, 128
    proj = lambda h: 2 * d * h * hd + 2 * d * 8 * hd + d * h
    assert proj(48) == 44187648 and proj(72) == 63135744
    dense = 3 * d * 12288                                   # 113,246,208
    sparse = d * 256 + (10 * 8 / 256) * 3 * d * 1024 + 3 * d * 1024
    assert fl.ff_macs(CFG, 0) == dense == 113246208
    assert fl.ff_macs(CFG, 1) == sparse == 13172736.0
    per_token = proj(48) + dense + 3 * (proj(72) + sparse) + \
        (proj(48) + sparse) + d * 12544
    assert fl.token_macs(CFG) == per_token == 482254848.0
    seq = 4096
    full = 2 * 48 * hd * 8390656
    band = 2 * 72 * hd * 1966336
    assert fl.attention_macs(CFG, seq, 48) == full
    assert fl.attention_macs(CFG, seq, 72, 512) == band
    assert fl.attention_fwd_flops(CFG, 1, seq, 72, 512) == 2 * band
    assert fl.train_flops(CFG, 1, seq) == \
        6 * (seq * per_token + 2 * full + 3 * band)
    assert (seq * per_token + 2 * full + 3 * band) / seq == \
        pytest.approx(559.14e6, rel=1e-4)
    assert fl.train_flops(CFG, 1, seq) == pytest.approx(13.74e12, rel=1e-3)
    assert fl.train_flops(CFG, 2, seq) == 2 * fl.train_flops(CFG, 1, seq)


def _ctx(scope_events, device_events):
    window = ("bench.window", 0, 10_000, None)
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            window, ("trainer_step", 100, 200, None),
            ("trainer_step", 300, 400, None)]}]},
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops",
                                             "events": device_events}]}]
    return {"planes": planes, "cfg": CFG,
            "run": {"batch": 1, "seq": 4096, "scope_events": scope_events}}


DEVICE = [("fusion.1", 1000, 1100, "x"),           # full's projections
          ("_flash_call.1", 1100, 1500, "x"),      # full's kernel
          ("fusion.2", 2000, 2100, "x"),           # a window layer's
          ("_flash_call.2", 2101, 2400, "x"),
          ("_flash_call.3", 3000, 3300, "x"),      # scoped itself
          ("_flash_call.9", 20_000, 20_100, "x")]  # outside the window


@pytest.mark.parametrize("scopes", [attn_kinds.LAGUNA, attn_events.SCOPES])
def test_kernel_events_take_the_kind_of_the_scope_they_run_under(scopes):
    """Laguna's scopes, and SmallThinker's read as ``lib/attn_events``
    reads them: one reading."""
    w, f = scopes["window"], scopes["full"]
    scoped = {f: [(1000, 1100), (1500, 1600)],
              w: [(2000, 2100), (2400, 2500), (2999, 3300)]}
    ctx = _ctx(scoped, DEVICE)
    got = attn_kinds.kernel_events(ctx, scopes)
    assert got == {"full": [(1100, 1500)],
                   "window": [(2101, 2400), (3000, 3300)]}
    assert attn_kinds.kind_ms(ctx, scopes, "full") == \
        pytest.approx(600 / 2 / 1e6)
    assert attn_kinds.kind_ms(ctx, scopes, "window") == pytest.approx(
        (100 + 399 + 301) / 2 / 1e6)
    if scopes is attn_events.SCOPES:
        assert attn_events.kernel_events(ctx) == got
        assert attn_events.kind_ms(ctx, "window") == \
            attn_kinds.kind_ms(ctx, scopes, "window")
    # a run without the digest, or of a model without these scopes
    assert attn_kinds.kernel_events(_ctx(None, DEVICE), scopes) is None
    assert attn_kinds.kernel_events(
        _ctx({"lfm2.attn": [(1000, 1100)]}, DEVICE), scopes) is None
    assert attn_kinds.kind_ms(_ctx(None, DEVICE), scopes, "full") is None
