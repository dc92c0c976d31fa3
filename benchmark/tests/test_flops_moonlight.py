"""``lib/flops_moonlight`` against hand counts at the published widths of
the Moonlight-16B-A3B cut: latent attention's projections and its score
and value products at their own widths forward and backward, the expected
expert visits, the parameter count."""
import json
import os

import pytest

from conftest import ROOT

from benchmark.lib import flops_moonlight as fm

with open(os.path.join(ROOT, "benchmark", "configs",
                       "moonlight-16b-a3b.json")) as f:
    CFG = json.load(f)


def test_latent_attention_against_a_hand_count():
    """Scores over 128 + 64 lanes, values over 128, 16 heads: 21.0 M
    multiply-accumulates a token and layer at 8,192 tokens (4,096.5
    average pairs), 13.76 M in the four projections."""
    assert fm.widths(CFG) == (192, 128)
    pairs = 8192 * 8193 // 2
    assert fm.attention_macs(CFG, 8192) == 16 * 320 * pairs
    assert fm.attention_macs(CFG, 8192) / 8192 == pytest.approx(20.97e6,
                                                                rel=1e-3)
    assert fm.attention_fwd_flops(CFG, 1, 8192) == 2 * 16 * 320 * pairs
    assert fm.attention_bwd_flops(CFG, 1, 8192) == \
        2 * 16 * (3 * 192 + 2 * 128) * pairs
    # q_proj 3,072 x 2,048, kv_a_proj_with_mqa 576 x 2,048, kv_b_proj
    # 4,096 x 512, o_proj 2,048 x 2,048
    assert fm.projection_macs(CFG) == 3072 * 2048 + 576 * 2048 + \
        4096 * 512 + 2048 * 2048 == 13762560


def test_expected_expert_visits_and_the_step():
    """6 of 64 experts a token, 8 held: 0.75 expected visits to a held
    expert of 8.65 M MACs, the router over all 64, two shared experts;
    the dense layer's SwiGLU at 11,264. About 440 M MACs a token, 47 % of
    them latent attention; 21.59 TFLOP a step of 8,192 tokens."""
    d, f = 2048, 1408
    assert fm.ff_macs(CFG, 0) == 3 * d * 11264
    assert fm.ff_macs(CFG, 1) == d * 64 + 0.75 * 3 * d * f + 3 * d * 2 * f
    per_token = fm.token_macs(CFG) + 6 * fm.attention_macs(CFG, 8192) / 8192
    assert per_token == pytest.approx(439.17e6, rel=1e-4)
    mla = 6 * (fm.projection_macs(CFG) + fm.attention_macs(CFG, 8192) / 8192)
    assert mla / per_token == pytest.approx(0.4746, abs=1e-3)
    assert fm.train_flops(CFG, 1, 8192) == 6 * (
        8192 * fm.token_macs(CFG) + 6 * fm.attention_macs(CFG, 8192))
    assert fm.train_flops(CFG, 1, 8192) / 1e12 == pytest.approx(21.586,
                                                                rel=1e-4)
    # each held expert's expected visits a step: 8,192 x 6 / 64
    assert 8192 * CFG["num_experts_per_tok"] / \
        CFG["published_num_experts"] == 768


def test_parameter_count_from_the_configuration():
    """Dense + 5 expert layers, 8 of 64 experts, a 20,480-id slice:
    668,890,112 parameters, 10.70 GB at 16 bytes a parameter."""
    assert fm.parameters(CFG) == CFG["parameters"] == 668890112
    assert 16 * fm.parameters(CFG) == \
        CFG["parameter_bytes_16_per_parameter"] == 10702241792
    assert fm.routes(CFG, 0) is False and fm.routes(CFG, 1) is True
