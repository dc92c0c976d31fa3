"""``lib/flops_qwen3next`` against hand counts at the published widths."""
import pytest

from conftest import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmark.lib import flops_qwen3next as fq

CFG = {"vocab_size": 18992, "hidden_size": 2048, "num_hidden_layers": 4,
       "full_attention_interval": 4, "num_attention_heads": 16,
       "num_key_value_heads": 2, "head_dim": 256,
       "linear_num_key_heads": 16, "linear_num_value_heads": 32,
       "linear_key_head_dim": 128, "linear_value_head_dim": 128,
       "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
       "shared_expert_intermediate_size": 512, "num_experts": 32,
       "published_num_experts": 512, "held": [0, 32],
       "num_experts_per_tok": 10}


def test_layer_kinds_from_the_interval_or_the_list():
    assert fq.kinds(CFG) == ["linear_attention"] * 3 + ["full_attention"]
    assert fq.kinds(dict(CFG, layer_types=["full_attention"])) == \
        ["full_attention"]
    assert fq.rule_layers(CFG) == 3


def test_token_macs_against_a_hand_count():
    """The cut of Qwen3-Next-80B-A3B: 213.6 M multiply-accumulates a
    token forward, attention's score and value products included."""
    experts = 2048 * 512 + (10 * 32 / 512) * 3 * 2048 * 512 + \
        3 * 2048 * 512 + 2048                               # 6,162,432
    assert fq.expert_layer_macs(CFG) == experts == 6162432.0
    rule = 3 * 32 * 128 * 128                               # 1,572,864
    assert fq.rule_macs(CFG) == rule
    linear = 2048 * 12288 + 2048 * 64 + 4 * 8192 + rule + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    head = 2048 * 18992
    per_token = 3 * (linear + experts) + (attn + experts) + head
    assert fq.token_macs(CFG) == per_token
    seq = 4096
    attention = 2 * 16 * 256 * seq * (seq + 1) // 2
    assert fq.attention_macs(CFG, seq) == attention
    assert (per_token * seq + attention) / seq == pytest.approx(213.6e6,
                                                                rel=2e-3)
    assert fq.train_flops(CFG, 2, seq) == \
        6 * 2 * (seq * per_token + attention)
    # 10.5 TFLOP a step at 8,192 tokens
    assert fq.train_flops(CFG, 2, seq) == pytest.approx(10.5e12, rel=5e-3)


def test_rule_work_is_three_forward_passes_read_once():
    # q, k 2,048 lanes each, v and o 4,096, in two bytes; g and beta 32
    # float32 each
    assert fq.rule_bytes(CFG) == 2 * (2 * 2048 + 2 * 4096) + 2 * 4 * 32
    flops, nbytes = fq.rule_train_work(CFG, 8192)
    assert flops == 3 * 2 * 8192 * 1572864
    assert nbytes == 3 * 8192 * 24832
    # the bytes bound it on a v5e: 0.745 ms against 0.392
    assert nbytes / 819e9 > flops / 197e12
