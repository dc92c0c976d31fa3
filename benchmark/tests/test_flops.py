"""flops.py against hand counts."""
from benchmark.lib import flops

OPT = {"hidden_size": 2048, "ffn_dim": 8192, "num_hidden_layers": 24,
       "vocab_size": 50272}
RESNET50 = {"image_size": 224, "in_channels": 3, "classes": 1000,
            "layers": [3, 4, 6, 3],
            "channels": [64, 256, 512, 1024, 2048]}


def test_one_convolution():
    # the stem: 64 filters of 3x7x7 at 112x112 positions, one image:
    # 112 * 112 * 64 * 147 MACs
    assert flops.conv2d_flops(1, 3, 64, 7, 112, 112) == 2 * 118013952


def test_resnet50_forward_is_the_published_count():
    # He et al. give 3.8 G multiply-adds for the convolutions of the
    # original (stride on the 3x3); gluon's v1 strides the first 1x1,
    # which makes the first block of stages 2-4 cheaper. Hand count of
    # stage 1, block 0 at 56x56: 64*64 + 64*64*9 + 64*256 + 64*256 MACs
    one = {"image_size": 224, "in_channels": 3, "classes": 1000,
           "layers": [1], "channels": [64, 256]}
    stem = 112 * 112 * 64 * 147
    block = 56 * 56 * (64 * 64 + 64 * 64 * 9 + 64 * 256 + 64 * 256)
    fc = 256 * 1000
    assert flops.resnet_v1_forward_flops(one, 1) == 2 * (stem + block + fc)
    whole = flops.resnet_v1_forward_flops(RESNET50, 1)
    assert 2 * 3.5e9 < whole < 2 * 4.2e9
    assert flops.resnet_v1_train_flops(RESNET50, 64) == 3 * 64 * whole


def test_one_opt_layer():
    # qkv 3*2048*2048, projection 2048*2048, MLP 2*2048*8192 MACs
    macs = 4 * 2048 * 2048 + 2 * 2048 * 8192
    assert flops.decoder_layer_matmul_flops(OPT) == 2 * macs == 100663296
    # one query over 100 positions: 100 * 2048 MACs for QK^T, the same
    # for PV
    assert flops.attention_flops(OPT, 100) == 2 * 2 * 100 * 2048
    assert flops.causal_attention_flops(OPT, 3) == \
        flops.attention_flops(OPT, 1) * 6


def test_generation_flops_by_hand():
    cfg = {"hidden_size": 4, "ffn_dim": 8, "num_hidden_layers": 2,
           "vocab_size": 10}
    per_tok = 2 * (2 * (4 * 16 + 2 * 32))          # two layers
    head = 2 * 4 * 10
    prefill = 3 * per_tok + 2 * (4 * 4 * (1 + 2 + 3)) + head
    assert flops.generation_flops(cfg, 3, 0, 1) == prefill
    # output token 1 is decoded at position 3 and sees 4 positions
    decode = per_tok + 2 * (4 * 4 * 4) + head
    assert flops.generation_flops(cfg, 3, 1, 2) == decode
    assert flops.generation_flops(cfg, 3, 0, 2) == prefill + decode


def test_paged_bytes():
    # 100 cached tokens, K and V, 2048 values of 2 bytes
    assert flops.paged_attention_bytes(OPT, 100, 2) == 100 * 2 * 2048 * 2
