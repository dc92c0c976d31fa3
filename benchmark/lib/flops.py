"""Operations and bytes the algorithms need, from shapes alone.

Two FLOPs per multiply-accumulate. The counts are of the algorithm's
work, whatever implements it: padding, recomputation and logits nobody
reads are not counted. Unit-tested against hand counts in
benchmark/tests/test_flops.py.
"""


def conv2d_flops(batch, c_in, c_out, kernel, h_out, w_out):
    """One dense 2-D convolution, forward."""
    return 2 * batch * h_out * w_out * c_out * c_in * kernel * kernel


def dense_flops(rows, n_in, n_out):
    return 2 * rows * n_in * n_out


def _out(size, kernel, stride, pad):
    return (size + 2 * pad - kernel) // stride + 1


def resnet_v1_forward_flops(cfg, batch):
    """Convolutions and the classifier of a bottleneck ResNet v1 as
    gluon's model zoo builds it (stride on the first 1x1 of a stage's
    first block), forward, for ``batch`` images."""
    hw = _out(cfg["image_size"], 7, 2, 3)
    chans = cfg["channels"]
    total = conv2d_flops(batch, cfg["in_channels"], chans[0], 7, hw, hw)
    hw = _out(hw, 3, 2, 1)                      # max pool
    c_in = chans[0]
    for stage, (blocks, c_out) in enumerate(zip(cfg["layers"], chans[1:])):
        mid = c_out // 4
        for b in range(blocks):
            stride = 2 if (b == 0 and stage > 0) else 1
            out_hw = _out(hw, 1, stride, 0)
            total += conv2d_flops(batch, c_in, mid, 1, out_hw, out_hw)
            total += conv2d_flops(batch, mid, mid, 3, out_hw, out_hw)
            total += conv2d_flops(batch, mid, c_out, 1, out_hw, out_hw)
            if b == 0:                          # projection shortcut
                total += conv2d_flops(batch, c_in, c_out, 1, out_hw, out_hw)
            c_in, hw = c_out, out_hw
    return total + dense_flops(batch, c_in, cfg["classes"])


def resnet_v1_train_flops(cfg, batch):
    """Forward plus backward: the backward pass of a convolution or a
    dense layer is two products of the forward's size."""
    return 3 * resnet_v1_forward_flops(cfg, batch)


def decoder_layer_matmul_flops(cfg):
    """The four dense products of one pre-norm decoder layer, for one
    token: fused qkv, output projection, two of the MLP."""
    d, f = cfg["hidden_size"], cfg["ffn_dim"]
    return 2 * (3 * d * d + d * d + 2 * d * f)


def attention_flops(cfg, context):
    """QK^T and PV of one query token over ``context`` visible
    positions, all heads, one layer."""
    return 4 * cfg["hidden_size"] * context


def causal_attention_flops(cfg, n):
    """One layer's attention of a whole prompt of ``n`` tokens: token i
    sees i positions."""
    return attention_flops(cfg, 1) * n * (n + 1) // 2


def generation_flops(cfg, prompt_len, first, last):
    """Work to produce output tokens ``first`` .. ``last - 1`` (0-based)
    of one request: token 0 comes from the prefill of the whole prompt,
    token j > 0 from one decode step at position prompt_len + j - 1.
    Every output token needs one row of logits."""
    layers = cfg["num_hidden_layers"]
    per_tok = layers * decoder_layer_matmul_flops(cfg)
    head = dense_flops(1, cfg["hidden_size"], cfg["vocab_size"])
    total = 0
    for j in range(first, last):
        if j == 0:
            total += prompt_len * per_tok + \
                layers * causal_attention_flops(cfg, prompt_len)
        else:
            total += per_tok + layers * attention_flops(
                cfg, prompt_len + j)
        total += head
    return total


def paged_attention_bytes(cfg, valid_tokens, itemsize):
    """Bytes one layer's decode attention has to read: K and V of every
    valid cached token (the padded table is not the algorithm's)."""
    return 2 * valid_tokens * cfg["hidden_size"] * itemsize
