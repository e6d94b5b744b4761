"""Operations and bytes the mathematics needs, computed from shapes.

Nothing here asks XLA (``cost_analysis`` counts padding and recompute);
every count is the textbook one, so a change to the program cannot move
it. A multiply-accumulate is two floating-point operations.
"""
from __future__ import annotations

# --- ResNet v1 (He et al. 2015, Table 1), bottleneck as the model zoo
# builds it: stride on the first 1x1 convolution of a stage's first block.
RESNET_V1_BOTTLENECK = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3),
                        152: (3, 8, 36, 3)}


def _conv_macs(c_in, c_out, k, h_out, w_out):
    return c_in * c_out * k * k * h_out * w_out


def resnet_v1_forward_macs(depth=50, image=224, classes=1000):
    """Multiply-accumulates of one image's forward pass: convolutions
    and the classifier; BatchNorm, ReLU and pooling are not counted."""
    blocks = RESNET_V1_BOTTLENECK[depth]
    size = image // 2                       # 7x7 stride 2
    macs = _conv_macs(3, 64, 7, size, size)
    size //= 2                              # 3x3 max pool stride 2
    c_in = 64
    for stage, n in enumerate(blocks):
        c_out = 256 * 2 ** stage
        mid = c_out // 4
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            out = size // stride
            macs += _conv_macs(c_in, mid, 1, out, out)
            macs += _conv_macs(mid, mid, 3, out, out)
            macs += _conv_macs(mid, c_out, 1, out, out)
            if b == 0:                      # projection shortcut
                macs += _conv_macs(c_in, c_out, 1, out, out)
            c_in, size = c_out, out
    return macs + c_in * classes


def train_flops_per_image(forward_macs):
    """Forward, gradient with respect to the activations, gradient with
    respect to the weights: three passes of two operations per MAC."""
    return 3 * 2 * forward_macs


# --- pre-LN decoder-only transformer with a 2-matrix feed-forward (OPT)
def decoder_layer_weight_count(d_model, d_ff):
    """Matrix weights of one block (q, k, v, o, two feed-forward)."""
    return 4 * d_model * d_model + 2 * d_model * d_ff


def decode_step_bytes(n_layers, d_model, d_ff, vocab, live_tokens,
                      weight_bytes=4, kv_bytes=4):
    """Bytes one decode step has to read at the least: every matrix
    weight once (the batch shares them) and the K and V of the tokens
    that are live in the batch. ``live_tokens`` is the sum of the
    context lengths over the rows of the batch."""
    weights = (n_layers * decoder_layer_weight_count(d_model, d_ff)
               + d_model * vocab) * weight_bytes
    kv = 2 * n_layers * live_tokens * d_model * kv_bytes
    return weights + kv
