"""Operations and bytes from shapes: the yardstick's arithmetic.

Copied from ``apex_example_tpu/utils/flops.py`` (the transformer and ResNet
training FLOP models) so that it cannot move when the program does, and
extended with the bytes a decode tick must move.  Every count is the
*least* the algorithm needs: a share of a roofline that reads over 100% is
a bug here, never a fast kernel.
"""

from __future__ import annotations

from typing import Dict


def transformer_train_flops_per_token(*, num_layers, hidden_size,
                                      intermediate_size, vocab_size,
                                      seq_len) -> float:
    """6 FLOPs per matmul weight per token (2 per multiply-add, forward
    plus the two backward products) over QKVO, the FFN and the vocabulary
    head, plus attention's quadratic 12*L*S*d.  Gathers count nothing; the
    head's 768x768 dense of BERT's MLM head is left out (the least)."""
    per_layer = 4 * hidden_size * hidden_size \
        + 2 * hidden_size * intermediate_size
    n_matmul = num_layers * per_layer + hidden_size * vocab_size
    return 6.0 * n_matmul + 12.0 * num_layers * seq_len * hidden_size


def _resnet_convs(stage_sizes, bottleneck, image_size):
    convs = []
    h = image_size // 2
    convs.append((7, 3, 64, h))
    h = -(-h // 2)
    cin = 64
    for si, n_blocks in enumerate(stage_sizes):
        f = 64 * 2 ** si
        for b in range(n_blocks):
            s = 2 if (si > 0 and b == 0) else 1
            hout = -(-h // s)
            if bottleneck:
                # v1.5: the 1x1 runs at the input size, the strided 3x3
                # and the expanding 1x1 at the output size
                convs += [(1, cin, f, h), (3, f, f, hout),
                          (1, f, 4 * f, hout)]
                cout = 4 * f
            else:
                convs += [(3, cin, f, hout), (3, f, f, hout)]
                cout = f
            if b == 0 and (s != 1 or cin != cout):
                convs.append((1, cin, cout, hout))
            cin, h = cout, hout
    return convs


def resnet_train_flops_per_image(*, stage_sizes, bottleneck: bool,
                                 image_size: int, num_classes: int) -> float:
    """Twice the multiply-adds of every convolution and of the classifier,
    forward; three times that to train."""
    fwd = sum(2.0 * k * k * cin * cout * hout * hout for k, cin, cout, hout
              in _resnet_convs(stage_sizes, bottleneck, image_size))
    fwd += 2.0 * 512 * (4 if bottleneck else 1) * num_classes
    return 3.0 * fwd


# ----------------------------------------------------------- serving

def decode_tick_bytes(cfg: Dict, live_tokens: float) -> float:
    """Bytes one serving tick must read: every weight once, and the keys
    and values of the tokens live in the cache."""
    spec = cfg["serving_bytes"]
    return spec["weight_bytes"] + live_tokens * spec["kv_bytes_per_token"]
