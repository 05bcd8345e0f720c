"""Operations of one ResNet-50 training sample, from shapes.

Counted here, independent of ``models/resnet.py``: the convolutions and the
classifier of the 50-layer column of He et al. (arXiv:1512.03385, table 1)
with the v1.5 stride placement (stride on the 3x3).  Two operations per
multiply-add; batch norm, ReLU, pooling and the loss are left out (under
1% of the total); the backward pass is twice the forward pass.
"""


def conv_macs(size: int, k: int, c_in: int, c_out: int, stride: int):
    out = -(-size // stride)  # SAME padding
    return out * out * k * k * c_in * c_out, out


def forward_macs(config: dict) -> int:
    size = config["image_size"]
    filters = config["num_filters"]
    total, size = conv_macs(size, 7, 3, filters, 2)      # stem
    size = -(-size // 2)                                 # 3x3/2 max pool
    c_in = filters
    for stage, blocks in enumerate(config["stage_sizes"]):
        width = filters * 2 ** stage
        for block in range(blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            macs, _ = conv_macs(size, 1, c_in, width, 1)
            total += macs
            macs, out = conv_macs(size, 3, width, width, stride)
            total += macs
            macs, _ = conv_macs(out, 1, width, 4 * width, 1)
            total += macs
            if c_in != 4 * width or stride != 1:         # projection
                macs, _ = conv_macs(size, 1, c_in, 4 * width, stride)
                total += macs
            size, c_in = out, 4 * width
    return total + c_in * config["num_classes"]          # classifier


def train_flops_per_sample(config: dict) -> float:
    """Forward plus backward (2 x forward), 2 operations a multiply-add."""
    return 3 * 2 * forward_macs(config)
