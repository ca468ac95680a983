"""ResNet-18 as ``models/resnet.py`` builds it, counted from shapes.

Basic blocks of two 3x3 convolutions, a 1x1 projection where the shape
changes, stride 2 at the head of stages 2-4, global mean pool, linear
head. Convolutions carry no bias; every GroupNorm has scale and bias.
"""

from typing import Any, Sequence


def _conv_macs(out_hw: int, kernel: int, c_in: int, c_out: int) -> int:
    return out_hw * out_hw * kernel * kernel * c_in * c_out


def forward_macs(image_size: int = 32, in_channels: int = 3, width: int = 64,
                 stage_sizes: Sequence[int] = (2, 2, 2, 2),
                 num_classes: int = 10, small_inputs: bool = True) -> int:
    if small_inputs:
        hw = image_size
        macs = _conv_macs(hw, 3, in_channels, width)
    else:
        hw = image_size // 2
        macs = _conv_macs(hw, 7, in_channels, width)
        hw //= 2  # 3x3/2 max pool
    c_in = width
    for i, blocks in enumerate(stage_sizes):
        c_out = width * 2 ** i
        for b in range(blocks):
            if i > 0 and b == 0:
                hw //= 2
            macs += _conv_macs(hw, 3, c_in, c_out)
            macs += _conv_macs(hw, 3, c_out, c_out)
            if c_in != c_out:
                macs += _conv_macs(hw, 1, c_in, c_out)
            c_in = c_out
    return macs + c_in * num_classes


def parameters(in_channels: int = 3, width: int = 64,
               stage_sizes: Sequence[int] = (2, 2, 2, 2),
               num_classes: int = 10, small_inputs: bool = True,
               **_: Any) -> int:
    k = 3 if small_inputs else 7
    n = k * k * in_channels * width + 2 * width
    c_in = width
    for i, blocks in enumerate(stage_sizes):
        c_out = width * 2 ** i
        for _b in range(blocks):
            n += 9 * c_in * c_out + 2 * c_out
            n += 9 * c_out * c_out + 2 * c_out
            if c_in != c_out:
                n += c_in * c_out + 2 * c_out
            c_in = c_out
    return n + c_in * num_classes + num_classes
