"""ViT as ``models/vit.py`` builds it, counted from shapes.

Strided-convolution patch embedding, a CLS token, learned positions,
``layers`` pre-norm blocks (fused QKV, full attention, output
projection, two-layer MLP), linear head on CLS. ``heads`` does not
change the count: scores and the weighted sum are T*T*hidden each
whatever the split.
"""

from typing import Any


def forward_macs(image_size: int = 224, patch_size: int = 16,
                 in_channels: int = 3, hidden: int = 768, layers: int = 12,
                 heads: int = 12, mlp_dim: int = 3072,
                 num_classes: int = 1000) -> int:
    del heads
    patches = (image_size // patch_size) ** 2
    tokens = patches + 1
    macs = patches * patch_size * patch_size * in_channels * hidden
    per_layer = (
        tokens * hidden * 3 * hidden      # QKV
        + 2 * tokens * tokens * hidden    # QK^T and AV
        + tokens * hidden * hidden        # output projection
        + 2 * tokens * hidden * mlp_dim   # MLP
    )
    return macs + layers * per_layer + hidden * num_classes


def parameters(image_size: int = 224, patch_size: int = 16,
               in_channels: int = 3, hidden: int = 768, layers: int = 12,
               mlp_dim: int = 3072, num_classes: int = 1000,
               **_: Any) -> int:
    tokens = (image_size // patch_size) ** 2 + 1
    n = patch_size * patch_size * in_channels * hidden + hidden  # patchify
    n += hidden + tokens * hidden                                # cls, pos
    per_layer = (
        2 * hidden                                # LayerNorm 1
        + hidden * 3 * hidden + 3 * hidden        # QKV
        + hidden * hidden + hidden                # output projection
        + 2 * hidden                              # LayerNorm 2
        + hidden * mlp_dim + mlp_dim              # MLP in
        + mlp_dim * hidden + hidden               # MLP out
    )
    n += layers * per_layer
    return n + 2 * hidden + hidden * num_classes + num_classes
