"""Operations a configuration's forward pass needs, from its sizes alone.

One multiply-accumulate is two operations. Only the matrix products and
convolutions are counted (norms, activations, softmax and the residual
sums are a rounding error beside them), nothing recomputed is counted,
and a backward pass costs twice its forward pass. Nothing here comes
from the compiler: XLA's ``cost_analysis`` moves with the compiler and
counts what was emitted, not what the algorithm needs.
"""

from __future__ import annotations


def vit_forward_flops(cfg: dict) -> dict:
    """Forward operations of one image through a ViT, by part."""
    d = cfg["hidden_size"]
    m = cfg["intermediate_size"]
    p = cfg["patch_size"]
    patches = (cfg["image_size"] // p) ** 2
    tokens = patches + (1 if cfg.get("class_token") else 0)
    layers = cfg["num_hidden_layers"]
    patch_embed = 2 * patches * (p * p * cfg["num_channels"]) * d
    # query, key, value and output projections, then the two MLP products
    block_weights = 2 * tokens * (4 * d * d + 2 * d * m)
    # Q.K^T and A.V, each tokens x tokens x (heads * head_dim)
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    block_attention = 2 * 2 * tokens * tokens * inner
    head = 2 * d * cfg["num_classes"]
    parts = {"patch_embed": patch_embed,
             "block_weights": layers * block_weights,
             "attention": layers * block_attention,
             "head": head}
    parts["total"] = sum(parts.values())
    return parts


def resnet_forward_flops(cfg: dict) -> dict:
    """Forward operations of one image through a bottleneck ResNet
    (v1.5: the stride sits on the 3x3), by part. The stem is counted as
    the 7x7 convolution the architecture states, not as the padded
    space-to-depth form a program may compute it in."""
    w = cfg["width"]
    e = cfg["bottleneck_expansion"]
    size = cfg["image_size"] // 2                  # after the 7x7 / 2 stem
    stem = 2 * size * size * 7 * 7 * cfg["num_channels"] * w
    size //= 2                                     # after the 3x3 / 2 pool
    cin = w
    blocks = 0
    for stage, n_blocks in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** stage
        for block in range(n_blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            out = size // stride
            blocks += 2 * size * size * cin * f            # conv1, 1x1
            blocks += 2 * out * out * 9 * f * f            # conv2, 3x3
            blocks += 2 * out * out * f * e * f            # conv3, 1x1
            if cin != e * f or stride != 1:
                blocks += 2 * out * out * cin * e * f      # projection
            cin, size = e * f, out
    head = 2 * cin * cfg["num_classes"]
    parts = {"stem": stem, "blocks": blocks, "head": head}
    parts["total"] = sum(parts.values())
    return parts


FORWARD = {"vit": vit_forward_flops, "resnet": resnet_forward_flops}


def forward_flops(cfg: dict) -> int:
    """Forward operations of one image, by the configuration's family."""
    return FORWARD[cfg["family"]](cfg)["total"]


def train_flops(cfg: dict) -> int:
    """Forward and backward operations of one trained image."""
    return 3 * forward_flops(cfg)


def peak_share_percent(run: dict, ops_per_row: int):
    """The window's work (``ops_per_row`` times its rows) over its seconds,
    as a percentage of the chips' bf16 peak: what an ``mfu`` reader
    returns. ``None`` where the window did no rows or no peak is known."""
    window = run["window"]
    if not window.get("rows") or run["peaks"] is None:
        return None
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * ops_per_row * window["rows"] / window["window_s"] / peak
