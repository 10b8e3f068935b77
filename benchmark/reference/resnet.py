"""Plain reference for scoring with a folded bottleneck ResNet: the forward
pass in straightforward ``jax.numpy`` and float32.

It follows He et al., arXiv:1512.03385 (Table 1; v1.5 puts a stage's stride
on its 3x3 convolution) with every frozen BatchNorm folded into the
convolution before it: a convolution without bias, then a float32 bias,
then the ReLU where the architecture has one. The 7x7 stride-2 stem is
computed as the plain convolution it is, not in the space-to-depth form
the program uses. Input is ImageNet-normalised 0..255 RGB.

It imports nothing of the program and is given nothing the program made:
the folded kernels and biases come from :func:`make_params`, from the
seed's key, and the driver hands the same values to the program.

``quant`` puts a lower precision in the reference's place (the control):
both operands of every convolution and of the head are rounded to that
type, scaled per tensor.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.rounding import operand_rounder, round_to

HIGHEST = jax.lax.Precision.HIGHEST
IMAGENET_MEAN = (123.675, 116.28, 103.53)
IMAGENET_STD = (58.395, 57.12, 57.375)


def _blocks(cfg: dict):
    """(name, input channels, filters, stride) of every block, in order."""
    w, e = cfg["width"], cfg["bottleneck_expansion"]
    cin = w
    for stage, n_blocks in enumerate(cfg["stage_sizes"]):
        f = w * 2 ** stage
        for block in range(n_blocks):
            stride = 2 if stage > 0 and block == 0 else 1
            yield f"stage{stage}_block{block}", cin, f, stride
            cin = e * f


def param_shapes(cfg: dict) -> dict:
    """Flat ``path -> shape``; the paths are flax's, joined by '/'."""
    w, e, c = cfg["width"], cfg["bottleneck_expansion"], cfg["num_channels"]
    shapes = {"conv_stem/kernel": (7, 7, c, w), "fold_stem/bias": (w,)}
    cin = w
    for name, cin, f, stride in _blocks(cfg):
        shapes[f"{name}/conv1/kernel"] = (1, 1, cin, f)
        shapes[f"{name}/fold1/bias"] = (f,)
        shapes[f"{name}/conv2/kernel"] = (3, 3, f, f)
        shapes[f"{name}/fold2/bias"] = (f,)
        shapes[f"{name}/conv3/kernel"] = (1, 1, f, e * f)
        shapes[f"{name}/fold3/bias"] = (e * f,)
        if cin != e * f or stride != 1:
            shapes[f"{name}/proj/kernel"] = (1, 1, cin, e * f)
            shapes[f"{name}/fold_proj/bias"] = (e * f,)
        cin = e * f
    shapes["head/kernel"] = (cin, cfg["num_classes"])
    shapes["head/bias"] = (cfg["num_classes"],)
    return shapes


def make_params(cfg: dict, key) -> dict:
    """All folded weights from one key, as float32 values: kernels hold
    what ``param_dtype`` holds exactly, biases stay float32. Traceable."""
    store = jnp.dtype(cfg["param_dtype"])
    shapes = param_shapes(cfg)
    out = {}
    for (path, shape), k in zip(shapes.items(),
                                jax.random.split(key, len(shapes))):
        z = jax.random.normal(k, shape, jnp.float32)
        if path.endswith("/kernel"):
            before_relu = path.split("/")[-2] in ("conv_stem", "conv1",
                                                  "conv2")
            gain = 2.0 if before_relu else 1.0
            v = z * math.sqrt(gain / math.prod(shape[:-1]))
            v = round_to(v, store)
        else:
            v = 0.05 * z
        out[path] = v
    return out


def forward(params: dict, images, cfg: dict, quant: str | None = None):
    """Logits ``[B, classes]`` of images ``[B, S, S, C]`` in 0..255."""
    q = operand_rounder(quant)

    def conv(x, kernel, stride, padding):
        return jax.lax.conv_general_dilated(
            q(x), q(kernel), window_strides=(stride, stride),
            padding=padding, dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST)

    def site(x, name, conv_name, fold_name, stride=1, relu=True):
        k = params[f"{name}{conv_name}/kernel"]
        pad = "SAME" if k.shape[0] > 1 else "VALID"
        y = conv(x, k, stride, pad) + params[f"{name}{fold_name}/bias"]
        return jnp.maximum(y, 0.0) if relu else y

    x = images.astype(jnp.float32)
    x = (x - jnp.asarray(IMAGENET_MEAN)) / jnp.asarray(IMAGENET_STD)
    x = site(x, "", "conv_stem", "fold_stem", stride=2)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    e = cfg["bottleneck_expansion"]
    for name, cin, f, stride in _blocks(cfg):
        n = name + "/"
        y = site(x, n, "conv1", "fold1")
        y = site(y, n, "conv2", "fold2", stride=stride)
        y = site(y, n, "conv3", "fold3", relu=False)
        if cin != e * f or stride != 1:
            x = site(x, n, "proj", "fold_proj", stride=stride, relu=False)
        x = jnp.maximum(y + x, 0.0)
    x = jnp.mean(x, axis=(1, 2))
    return (jnp.dot(q(x), q(params["head/kernel"]), precision=HIGHEST)
            + params["head/bias"])


def score_rows(cfg: dict, key, rows, block_rows: int,
               quant: str | None = None):
    """Reference logits of ``rows`` (``[N, S*S*C]`` or ``[N, S, S, C]``
    uint8), in blocks of rows, as one host array."""
    import numpy as np

    s, c = cfg["image_size"], cfg["num_channels"]
    params = jax.jit(lambda k: make_params(cfg, k))(key)
    fwd = jax.jit(lambda p, x: forward(p, x, cfg, quant))
    out = []
    for start in range(0, len(rows), block_rows):
        block = np.asarray(rows[start:start + block_rows]).reshape(-1, s, s, c)
        pad = block_rows - len(block)
        if pad:
            block = np.concatenate([block, np.zeros((pad, s, s, c),
                                                    block.dtype)])
        out.append(np.asarray(fwd(params, block))[:block_rows - pad])
    return np.concatenate(out)
