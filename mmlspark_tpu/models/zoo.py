"""Built-in model architectures (the model-zoo analog).

The reference's zoo is a manifest of pretrained CNTK graphs (ConvNet
CIFAR-10, ResNet-50, …) downloaded by ``ModelDownloader`` (reference:
downloader/src/main/scala/{ModelDownloader,Schema}.scala). Here
architectures are flax modules defined in-repo; weights come either from
random init (training) or downloaded checkpoints
(:mod:`mmlspark_tpu.data.downloader`).

TPU-first choices: NHWC layout (XLA:TPU's native conv layout), bfloat16
compute with float32 params/accumulation, channel counts in MXU-friendly
multiples of 128 where the architecture allows, named output nodes for
featurization cuts (the ``cutOutputLayers`` analog, reference:
image-featurizer/src/main/scala/ImageFeaturizer.scala:116-140).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.models.bundle import ModelBundle


class PatchConv3x3(nn.Module):
    """3×3 same-padding stride-1 conv on a tiny-channel input, computed in
    2×2 space-to-depth form — numerically identical, MXU-shaped.

    A direct RGB-stem conv contracts over just 3 of the MXU's 128 lanes —
    measured ~1.7 TFLOP/s on v5e in round 2, ~40× off peak, dominating the
    whole CIFAR step (not re-measured on current code). Reorganizing 2×2 pixel blocks into channels makes
    the same op a [B·H/2·W/2, 9·4·cin] × [9·4·cin, 4·features] matmul
    (contraction 108 wide, output 256 wide for the CIFAR stem): 4× fewer
    output tiles, 4× the contraction depth. The block-form weight matrix is
    assembled at trace time from the standard ``nn.Conv`` parameter layout
    ((3,3,cin,features) kernel + bias), so checkpoints are interchangeable
    with the direct formulation; zero entries encode the taps that fall
    outside each output pixel's 3×3 window.

    Requires even H and W (pad the input otherwise).
    """

    features: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        cin, F = x.shape[-1], self.features
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (3, 3, cin, F))
        bias = self.param("bias", nn.initializers.zeros, (F,))
        B, H, W = x.shape[0], x.shape[1], x.shape[2]
        if H % 2 or W % 2:
            raise ValueError(f"PatchConv3x3 needs even H/W, got {H}x{W}")
        k = kernel.astype(self.dtype)
        # block-form weights Wb[(rb·3+cb)·4cin + (uu·2+vv)·cin + c,
        #                       (u·2+v)·F + f]
        #   = kernel[dy, dx, c, f] at dy = 2rb+uu-u-1, dx = 2cb+vv-v-1
        # (zero where the tap leaves the 3×3 window)
        wb = jnp.zeros((9 * 4 * cin, 4 * F), self.dtype)
        for rb in range(3):
            for cb in range(3):
                for uu in range(2):
                    for vv in range(2):
                        p0 = ((rb * 3 + cb) * 4 + uu * 2 + vv) * cin
                        for u in range(2):
                            dy = 2 * rb + uu - u - 1
                            if not 0 <= dy < 3:
                                continue
                            for v in range(2):
                                dx = 2 * cb + vv - v - 1
                                if not 0 <= dx < 3:
                                    continue
                                q0 = (u * 2 + v) * F
                                wb = wb.at[p0:p0 + cin, q0:q0 + F].set(
                                    k[dy, dx])
        h, w = H // 2, W // 2
        # space-to-depth: [B,H,W,cin] -> [B,h,w,4cin], block channel
        # (uu·2+vv)·cin + c
        xs = x.astype(self.dtype).reshape(B, h, 2, w, 2, cin)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(B, h, w, 4 * cin)
        # one zero block of padding: the conv's SAME halo lives in the
        # nearest row/col of each neighbor block, the rest hits zeros in wb
        xp = jnp.pad(xs, ((0, 0), (1, 1), (1, 1), (0, 0)))
        patches = jnp.concatenate(
            [xp[:, i:i + h, j:j + w, :] for i in range(3) for j in range(3)],
            axis=-1)
        y = patches @ wb  # [B,h,w,4F]
        # depth-to-space back to [B,H,W,F]
        y = y.reshape(B, h, w, 2, 2, F).transpose(0, 1, 3, 2, 4, 5)
        y = y.reshape(B, H, W, F)
        return y + bias.astype(self.dtype)


class ConvNetCifar(nn.Module):
    """CIFAR-10 ConvNet — flagship model, notebook-301 analog.

    Mirrors the capability of the reference zoo's ``ConvNet_CIFAR10`` entry
    (conv/pool stack + dense head). Compute runs in bfloat16 for the MXU;
    params stay float32. The RGB stem runs as :class:`PatchConv3x3` (same
    parameters, MXU-friendly formulation).

    Output nodes (selectable like CNTK node names): ``features`` (penultimate
    dense activations, used by ImageFeaturizer) and ``logits``.
    """

    num_classes: int = 10
    # MXU-sized widths: measured step MFU on v5e is 54.9% at (64,128,256)
    # but 76.7% at (128,256,512) — the narrow stem/blocks leave MXU lanes
    # idle, wide ones fill them (round-2 measurement, not re-measured)
    widths: Sequence[int] = (128, 256, 512)
    dense_width: int = 512
    dtype: Any = jnp.bfloat16
    stem: str = "direct"  # "direct" (nn.Conv) | "patch" (s2d matmul form);
    # measured in the full train step XLA's direct lowering beats the
    # hand-rolled s2d form (8.4 vs 9.8 ms/step @ B=1024) — keep "direct"

    OUTPUT_NAMES = ("features", "logits")

    @nn.compact
    def __call__(self, x, output: str = "logits", train: bool = False):
        x = x.astype(self.dtype)
        for i, w in enumerate(self.widths):
            if x.shape[-1] < 32 and self.stem == "patch":
                x = PatchConv3x3(w, dtype=self.dtype, name=f"conv{i}a")(x)
            else:
                x = nn.Conv(w, (3, 3), dtype=self.dtype, name=f"conv{i}a")(x)
            x = nn.relu(x)
            x = nn.Conv(w, (3, 3), dtype=self.dtype, name=f"conv{i}b")(x)
            x = nn.relu(x)
            x = nn.max_pool(x, (2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(self.dense_width, dtype=self.dtype, name="dense0")(x)
        x = nn.relu(x)
        features = x.astype(jnp.float32)
        if output == "features":
            return features
        logits = nn.Dense(self.num_classes, dtype=self.dtype, name="head")(x)
        return logits.astype(jnp.float32)


class MLP(nn.Module):
    """Plain MLP — used by TrainClassifier's NN family and tests."""

    features: Sequence[int] = (128, 128)
    num_outputs: int = 2
    dtype: Any = jnp.float32

    OUTPUT_NAMES = ("features", "logits")

    @nn.compact
    def __call__(self, x, output: str = "logits", train: bool = False):
        x = x.astype(self.dtype)
        for i, f in enumerate(self.features):
            x = nn.Dense(f, dtype=self.dtype, name=f"dense{i}")(x)
            x = nn.relu(x)
        if output == "features":
            return x.astype(jnp.float32)
        return nn.Dense(self.num_outputs, name="head")(x).astype(jnp.float32)


# ---- zoo registry ----

ZOO: dict[str, Callable[..., ModelBundle]] = {}


def register_model(name: str):
    def deco(fn):
        ZOO[name] = fn
        return fn
    return deco


def init_bundle(module: Any, input_spec: tuple, name: str,
                preprocess: str | None = None, seed: int = 0,
                output_names: tuple | None = None) -> ModelBundle:
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.zeros((1,) + tuple(input_spec), jnp.float32)
    variables = module.init(rng, dummy)
    return ModelBundle(
        module=module,
        params=variables["params"],
        input_spec=tuple(input_spec),
        output_names=output_names or getattr(
            type(module), "OUTPUT_NAMES", ("logits",)),
        preprocess=preprocess,
        name=name,
    )


@register_model("ConvNet_CIFAR10")
def conv_net_cifar(num_classes: int = 10, seed: int = 0, **kw) -> ModelBundle:
    return init_bundle(ConvNetCifar(num_classes=num_classes, **kw),
                       (32, 32, 3), "ConvNet_CIFAR10",
                       preprocess="center_128", seed=seed)


@register_model("MLP")
def mlp(input_dim: int = 16, num_outputs: int = 2, seed: int = 0,
        **kw) -> ModelBundle:
    return init_bundle(MLP(num_outputs=num_outputs, **kw),
                       (input_dim,), "MLP", seed=seed)


@register_model("ResNet50")
def resnet50_bundle(num_classes: int = 1000, input_size: int = 224,
                    seed: int = 0, **kw) -> ModelBundle:
    """BASELINE config 3 backbone (reference zoo's pretrained ResNet-50,
    Schema.scala:54-74). GroupNorm variant — see models/resnet.py."""
    from mmlspark_tpu.models.resnet import resnet50
    return init_bundle(resnet50(num_classes=num_classes, **kw),
                       (input_size, input_size, 3), "ResNet50",
                       preprocess="imagenet_norm", seed=seed)


def _folded_resnet_bundle(name: str, factory: Any, num_classes: int,
                          input_size: int, seed: int,
                          param_dtype: Any, **kw) -> ModelBundle:
    """Init a frozen-BN net and fold its statistics into the conv weights
    (models/resnet.py:fold_batchnorm). The published zoo path folds
    *trained* statistics at publish time (tools/build_model_repo.py); this
    zoo entry folds the init stats so the inference architecture is
    constructible without a repo download."""
    from mmlspark_tpu.models.resnet import fold_batchnorm
    bn_net = factory(num_classes=num_classes, norm="batch", **kw)
    dummy = jnp.zeros((1, input_size, input_size, 3), jnp.float32)
    # init + fold are host-side setup (the fold itself is numpy): pin them
    # to the CPU backend so bundle construction never compiles a 224² init
    # for the accelerator only to fold it away on the host. A
    # JAX_PLATFORMS pin that excludes cpu makes the backend unavailable —
    # fall back to the default device there
    import contextlib
    try:
        ctx = jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        ctx = contextlib.nullcontext()
    with ctx:
        variables = bn_net.init(jax.random.PRNGKey(seed), dummy)
        params = fold_batchnorm(variables, param_dtype=param_dtype)
    folded = factory(num_classes=num_classes, norm="none", **kw)
    return ModelBundle(module=folded, params=params,
                       input_spec=(input_size, input_size, 3),
                       output_names=type(folded).OUTPUT_NAMES,
                       preprocess="imagenet_norm", name=name)


@register_model("ResNet50_Infer")
def resnet50_infer_bundle(num_classes: int = 1000, input_size: int = 224,
                          seed: int = 0, param_dtype: Any = jnp.bfloat16,
                          stem: str = "s2d", **kw) -> ModelBundle:
    """Frozen-norm inference ResNet-50 — the featurization variant.

    The reference's zoo ResNet-50 is a BatchNorm network whose frozen
    inference statistics fold into the conv weights (Schema.scala:54-74,
    ImageFeaturizer.scala:116-140) — zero norm cost at scoring time. This
    is the TPU-native equivalent: ``norm="none"`` architecture + folded
    params (bf16 by default — frozen inference weights need no f32
    master) + the space-to-depth stem (``stem="s2d"``, same param layout).
    Measured on v5e at batch 256/224² in round 5: 0.39 MFU (GroupNorm
    train variant) → 0.64 MFU folded; not re-measured on current code."""
    from mmlspark_tpu.models.resnet import resnet50
    return _folded_resnet_bundle("ResNet50_Infer", resnet50, num_classes,
                                 input_size, seed, param_dtype, stem=stem,
                                 **kw)


@register_model("ResNet_Small_Infer")
def resnet_small_infer_bundle(num_classes: int = 10, input_size: int = 32,
                              seed: int = 0,
                              param_dtype: Any = jnp.bfloat16,
                              stem: str = "s2d", **kw) -> ModelBundle:
    """CI-scale folded variant (same fold path as ResNet50_Infer)."""
    from mmlspark_tpu.models.resnet import resnet18_thin
    return _folded_resnet_bundle("ResNet_Small_Infer", resnet18_thin,
                                 num_classes, input_size, seed,
                                 param_dtype, stem=stem, **kw)


@register_model("ResNet_Small")
def resnet_small_bundle(num_classes: int = 10, input_size: int = 32,
                        seed: int = 0, **kw) -> ModelBundle:
    """Same ResNet family at CI scale (tests, local-repo publishing)."""
    from mmlspark_tpu.models.resnet import resnet18_thin
    return init_bundle(resnet18_thin(num_classes=num_classes, **kw),
                       (input_size, input_size, 3), "ResNet_Small",
                       preprocess="imagenet_norm", seed=seed)


@register_model("ViT_B16")
def vit_b16_bundle(num_classes: int = 1000, input_size: int = 224,
                   seed: int = 0, **kw) -> ModelBundle:
    """BASELINE config 5 flagship (distributed fine-tune)."""
    from mmlspark_tpu.models.vit import vit_b16
    return init_bundle(vit_b16(num_classes=num_classes, **kw),
                       (input_size, input_size, 3), "ViT_B16",
                       preprocess="scale_pm1", seed=seed)


@register_model("ViT_Tiny")
def vit_tiny_bundle(num_classes: int = 10, input_size: int = 32,
                    seed: int = 0, **kw) -> ModelBundle:
    from mmlspark_tpu.models.vit import vit_tiny
    return init_bundle(vit_tiny(num_classes=num_classes, **kw),
                       (input_size, input_size, 3), "ViT_Tiny",
                       preprocess="scale_pm1", seed=seed)


@register_model("BiLSTM_MedTag")
def bilstm_medtag_bundle(vocab_size: int = 8192, num_tags: int = 16,
                         max_len: int = 613, seed: int = 0,
                         **kw) -> ModelBundle:
    """Notebook-304 analog (medical entity tagger; the reference pads
    sentences to a fixed 613 tokens — kept as the default input length)."""
    import jax as _jax

    from mmlspark_tpu.models.sequence import BiLSTMTagger
    module = BiLSTMTagger(vocab_size=vocab_size, num_tags=num_tags, **kw)
    tokens = jnp.zeros((1, max_len), jnp.int32)
    params = module.init(_jax.random.PRNGKey(seed), tokens)["params"]
    return ModelBundle(module=module, params=params, input_spec=(max_len,),
                       output_names=BiLSTMTagger.OUTPUT_NAMES,
                       name="BiLSTM_MedTag")


def get_model(name: str, **kwargs: Any) -> ModelBundle:
    if name not in ZOO:
        raise KeyError(f"unknown zoo model {name!r}; available: {sorted(ZOO)}")
    return ZOO[name](**kwargs)
