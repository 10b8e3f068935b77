"""On-device train preprocessing — the :class:`DevicePreprocess` spec the
jitted step fuses.

The reference pipeline (OpenCV ``ImageTransformer`` + in-reader
``Imgcodecs.imdecode``) does all image work host-side, and the train
path used to mirror it: ``data/readers.py`` decoded (and optionally
resized) on a host thread pool and every pixel crossed host→device at
final-batch f32 width. uint8 shipping is 4× fewer H2D bytes; this module
moves the REST of the image work — resize, crop, flip,
brightness/contrast, normalization — inside the compiled train step,
generalizing the in-step ``input_scale`` cast:

* **thin wire**: the loader ships source-resolution (or minimal
  crop-envelope — :func:`envelope_batch`) uint8 batches; geometry and
  normalization replay on device, where the VPU hides them under the
  matmuls;
* **one program**: the spec's ops trace into the SAME jitted step —
  zero extra dispatches, zero extra H2D/D2H crossings;
* **deterministic randomness**: every stochastic op draws from a key
  folded from the GLOBAL STEP (``fold_in(PRNGKey(cfg.seed), step)``
  where ``step`` is the device step counter carried in the train state),
  so prefetch on/off, host count, and resume-from-checkpoint all replay
  the identical augmentation stream bit-for-bit — the step counter is
  checkpointed, so a resumed run continues the stream exactly where the
  interrupted run left it.

Stage order (fixed; ``apply`` is the one implementation):

1. **geometry** — random source crop (``src_crop``) + bilinear
   ``resize``, fused with the normalize cast in one pass
   (:func:`mmlspark_tpu.ops.resize.fused_resize_norm`);
2. **normalize** — float32 × ``input_scale`` (inside the fused pass);
3. **stochastic augment** — pad+random-crop / flips / brightness /
   contrast (:func:`mmlspark_tpu.ops.augment.augment_batch`, operating
   on normalized floats);
4. **standardize** — optional per-channel ``(x - mean) / std``.

**The float-input convention** (the host-baseline A/B): uint8 input
takes the full chain; float input is taken as *already host-preprocessed
through stage 2* (:func:`host_preprocess` is the exact host twin of
stages 1–2), so only stages 3–4 run on device. Both wire forms therefore
see identical stochastic draws and identical post-normalize values —
the loss-parity contract ``tools/perf_smoke.py
check_train_device_preprocess`` gates in tier-1.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np


@dataclasses.dataclass(frozen=True)
class DevicePreprocess:
    """Declarative on-device preprocessing spec, fused into the jitted
    train step by ``TrainConfig(preprocess=...)``.

    Geometry fields (``src_crop``, ``resize``) consume the thin uint8
    wire form; stochastic fields mirror
    :mod:`mmlspark_tpu.ops.augment` (values in the NORMALIZED scale —
    ``brightness=0.1`` shifts [0, 1]-scaled pixels); ``mean``/``std``
    standardize per channel after augmentation."""

    resize: tuple | None = None      # (oh, ow) bilinear target
    src_crop: tuple | None = None    # (ch, cw) random source window
    crop_pad: int = 0                # post-resize reflect pad + random crop
    flip_lr: bool = False
    flip_ud: bool = False
    brightness: float = 0.0          # uniform shift in [-b, b], normalized
    contrast: tuple | None = None    # (lo, hi) per-sample contrast factor
    mean: tuple | None = None        # per-channel, normalized scale
    std: tuple | None = None

    def __post_init__(self):
        for field in ("resize", "src_crop", "contrast", "mean", "std"):
            v = getattr(self, field)
            if v is not None:
                object.__setattr__(self, field, tuple(v))
        for field in ("resize", "src_crop"):
            v = getattr(self, field)
            if v is not None and (len(v) != 2 or min(v) < 1):
                raise ValueError(f"DevicePreprocess.{field} must be a "
                                 f"(height, width) pair >= 1, got {v!r}")
        if self.contrast is not None and (
                len(self.contrast) != 2
                or not 0 <= self.contrast[0] <= self.contrast[1]):
            raise ValueError("DevicePreprocess.contrast must be a "
                             f"0 <= lo <= hi pair, got {self.contrast!r}")
        if self.crop_pad < 0:
            raise ValueError(
                f"DevicePreprocess.crop_pad must be >= 0, "
                f"got {self.crop_pad}")
        if self.std is not None and any(s == 0 for s in self.std):
            raise ValueError("DevicePreprocess.std contains a zero "
                             f"channel: {self.std!r}")

    # ---- construction / identity ----

    @classmethod
    def parse(cls, obj: Any) -> "DevicePreprocess | None":
        """None / spec / plain-dict (the TrainConfig wire form) → spec."""
        if obj is None or isinstance(obj, cls):
            return obj
        if isinstance(obj, dict):
            return cls(**obj)
        raise TypeError(
            "TrainConfig.preprocess must be a DevicePreprocess, a dict of "
            f"its fields, or None; got {type(obj).__name__}")

    def fingerprint(self) -> str:
        """Canonical string identity for the checkpoint-schedule
        fingerprint: resuming under a CHANGED spec would silently replay
        different pixels into the remaining steps."""
        d = dataclasses.asdict(self)
        return ",".join(f"{k}={d[k]!r}" for k in sorted(d))

    # ---- static geometry replay (the analyzer's infer_schema) ----

    def out_shape(self, in_shape: tuple) -> tuple:
        """Replay the spec over an ``(h, w, c)`` input geometry; raises
        ``ValueError`` on a geometry the device chain would reject —
        the pre-flight half of ``analysis.audit_train_preprocess``."""
        if len(in_shape) != 3:
            raise ValueError(
                f"DevicePreprocess expects (h, w, c) image geometry, "
                f"got {tuple(in_shape)}")
        h, w, c = (int(d) for d in in_shape)
        if self.src_crop is not None:
            ch, cw = self.src_crop
            if ch > h or cw > w:
                raise ValueError(
                    f"src_crop {self.src_crop} larger than the source "
                    f"image ({h}, {w})")
            h, w = ch, cw
        if self.resize is not None:
            h, w = self.resize
        if self.crop_pad and self.crop_pad > min(h, w) - 1:
            raise ValueError(
                f"crop_pad {self.crop_pad} needs reflect padding wider "
                f"than the {h}x{w} image allows (max {min(h, w) - 1})")
        for field in ("mean", "std"):
            v = getattr(self, field)
            if v is not None and len(v) not in (1, c):
                raise ValueError(
                    f"{field} has {len(v)} channels for {c}-channel "
                    "images")
        return h, w, c


def resolve(obj: Any) -> DevicePreprocess | None:
    """``TrainConfig.preprocess`` (spec | dict | None) → validated spec."""
    return DevicePreprocess.parse(obj)


def _geometry_normalize(spec: DevicePreprocess, key, x, scale):
    """Stages 1–2 on the thin uint8 wire form: random source crop +
    bilinear resize + f32 × scale, as ONE fused pass."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.resize import fused_resize_norm

    n, h, w, _c = x.shape
    if spec.src_crop is not None:
        ch, cw = spec.src_crop
        ky, kx = jax.random.split(key)
        oy = jax.random.randint(ky, (n,), 0, h - ch + 1, dtype=jnp.int32)
        ox = jax.random.randint(kx, (n,), 0, w - cw + 1, dtype=jnp.int32)
    else:
        ch, cw = h, w
        oy = ox = jnp.zeros((n,), jnp.int32)
    out_hw = spec.resize or (ch, cw)
    if spec.src_crop is None and tuple(out_hw) == (h, w):
        # identity geometry: the fused pass degenerates to the round-3
        # cast convention exactly (v00 × 1 = v00) — skip the gathers
        return x.astype(jnp.float32) * np.float32(scale)
    return fused_resize_norm(x, oy, ox, (ch, cw), out_hw, scale)


def apply(spec: DevicePreprocess, key, x, scale: float):
    """The in-step entry: full chain for uint8 input, stages 3–4 only for
    float input (already host-preprocessed — see the module docstring's
    float-input convention). Pure jax; traces into the step program."""
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops import augment

    k_geom, k_aug = jax.random.split(key)
    if x.dtype == jnp.uint8:
        x = _geometry_normalize(spec, k_geom, x, scale)
    else:
        x = x.astype(jnp.float32)
    x = augment.augment_batch(
        k_aug, x, flip_lr=spec.flip_lr, flip_ud=spec.flip_ud,
        crop_pad=spec.crop_pad, brightness=spec.brightness,
        contrast=spec.contrast)
    if spec.mean is not None or spec.std is not None:
        if spec.mean is not None:
            x = x - jnp.asarray(spec.mean, jnp.float32)
        if spec.std is not None:
            x = x / jnp.asarray(spec.std, jnp.float32)
    # the batch is data, not a differentiation target: make that explicit
    # so no backward rule is ever required of the fused kernel
    return jax.lax.stop_gradient(x)


def host_preprocess(spec: DevicePreprocess, batch: np.ndarray,
                    scale: float) -> np.ndarray:
    """The exact host twin of stages 1–2 (numpy): deterministic geometry
    (``resize``) + the normalize cast. This is the HOST-PREPROCESS
    baseline wire form of the thin-wire A/B — feed its float output to a
    Trainer carrying the same spec and the device applies only the
    stochastic stages, with identical draws. Random source crops cannot
    be replayed host-side (the draw lives in the step): specs with
    ``src_crop`` have no host baseline."""
    from mmlspark_tpu.ops.resize import fused_resize_norm_host

    if spec.src_crop is not None:
        raise ValueError(
            "host_preprocess cannot replay a random src_crop — the draw "
            "happens inside the jitted step; drop src_crop from the "
            "host-baseline spec")
    x = np.asarray(batch)
    if x.ndim != 4:
        raise ValueError(
            f"host_preprocess expects an [N, H, W, C] batch, got shape "
            f"{x.shape}")
    n, h, w, _c = x.shape
    if spec.resize is not None and tuple(spec.resize) != (h, w):
        zeros = np.zeros(n, np.int32)
        return fused_resize_norm_host(x, zeros, zeros, (h, w),
                                      spec.resize, scale)
    return x.astype(np.float32) * np.float32(scale)


def envelope_batch(images: list, envelope: tuple) -> np.ndarray:
    """Pack ragged source-resolution HWC uint8 images into ONE
    ``[N, H, W, C]`` batch by zero-pad / center-crop only — no
    interpolation, pure memcpy — the minimal crop-envelope wire format
    for thin-wire streaming of mixed-resolution sources. Larger images
    center-crop to the envelope, smaller ones center inside zero
    padding; the device spec replays the real geometry (crop + resize)
    from there."""
    h, w = int(envelope[0]), int(envelope[1])
    if not images:
        return np.zeros((0, h, w, 3), np.uint8)
    arrs = []
    for img in images:
        a = np.asarray(img)
        if a.dtype != np.uint8:
            # the envelope IS the thin uint8 wire form — silently
            # truncating normalized floats into it would ship all-black
            # batches; refuse loudly instead
            raise TypeError(
                f"envelope_batch packs the uint8 wire form; got dtype "
                f"{a.dtype} (host-preprocessed float batches skip the "
                "envelope and ship as-is)")
        if a.ndim == 2:
            a = a[:, :, None]
        arrs.append(a)
    c = max(a.shape[2] for a in arrs)
    out = np.zeros((len(arrs), h, w, c), np.uint8)
    for i, a in enumerate(arrs):
        sh, sw = a.shape[:2]
        # crop (centered) when the source overflows the envelope
        cy, cx = max((sh - h) // 2, 0), max((sw - w) // 2, 0)
        a = a[cy:cy + h, cx:cx + w]
        sh, sw = a.shape[:2]
        # center (zero pad) when it underflows
        oy, ox = (h - sh) // 2, (w - sw) // 2
        out[i, oy:oy + sh, ox:ox + sw, :a.shape[2]] = a
    return out
