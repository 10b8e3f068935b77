"""Per-sample crop → bilinear resize → normalize, in one traced pass.

The gather path of on-device train preprocessing
(:mod:`mmlspark_tpu.train.preprocess`): each sample takes a (possibly
random) fixed-size crop window out of the source-resolution uint8 image,
bilinearly resizes the window to the training resolution, and scales the
result into normalized float32 — the geometry the thin-wire ingest mode
replays on device instead of paying for it on a host thread pool.

Two implementations share ONE coordinate/weight grid (:func:`_grids`,
precomputed in numpy float32 at trace time) and ONE tap/blend body
(:func:`_blend`), so they can be pinned against each other:

* :func:`fused_resize_norm` — pure XLA (``vmap`` over samples), what the
  train step runs;
* :func:`fused_resize_norm_host` — the numpy oracle host baselines and
  property tests compare against: ≤ 2 ULP from the device path (XLA
  contracts the four-tap blend into FMAs, numpy cannot — one extra
  rounding per tap), far inside the 1e-5 end-to-end loss tolerance.

There is no Pallas kernel for this op: the one that was here was built
on ``dynamic_slice`` and ``take`` over a uint8 window with a 3-wide lane
dim, which the Pallas TPU lowering has no rule for (it had only ever run
interpreted). A kernel that can compile is a matmul formulation, and
whether it beats this XLA lowering is a chip measurement nobody has
taken (ROADMAP Speed 8).

Coordinate math matches the repo's bilinear convention
(``stages/image._device_resize_step`` / native ``img_resize_bilinear``):
align-corners f32 source coordinates, left-associated blend — except the
output stays float32 (training consumes normalized floats; the inference
path's final uint8 quantization step does not apply).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def _grids(ch: int, cw: int, oh: int, ow: int) -> tuple:
    """Static gather indices + blend weights for a (ch, cw) → (oh, ow)
    align-corners bilinear resize. All float math in numpy float32 so the
    XLA path and the numpy oracle consume bit-identical constants."""
    sy = (np.float32(ch - 1) / np.float32(oh - 1)) if oh > 1 else np.float32(0)
    sx = (np.float32(cw - 1) / np.float32(ow - 1)) if ow > 1 else np.float32(0)
    fy = np.arange(oh, dtype=np.float32) * sy
    fx = np.arange(ow, dtype=np.float32) * sx
    y0 = fy.astype(np.int32)
    x0 = fx.astype(np.int32)
    y1 = np.minimum(y0 + 1, ch - 1)
    x1 = np.minimum(x0 + 1, cw - 1)
    # subtract in f32 (int32 operands would promote the whole weight
    # chain to f64, and the numpy oracle would then blend in f64 while
    # the device paths blend in canonicalized f32)
    wy = (fy - y0.astype(np.float32)).reshape(oh, 1, 1)
    wx = (fx - x0.astype(np.float32)).reshape(1, ow, 1)
    one = np.float32(1)
    # the four corner weights, precomputed: v = Σ v_ij * w_ij is then a
    # single multiply-add sequence identical across implementations
    w00 = (one - wy) * (one - wx)
    w01 = (one - wy) * wx
    w10 = wy * (one - wx)
    w11 = wy * wx
    return y0, y1, x0, x1, w00, w01, w10, w11


def _blend(win, g, scale: np.float32):
    """The shared tap/blend/normalize body over one (ch, cw, C) window.
    jnp and numpy expose identical take/astype/arithmetic surface, so the
    SAME code is the XLA path and the numpy oracle — implementations
    cannot drift apart op by op."""
    xp = jnp if isinstance(win, jnp.ndarray) else np
    y0, y1, x0, x1, w00, w01, w10, w11 = g
    rows0 = xp.take(win, y0, axis=0)
    rows1 = xp.take(win, y1, axis=0)
    v00 = xp.take(rows0, x0, axis=1).astype(np.float32)
    v01 = xp.take(rows0, x1, axis=1).astype(np.float32)
    v10 = xp.take(rows1, x0, axis=1).astype(np.float32)
    v11 = xp.take(rows1, x1, axis=1).astype(np.float32)
    v = v00 * w00 + v01 * w01 + v10 * w10 + v11 * w11
    return v * scale


def fused_resize_norm(x, oy, ox, crop: tuple, out_hw: tuple,
                      scale: float) -> jnp.ndarray:
    """Crop → bilinear resize → normalize over an ``[N, H, W, C]`` batch:
    sample ``i`` takes the ``crop``-sized window at ``(oy[i], ox[i])``,
    resizes it to ``out_hw``, and returns float32 ``* scale``. Per-sample
    window slice + bilinear taps + normalize, vmapped over the batch."""
    ch, cw = int(crop[0]), int(crop[1])
    h, w, c = x.shape[1:]
    if ch > h or cw > w:
        raise ValueError(f"crop window ({ch}, {cw}) larger than the "
                         f"source image ({h}, {w})")
    g = _grids(ch, cw, int(out_hw[0]), int(out_hw[1]))
    s = np.float32(scale)

    def one(img, y, xo):
        win = jax.lax.dynamic_slice(img, (y, xo, 0), (ch, cw, c))
        return _blend(win, g, s)

    return jax.vmap(one)(x, oy.astype(jnp.int32), ox.astype(jnp.int32))


def fused_resize_norm_host(x, oy, ox, crop: tuple, out_hw: tuple,
                           scale: float) -> np.ndarray:
    """Numpy oracle: the identical tap/blend/normalize sequence on host.
    Also the "host-preprocess" baseline wire format of the thin-wire A/B
    (``train/preprocess.host_preprocess``)."""
    x = np.asarray(x)
    ch, cw = int(crop[0]), int(crop[1])
    oh, ow = int(out_hw[0]), int(out_hw[1])
    g = _grids(ch, cw, oh, ow)
    s = np.float32(scale)
    oy = np.asarray(oy, np.int64)
    ox = np.asarray(ox, np.int64)
    out = np.empty((len(x), oh, ow, x.shape[-1]), np.float32)
    for i in range(len(x)):
        win = x[i, oy[i]:oy[i] + ch, ox[i]:ox[i] + cw]
        out[i] = _blend(win, g, s)
    return out
