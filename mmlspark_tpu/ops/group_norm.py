"""Fused GroupNorm(+activation) Pallas kernel for NHWC feature maps.

ResNet-50's GroupNorm layers are pure HBM traffic — XLA lowers GN as
separate reduce + normalize passes over the feature map. This kernel
reads each sample's (H·W, C) block into VMEM once and does everything
there: per-group statistics via two tiny mask matmuls (lane-aligned — no
awkward lane-dim reshapes), normalization, scale/bias, and the optional
ReLU that always follows GN in the ResNet blocks. One HBM read + one HBM
write per element.

Per-sample VMEM footprint: the largest ResNet-50 GN input is 56·56·256
(f32 ≈ 3.2 MB in + out) — comfortably inside the ~16 MB budget, so the
grid is simply the batch dimension. The wrapper hands the kernel
``[N, H·W, C]`` (a free reshape in XLA), so no spatial size needs an
in-kernel relayout.

Training still works: ``jax.custom_vjp`` routes the backward through the
jnp reference implementation (correctness first; the forward is the
featurize/inference hot path). The kernel is always compiled by Mosaic;
off-TPU a caller asks for jax's Pallas interpreter explicitly
(``pltpu.force_tpu_interpret_mode()``, what tier-1 does).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mmlspark_tpu.ops.pallas.budget import (
    VMEM_BUDGET, lane_pad, note_vmem_fallback,
)


def group_norm_reference(x: jnp.ndarray, scale: jnp.ndarray,
                         bias: jnp.ndarray, num_groups: int,
                         eps: float = 1e-6, relu: bool = False
                         ) -> jnp.ndarray:
    """Plain-jnp GroupNorm over the channel (last) axis of NHWC input."""
    n, h, w, c = x.shape
    _validate_groups(c, num_groups)
    cg = c // num_groups
    xf = x.astype(jnp.float32).reshape(n, h * w, num_groups, cg)
    mean = xf.mean(axis=(1, 3), keepdims=True)
    var = ((xf - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    out = out.reshape(n, h, w, c) * scale + bias
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype)


def _gn_kernel(x_ref, scale_ref, bias_ref, o_ref, *, num_groups: int,
               eps: float, relu: bool):
    hw, c = x_ref.shape[1], x_ref.shape[2]
    cg = c // num_groups
    xs = x_ref[0].astype(jnp.float32)

    # channel→group aggregation as a mask matmul (lane-aligned; avoids
    # lane-dim reshapes that Mosaic lays out badly)
    ch = jax.lax.broadcasted_iota(jnp.int32, (c, num_groups), 0)
    gr = jax.lax.broadcasted_iota(jnp.int32, (c, num_groups), 1)
    mask = (ch // cg == gr).astype(jnp.float32)        # (C, G)

    # statistics must aggregate in f32 — the MXU's default bf16 multiply
    # visibly corrupts means over thousands of elements
    denom = float(hw * cg)
    hi = jax.lax.Precision.HIGHEST

    # TWO-PASS (centered) variance. The one-pass E[x²] − E[x]² form
    # cancels catastrophically in f32 for feature maps whose mean
    # dominates their spread (x ~ μ ± σ with μ ≫ σ: E[x²] and E[x]²
    # agree to ~σ²/μ² relative — at μ=200, σ=0.02 the f32 one-pass
    # variance was pure noise). Centering first costs one extra pass
    # over the VMEM-resident block and keeps every accumulation f32 —
    # the same stance flax's force_float32_reductions takes, and what a
    # bf16 activation policy (docs/quantization.md) relies on
    s1 = jnp.sum(xs, axis=0, keepdims=True)            # (1, C) Σx
    g1 = jnp.dot(s1, mask, precision=hi) / denom       # (1, G) group mean
    mean_c = jnp.dot(g1, mask.T, precision=hi)         # (1, C) broadcast
    xc = xs - mean_c                                   # centered block
    s2 = jnp.sum(xc * xc, axis=0, keepdims=True)       # (1, C) Σ(x−μ)²
    g2 = jnp.dot(s2, mask, precision=hi) / denom       # (1, G) variance
    rstd = jax.lax.rsqrt(jnp.maximum(g2, 0.0) + eps)

    # group→channel broadcast via the transposed mask
    rstd_c = jnp.dot(rstd, mask.T, precision=hi)       # (1, C)

    out = xc * rstd_c
    out = out * scale_ref[...].astype(jnp.float32) \
        + bias_ref[...].astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _group_norm_fwd_pallas(x: jnp.ndarray, scale: jnp.ndarray,
                           bias: jnp.ndarray, num_groups: int, eps: float,
                           relu: bool) -> jnp.ndarray:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, h, w, c = x.shape
    kern = functools.partial(_gn_kernel, num_groups=num_groups, eps=eps,
                             relu=relu)
    out = pl.pallas_call(
        kern,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((1, h * w, c), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, c), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, h * w, c), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((n, h * w, c), x.dtype),
        name="group_norm",
    )(x.reshape(n, h * w, c), scale.reshape(1, c), bias.reshape(1, c))
    return out.reshape(n, h, w, c)


def _fits_vmem(h: int, w: int, c: int, itemsize: int) -> bool:
    """Conservative per-sample VMEM estimate for the kernel's buffers.

    The lane dim pads to 128, and the kernel holds the input block, an f32
    working copy, its square, the f32 output, and the cast output —
    roughly ``HW × C_pad × (2·itemsize + 12)`` bytes. Blocks that would
    blow the ~16 MB budget take the XLA lowering, visibly (the
    112×112×64 ResNet stem GN is the notable case: C=64 pads 2×)."""
    est = h * w * lane_pad(c) * (2 * itemsize + 12)
    return est < VMEM_BUDGET


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _group_norm_custom(x: jnp.ndarray, scale: jnp.ndarray,
                       bias: jnp.ndarray, num_groups: int, eps: float,
                       relu: bool) -> jnp.ndarray:
    return _group_norm_fwd_pallas(x, scale, bias, num_groups, eps, relu)


def _validate_groups(c: int, num_groups: int) -> None:
    # channels that match no group would silently normalize to zero (the
    # iota mask has no row for them) — refuse loudly instead
    if num_groups <= 0 or c % num_groups != 0:
        raise ValueError(
            f"group_norm: {c} channels not divisible into "
            f"{num_groups} groups")


def group_norm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
               num_groups: int, eps: float = 1e-6,
               relu: bool = False) -> jnp.ndarray:
    """Fused GroupNorm(+ReLU): Pallas forward, reference-impl backward.
    A per-sample block past the VMEM budget runs the XLA reference and
    says so (warning + the ``ops.pallas.vmem_fallback`` counter)."""
    n, h, w, c = x.shape
    _validate_groups(c, num_groups)
    if not _fits_vmem(h, w, c, x.dtype.itemsize):
        note_vmem_fallback("group_norm", (h, w, c))
        return group_norm_reference(x, scale, bias, num_groups, eps, relu)
    return _group_norm_custom(x, scale, bias, num_groups, eps, relu)


def _gn_fwd(x, scale, bias, num_groups, eps, relu):
    out = _group_norm_fwd_pallas(x, scale, bias, num_groups, eps, relu)
    return out, (x, scale, bias)


def _gn_bwd(num_groups, eps, relu, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(
        lambda xx, ss, bb: group_norm_reference(
            xx, ss, bb, num_groups, eps, relu), x, scale, bias)
    return vjp(g)


_group_norm_custom.defvjp(_gn_fwd, _gn_bwd)
