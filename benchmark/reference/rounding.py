"""Rounding to a narrower float type, for both references: the storage type
a configuration states, and the lower precision of the control."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def round_to(a, dtype):
    """``a`` rounded to what ``dtype`` holds, kept in float32. An explicit
    ``reduce_precision``: a cast there and back is a pair the compiler may
    drop (``xla_allow_excess_precision``), and then nothing is rounded."""
    info = jnp.finfo(jnp.dtype(dtype))
    if info.bits >= 32:
        return a
    return jax.lax.reduce_precision(a, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def operand_rounder(quant: str | None):
    """Per-tensor scaled rounding of a product's operand to ``quant``;
    the identity for ``None``."""
    if quant is None:
        return lambda a: a
    info = jnp.finfo(jnp.dtype(quant))
    # the largest finite value of an IEEE-like type of these widths
    top = (2.0 - 2.0 ** -info.nmant) * 2.0 ** (2 ** (info.nexp - 1) - 1)

    def q(a):
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
        return round_to(a / scale, quant) * scale
    return q
