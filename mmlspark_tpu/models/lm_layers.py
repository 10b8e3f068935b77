"""What the language-model families of ``models/lm_*.py`` share
(``docs/lm.md``): the by-kind weight stacks and how a ``_stacks`` declares
them, the one-scan layer runner :func:`run_layers`, and the blocks and
initialisers more than one family uses. The head and the tail of every
family's ``__call__`` are :func:`~mmlspark_tpu.models.lm.lm_input` and
:func:`~mmlspark_tpu.models.lm.readout`, in the hub."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.models.lm import _fan_in_normal, rms_norm
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import flash_attention

# the decay rates a fresh Mamba-2 layer's heads start from (Dao & Gu,
# arXiv:2405.21060: ``A`` uniform over a range, one scalar a head)
A_INIT = (1.0, 16.0)


class KindStack(nn.Module):
    """The weights of all ``layers`` layers of one kind, each leaf a
    ``[layers, ...]`` stack; ``leaves``: ``(name, shape of one layer's,
    initializer, dtype)``."""

    layers: int
    leaves: tuple

    @nn.compact
    def __call__(self) -> dict:
        return {name: self.param(name, init, (self.layers,) + shape, dtype)
                for name, shape, init, dtype in self.leaves}


def leaf_makers(param_dtype) -> tuple:
    """``(kernel, f32, stack)``, what a ``_stacks`` declares with:
    ``kernel(name, *shape)`` a fan-in-normal ``param_dtype`` matrix,
    ``f32(name, init, *shape)`` a float32 leaf, ``stack(name, layers,
    *leaves)`` the :class:`KindStack` ``name`` (``{}`` at no layers)."""

    def kernel(name, *shape):
        return (name, shape, _fan_in_normal, param_dtype)

    def f32(name, init, *shape):
        return (name, shape, init, jnp.float32)

    def stack(name, layers, *leaves):
        return KindStack(layers, leaves, name=name)() if layers else {}

    return kernel, f32, stack


def layer_counts(kinds, names: tuple, gauge: str = "lm.layers") -> dict:
    """``{name: how many of kinds are name}`` for each of ``names``, each
    set as the gauge ``gauge{kind=name}`` (the stack as built)."""
    count = {name: list(kinds).count(name) for name in names}
    reg = _obs_registry()
    for name in names:
        reg.gauge(gauge, kind=name).set(count[name])
    return count


def _at(stack, index):
    """Layer ``index`` (static or traced) of every leaf of a kind's stack."""
    return jax.tree.map(lambda v: jax.lax.dynamic_index_in_dim(
        v, index, keepdims=False), stack)


def run_layers(h, kinds: tuple, norm, mixers: dict, dtype, after=None,
               no_stats=None):
    """Every layer of a stack as ONE ``lax.scan``. Layer ``i`` of kind
    ``kinds[i]`` adds ``mixers[kinds[i]](at, norm(h, i))`` to ``h``, ``at``
    its place among the layers of its kind, then ``after(h, i)`` where that
    is given; each sum is taken in float32 and rounded to ``dtype``. ``i``
    and ``at`` are traced: the body picks the mixer by one ``lax.switch``
    among the kinds present, in the order of ``mixers`` (a stack of one
    kind has no conditional).

    A mixer or ``after`` that has statistics returns ``(y, *stats)``; a
    mixer that returns ``y`` alone emits ``no_stats`` (zeros of the same
    form), and ``after``'s statistics, where it has some, replace the
    mixer's.
    Returns ``h`` and the statistics stacked over all layers (``None``
    where there are none)."""
    present = [k for k in mixers if k in kinds]
    index = np.asarray([kinds[:i].count(k) for i, k in enumerate(kinds)],
                       np.int32)
    branch = np.asarray([present.index(k) for k in kinds], np.int32)

    def with_stats(out, none):
        return (out[0], out[1:]) if isinstance(out, tuple) else (out, none)

    def block(h, xs):
        layer, which, at = xs
        x = norm(h, layer)
        branches = [lambda k=k: with_stats(mixers[k](at, x), no_stats)
                    for k in present]
        mixed, stats = (branches[0]() if len(branches) == 1
                        else jax.lax.switch(which, branches))
        h = (h.astype(jnp.float32) + mixed).astype(dtype)
        if after is not None:
            y, stats = with_stats(after(h, layer), stats)
            h = (h.astype(jnp.float32) + y).astype(dtype)
        return h, stats

    return jax.lax.scan(block, h, (jnp.arange(len(kinds)),
                                   jnp.asarray(branch), jnp.asarray(index)))


def _dot(x, w, dtype):
    """A bias-free product on ``dtype`` operands, float32 out."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def gated_mlp(p: dict, x, dtype):
    """``(silu(x W_gate) * x W_up) W_down``, float32 out."""
    return _dot(jax.nn.silu(_dot(x, p["gate"], dtype))
                * _dot(x, p["up"], dtype), p["down"], dtype)


def rope_tables(positions, dim: int, theta: float) -> tuple:
    """``(cos, sin)`` ``[L, dim / 2]`` float32: angle ``p * theta^(-i /
    (dim / 2))``, no scaling."""
    inv = theta ** (-np.arange(dim // 2, dtype=np.float64) / (dim // 2))
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope_half(x, cos, sin):
    """Rotate the pairs ``(x[i], x[i + dim / 2])`` of the last axis; ``x``
    ``[B, L, heads, dim]`` float32, tables ``[L, dim / 2]``."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention_leaves(c, kernel) -> tuple:
    """The projections :func:`grouped_attention` reads: ``q``, ``k``,
    ``v``, ``o``."""
    d, hd = c.hidden_size, c.head_dim
    q, kv = c.num_attention_heads * hd, c.num_key_value_heads * hd
    return (kernel("q", d, q), kernel("k", d, kv), kernel("v", d, kv),
            kernel("o", q, d))


def grouped_attention(p: dict, x, positions, c):
    """Causal attention of ``c.num_attention_heads`` query heads on
    ``c.num_key_value_heads`` key/value heads, on normed ``x`` ``[B, L,
    d]``. What it does beyond the projections and the core follows what it
    is handed: per-head q/k RMSNorms where the tree ``p`` holds their
    scales (``q_norm`` / ``k_norm``; eps ``c.norm_eps``), half-split RoPE
    (``c.rope_theta``) where ``positions`` are given and no positional term
    where they are ``None``. The core's scope is ``lm/mqa/attention`` on one
    key/value head, ``lm/gqa/attention`` on more."""
    b, n, _ = x.shape
    h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = _dot(x, p["q"], c.dtype).reshape(b, n, h, hd)
    k = _dot(x, p["k"], c.dtype).reshape(b, n, hkv, hd)
    v = _dot(x, p["v"], c.dtype).reshape(b, n, hkv, hd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], c.norm_eps)
        k = rms_norm(k, p["k_norm"], c.norm_eps)
    if positions is not None:
        cos, sin = rope_tables(positions, hd, c.rope_theta)
        q, k = apply_rope_half(q, cos, sin), apply_rope_half(k, cos, sin)

    def bhtd(a):
        return a.astype(c.dtype).transpose(0, 2, 1, 3)

    with jax.named_scope(f"lm/{'mqa' if hkv == 1 else 'gqa'}/attention"):
        o = flash_attention(bhtd(q), bhtd(k), bhtd(v), causal=True,
                            scale=hd ** -0.5)
    return _dot(o.transpose(0, 2, 1, 3).reshape(b, n, h * hd), p["o"],
                c.dtype)


def _a_log(key, shape, dtype):
    """``log A``, ``A`` uniform over ``A_INIT``: one decay rate a head."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_INIT)
                   ).astype(dtype)


def _dt_bias(lo: float = 1e-3, hi: float = 1e-1, floor: float = 1e-4):
    """``softplus^-1`` of step sizes log-uniform over ``[lo, hi]``, floored
    at ``floor``. The defaults are the step sizes a fresh Mamba layer
    starts from (Gu & Dao, arXiv:2312.00752, section 3.6), so that a
    channel forgets over 10 to 1,000+ positions; the floor lies below the
    range and never acts."""
    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        math.log(lo), math.log(hi)))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init
