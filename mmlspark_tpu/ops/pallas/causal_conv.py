"""The short depthwise causal convolution as one pass over its input.

For every row and channel ``c``, with ``K`` taps::

    z_t[c]   = pre_t[c] * x_t[c]                    (``pre`` optional)
    y_t[c]   = sum_j taps[j, c] * z_{t - (K - 1) + j}[c] + bias[c]
    out_t[c] = post_t[c] * silu(y_t[c])             (bias, SiLU, ``post`` optional)

``z`` zero before the row's start. Written as array code
(:func:`causal_taps`, the reference) XLA pads ``z`` and reads one shifted
copy a tap: ``K`` reads of the input from HBM where one would do. The
kernel reads every input position once and writes every output once:

* the grid is ``(row, channel block, sequence chunk)``, the chunk axis
  sequential; a block is ``[1, chunk, channels of a block]``, positions on
  sublanes as the projection that made them leaves them. Inside a chunk a
  loop walks tiles of sixteen positions and carries the tile before as a
  value, so a tap is a sublane shift of registers, not a second read; the
  last tile of a chunk waits in VMEM scratch for the next chunk and is
  zeroed at a row's first;
* **the operands are read where they lie.** Both callers cut a wide
  float32 product into parts (``[u | z]``, ``[B | C | u]``): the kernel is
  handed the wide array and the channel each operand starts at, which
  becomes a block offset in that operand's index map. No slice is copied
  to feed the call. (Where an offset or the channel count is no whole
  number of lane rows the parts are cut out and padded: a small model's
  shapes.)
* what is fused follows what the caller hands in: ``pre_at`` / ``post_at``
  (the gated short convolution's ``B *`` before and ``C *`` after),
  ``bias`` and ``silu`` (the Mamba mixer's), the cast to ``dtype`` on the
  way out. Everything up to that cast is float32. ``cast_at`` names a
  part that is only cast, as a second output (the mixer's gate, the other
  half of the same rows: a pass of its own over the wide product
  otherwise);
* the chunk length comes from the VMEM budget alone
  (:func:`chunk_positions`); a tail that fills no whole chunk is a partial
  block (what lies beyond the row's end reaches only outputs that are
  dropped: the convolution is causal), so no padded copy exists and a row
  of any length takes the kernel.

**One trace a shape.** The ``pallas_call`` sits behind ONE module-level
``jax.jit`` (:func:`_conv_call`), so the layers of a model that call it
at one shape share a trace (across ``jax.eval_shape``, the jit and every
output node's program) and one lowered function a module: what a process
pays in Python before the compile cache can answer does not grow with the
number of call sites.

``impl`` resolves as :func:`~mmlspark_tpu.ops.pallas.attention.resolve_impl`
does (the kernel on the TPU, the reference elsewhere; a CPU test asks for
the interpreter itself). A chunk that cannot fit the budget gives way to
the reference under ``auto``, logged and counted
(``ops.pallas.vmem_fallback{kernel=causal_conv}``), and raises under
``pallas``. Counted when the kernel call is traced:
``ops.pallas.causal_conv_grid_steps``; gauge ``ops.pallas.causal_conv_chunk``
(positions a chunk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import _takes_kernel
from mmlspark_tpu.ops.pallas.budget import VMEM_BUDGET

GRID_STEPS_COUNTER = "ops.pallas.causal_conv_grid_steps"
CHUNK_GAUGE = "ops.pallas.causal_conv_chunk"

_LANES = 128
# positions of a tile: one packed sublane tile of a 2-byte type, two of
# float32; what the body's loop carries, so the most taps less one
_TILE = 16
# channel-block widths, the widest that divides the channels and every
# operand's offset is taken
_BLOCKS = (1024, 512, 256, 128)
# positions of the longest chunk: past it a grid step's fixed cost is
# already under a hundredth of its work
_MAX_CHUNK = 512


def causal_taps(z, taps, bias=None):
    """The depthwise causal convolution of ``z`` ``[B, L, channels]`` with
    ``taps`` ``[K, channels]`` (float32): ``out_t = sum_j taps[j] * z[t -
    (K - 1) + j]`` (+ ``bias`` a channel), ``z`` zero before the row's
    start. The one tap loop of both families: the gated short convolution
    (3 taps, no bias) and the Mamba mixer's (4 taps, a bias, SiLU after)."""
    n, lead = z.shape[1], taps.shape[0] - 1
    padded = jnp.pad(z, ((0, 0), (lead, 0), (0, 0)))
    mixed = taps[lead] * z
    for j in range(lead):
        mixed = mixed + taps[j] * padded[:, j:j + n]
    return mixed if bias is None else mixed + bias


def causal_conv_reference(wide, taps, *, channels, at=0, pre_at=None,
                          post_at=None, cast_at=None, bias=None, silu=False,
                          dtype):
    """:func:`causal_conv` as array code around :func:`causal_taps`."""
    def cut(start):
        return wide[..., start:start + channels]

    z = cut(at).astype(jnp.float32)
    if pre_at is not None:
        z = cut(pre_at).astype(jnp.float32) * z
    y = causal_taps(z, taps.astype(jnp.float32), bias)
    if silu:
        y = jax.nn.silu(y)
    if post_at is not None:
        y = cut(post_at).astype(jnp.float32) * y
    y = y.astype(dtype)
    return y if cast_at is None else (y, cut(cast_at).astype(dtype))


def chunk_positions(length: int, block: int, moved_bytes: int) -> int:
    """Positions of one chunk, from the row's length, the channels of a
    block, the bytes a channel moves a position (every operand read and
    every output) and the VMEM budget alone: the moved blocks are
    double-buffered and one float32 block more is left to the body's
    temporaries; the largest power of two that fits, at most ``_MAX_CHUNK``
    and no more than the row needs; ``0`` where not even one tile
    fits."""
    most = VMEM_BUDGET // (block * (2 * moved_bytes + 4))
    if most < _TILE:
        return 0
    chunk = _TILE
    while chunk * 2 <= min(most, _MAX_CHUNK) and chunk < length:
        chunk *= 2
    return chunk


def _conv_kernel(*refs, taps: int, chunk: int, pre: bool, post: bool,
                 cast: bool, bias: bool, silu: bool):
    # grid (row, channel block, chunk). Blocks: x / pre / post / gate and
    # the outputs [1, chunk, block]; taps [K, block], bias [1, block].
    # Scratch [tile, block] float32: the last tile of z in the chunk before
    import jax.experimental.pallas as pl

    refs = list(refs)
    x_ref = refs.pop(0)
    pre_ref = refs.pop(0) if pre else None
    post_ref = refs.pop(0) if post else None
    gate_ref = refs.pop(0) if cast else None
    taps_ref = refs.pop(0)
    bias_ref = refs.pop(0) if bias else None
    o_ref = refs.pop(0)
    cast_ref = refs.pop(0) if cast else None
    last_ref, = refs
    lead, tile = taps - 1, _TILE

    @pl.when(pl.program_id(2) == 0)
    def _start():
        last_ref[...] = jnp.zeros(last_ref.shape, jnp.float32)

    def positions(i, before):
        rows = pl.ds(pl.multiple_of(i * tile, tile), tile)
        z = x_ref[0, rows, :].astype(jnp.float32)
        if pre:
            z = pre_ref[0, rows, :].astype(jnp.float32) * z
        window = jnp.concatenate([before, z], axis=0)
        # the reference's order of the sum: the newest tap first
        mixed = taps_ref[lead:lead + 1, :] * z
        for j in range(lead):
            mixed = mixed + taps_ref[j:j + 1, :] \
                * window[tile - lead + j:2 * tile - lead + j]
        if bias:
            mixed = mixed + bias_ref[...]
        if silu:
            mixed = jax.nn.silu(mixed)
        if post:
            mixed = post_ref[0, rows, :].astype(jnp.float32) * mixed
        o_ref[0, rows, :] = mixed.astype(o_ref.dtype)
        if cast:
            cast_ref[0, rows, :] = gate_ref[0, rows, :].astype(cast_ref.dtype)
        return z

    last_ref[...] = jax.lax.fori_loop(0, chunk // tile, positions,
                                      last_ref[...])


@functools.partial(jax.jit, static_argnames=(
    "channels", "block", "chunk", "offsets", "silu", "dtype"))
def _conv_call(parts, taps, bias, *, channels: int, block: int, chunk: int,
               offsets: tuple, silu: bool, dtype):
    """The kernel call. ``parts`` are the arrays that hold x, pre, post
    and the part to cast (``None`` where there is none; one array handed
    several times where the parts lie in one product) and ``offsets`` the
    BLOCK offset of each in its array; ``channels`` a whole number of
    blocks. Module-level and jitted so that equal call sites share a trace
    and a lowering."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, length = parts[0].shape[:2]
    blocks, chunks = channels // block, -(-length // chunk)
    _obs_registry().counter(GRID_STEPS_COUNTER).add(rows * blocks * chunks)
    _obs_registry().gauge(CHUNK_GAUGE).set(chunk)

    def moved(offset):
        return pl.BlockSpec((1, chunk, block),
                            lambda r, j, i: (r, i, offset + j))

    def a_channel(depth):
        return pl.BlockSpec((depth, block), lambda r, j, i: (0, j))

    held = [(a, s) for a, s in zip(parts, offsets) if a is not None]
    _, pre, post, cast = (a is not None for a in parts)
    operands = [a for a, _ in held] + [taps]
    specs = [moved(s) for _, s in held] + [a_channel(taps.shape[0])]
    if bias is not None:
        operands.append(bias.reshape(1, -1))
        specs.append(a_channel(1))
    out = jax.ShapeDtypeStruct((rows, length, channels), dtype)
    return pl.pallas_call(
        functools.partial(
            _conv_kernel, taps=taps.shape[0], chunk=chunk, pre=pre,
            post=post, cast=cast, bias=bias is not None, silu=silu),
        grid=(rows, blocks, chunks),
        in_specs=specs,
        out_specs=[moved(0)] * (1 + cast),
        out_shape=[out] * (1 + cast),
        scratch_shapes=[pltpu.VMEM((_TILE, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="causal_conv",
    )(*operands)


def causal_conv(wide, taps, *, channels: int, at: int = 0, pre_at=None,
                post_at=None, cast_at=None, bias=None, silu: bool = False,
                dtype, impl: str = "auto"):
    """The causal convolution of channels ``[at, at + channels)`` of
    ``wide`` ``[rows, L, width]`` with ``taps`` ``[K, channels]``, and
    what surrounds it elementwise (the module docstring has the
    equations): multiplied before by the channels from ``pre_at`` and
    after by those from ``post_at`` where given, ``bias`` ``[channels]``
    added and SiLU applied where asked. Returns ``[rows, L, channels]`` in
    ``dtype``, rounded once from float32; with ``cast_at`` a pair, the
    second the channels from ``cast_at`` in ``dtype``."""
    if taps.shape[0] > _TILE:
        raise ValueError(f"{taps.shape[0]} taps: the kernel carries at most "
                         f"{_TILE - 1} positions between chunks")
    starts = (at, pre_at, post_at, cast_at)
    held = [s for s in starts if s is not None]
    block = next((b for b in _BLOCKS if channels % b == 0
                  and all(s % b == 0 for s in held)), 0)
    outputs = 1 if cast_at is None else 2
    moved_bytes = len(held) * wide.dtype.itemsize \
        + outputs * jnp.dtype(dtype).itemsize
    chunk = chunk_positions(wide.shape[1], block or _LANES, moved_bytes)
    if not _takes_kernel(impl, "causal_conv", chunk > 0,
                         (_TILE, block or _LANES)):
        return causal_conv_reference(
            wide, taps, channels=channels, at=at, pre_at=pre_at,
            post_at=post_at, cast_at=cast_at, bias=bias, silu=silu,
            dtype=dtype)
    taps = taps.astype(jnp.float32)
    bias = None if bias is None else bias.astype(jnp.float32)
    # no whole lane rows: the parts are cut out and padded to them
    pad = 0 if block else -channels % _LANES

    def part(start):
        """The array a part is read from and its block offset there."""
        if start is None:
            return None, None
        if block:
            return wide, start // block
        return jnp.pad(wide[..., start:start + channels],
                       ((0, 0), (0, 0), (0, pad))), 0

    parts, offsets = zip(*map(part, starts))
    if not block:
        taps = jnp.pad(taps, ((0, 0), (0, pad)))
        bias = None if bias is None else jnp.pad(bias, (0, pad))
    out = _conv_call(parts, taps, bias, channels=channels + pad,
                     block=block or _LANES, chunk=chunk, offsets=offsets,
                     silu=silu, dtype=jnp.dtype(dtype))
    if pad:
        out = [o[..., :channels] for o in out]
    return out[0] if cast_at is None else tuple(out)
