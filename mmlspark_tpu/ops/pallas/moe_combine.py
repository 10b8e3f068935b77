"""The expert layer's combine: each token's held picks read from the sorted
buffer where it lies, weighted and summed in float32.

``moe_dropless`` leaves its expert outputs in a sorted buffer ``ys [rows,
d]`` (pairs grouped by expert, token-major within an expert) and knows, for
every (token, pick), the buffer row that holds its output (``back [N, k]``)
and whether the pick's expert is held on this share at all (``present [N,
k]``)::

    y[t] = sum_{j < k, present[t, j]} weights[t, j] * ys[back[t, j]]

in float32, the terms added in pick order to a sum that starts at 0.
Written as array code (:func:`moe_combine_reference`) that is ``k`` row
gathers of ``N`` rows each, whose operand is the whole buffer, and a masked
sum: every pick's row is read, the absent ones only to be selected away,
and XLA's row gather slows 2.5-4x once its operand passes ~110-116 MiB (the
Nemotron cell's first rung is 354 MiB). The kernel reads only the rows the
present picks lie in, by DMA from ``ys`` where it lies in HBM:

* **the form of the copy.** Mosaic moves a row of a ``[rows, d]`` operand
  in HBM only as part of an aligned tile of 8 rows: a copy of one row (or
  two, of any type, or of a 32-bit view) is refused ("slice shape along
  dimension 0 must be aligned to tiling (8)"), and so is a dynamic load of
  one bfloat16 row from VMEM. So the unit of a copy is the 8-row tile,
  and a block of tokens stages the tiles its present picks lie in.
  Because the buffer is sorted by expert and token-major within one, a
  block's picks of one expert are consecutive rows, so its tiles are a
  range an expert (from its least row's tile to its greatest's), at most
  ``k B / 8 + 2 held`` tiles for ``B`` tokens: a block reads 1.1-2.7x the
  rows its present picks need at the benchmark's three expert shapes, not
  8x;
* the grid walks blocks of tokens in order. Each block's tiles, and each
  pick's row in the block's staging (``-1`` where absent), come from
  array code around the call that only reduces over the held experts
  (each expert's least and greatest row in the block: nothing is sorted
  or scattered), and reach the kernel as SMEM blocks of one grid step, so
  SMEM does not bound the tokens. A block's tile copies go into one of
  two VMEM slots, so the copies of block ``i + 1`` are in flight while
  block ``i`` is worked; the staged rows are widened to float32 once
  (a float32 row can be loaded from any sublane), and for every 8 tokens
  each present pick's row is copied to its token's row of a ``[k, 8, d]``
  tile. An absent pick copies nothing: its tile row holds whatever was
  there before and is *selected* away, not multiplied (0 x NaN is NaN);
* ``ys`` is never copied, sliced or padded to feed the call, so the rung's
  row count does not matter to the kernel, and rows past the buffer's
  last group reach no token's sum;
* the sum is the reference's, term for term: each term ``row.astype(f32)
  * w`` selected by ``present``, added in pick order to a float32
  accumulator that starts at 0, so the answers are equal to the bit;
* the block of tokens comes from the shapes and the staging budgets of
  :mod:`~mmlspark_tpu.ops.pallas.budget` alone (:func:`block_tokens`); a
  token count that is no multiple of it ends in a partial block whose
  padding picks are absent.

**One trace a shape**: the entry is ONE module-level ``jax.jit``
(:func:`_combine_call`), as :mod:`~mmlspark_tpu.ops.pallas.causal_conv`'s.

``impl`` resolves as :func:`~mmlspark_tpu.ops.pallas.attention.resolve_impl`
does (the kernel on the TPU, the reference elsewhere; a CPU test asks for
the interpreter itself). Shapes whose smallest block cannot fit the budget
give way to the reference under ``auto``, logged and counted
(``ops.pallas.vmem_fallback{kernel=moe_combine}``), and raise under
``pallas``. Set when a combine is traced: the gauge ``moe.combine``, 1
where the kernel serves it and 0 where the reference does; counted when
the kernel call is traced: ``ops.pallas.moe_combine_grid_steps``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import _takes_kernel
from mmlspark_tpu.ops.pallas.budget import (
    SMEM_BUDGET, STAGING_VMEM_BUDGET, STAGING_VMEM_SLACK, lane_pad)

GRID_STEPS_COUNTER = "ops.pallas.moe_combine_grid_steps"
# 1 where the last combine traced is the kernel, 0 where the array form
COMBINE_GAUGE = "moe.combine"

# rows of the tile a copy from HBM moves, and tokens a sum works on at a
# time (one float32 sublane tile)
_ROWS = 8
# the blocks of tokens tried, largest first: a larger block shares more
# tiles among its tokens
_BLOCKS = (512, 256, 128, 64, 32, 16)


def moe_combine_reference(ys, back, present, weights):
    """The combine as array code: pick by pick, a row gather of ``ys`` at
    ``back[:, j]``, weighted in float32 and selected by ``present``."""
    y = jnp.zeros((back.shape[0], ys.shape[1]), jnp.float32)
    for j in range(back.shape[1]):
        row = jnp.take(ys, back[:, j], axis=0, mode="clip")
        y = y + jnp.where(present[:, j, None],
                          row.astype(jnp.float32) * weights[:, j, None], 0.0)
    return y


def staged_tiles(block: int, top_k: int, held: int) -> int:
    """The most 8-row tiles the present picks of ``block`` tokens lie in:
    a run of consecutive rows an expert of the ``held``, and a run of ``L``
    rows spans at most ``(L + 6) // 8 + 1`` tiles, and no more than its
    picks; even, so the staging is whole 16-row tiles."""
    most = min(block * top_k, block * top_k // _ROWS + 2 * held)
    return most + most % 2


def _vmem_bytes(block: int, top_k: int, held: int, width: int,
                itemsize: int) -> int:
    """What the kernel's buffers take in VMEM at a block of ``block``
    tokens: the staging twice in ``ys``'s type and once in float32, the
    float32 ``[k, 8, d]`` tile, the output block twice, and the picks' rows
    and weights (a lane row each, double-buffered)."""
    rows = _ROWS * staged_tiles(block, top_k, held)
    return (rows * width * (2 * itemsize + 4) + top_k * _ROWS * width * 4
            + 2 * block * width * 4 + 2 * 2 * block * lane_pad(top_k) * 4)


def _smem_bytes(tokens: int, block: int, top_k: int, held: int) -> int:
    """What the kernel's scalar operands take in SMEM: the tiles of two
    blocks and the picks' staged rows of one, each double-buffered, and
    every block's tile count."""
    return 4 * (2 * (2 * staged_tiles(block, top_k, held) + block * top_k)
                + -(-tokens // block))


def block_tokens(tokens: int, top_k: int, held: int, width: int,
                 itemsize: int) -> int:
    """Tokens of one block, from the shapes and the staging budgets alone:
    the largest of ``_BLOCKS`` whose buffers fit ``STAGING_VMEM_BUDGET``
    and whose scalars fit ``SMEM_BUDGET``, and no more than the tokens
    need (in whole 16-token steps); ``0`` where not even the smallest
    fits."""
    need = max(16, -(-tokens // 16) * 16)
    fits = [b for b in _BLOCKS
            if _vmem_bytes(b, top_k, held, width, itemsize)
            <= STAGING_VMEM_BUDGET
            and _smem_bytes(tokens, b, top_k, held) <= SMEM_BUDGET]
    if not fits:
        return 0
    return min(fits[0], need)


def _combine_kernel(counts_ref, tiles_ref, next_ref, staged_ref, picks_ref,
                    w_ref, ys_hbm, y_ref, stage, wide, rows, sems, *,
                    top_k: int, block: int):
    # grid (block of tokens,), sequential. SMEM: counts_ref [blocks] (scalar
    # prefetch) how many tiles each block stages; blocks of one grid step:
    # tiles_ref / next_ref [1, most] the tiles this block and the next stage,
    # staged_ref [1, block * top_k] each pick's row in this block's staging or
    # -1. VMEM blocks: picks_ref (the picks' buffer rows, -1 where absent)
    # and w_ref [block, top_k]; y_ref [block, d] float32; ys_hbm the whole
    # buffer. Scratch: stage [2, most * 8, d] in ys's type (a slot a block),
    # wide [most * 8, d] float32, rows [top_k, 8, d] float32, a DMA
    # semaphore a slot
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    step, steps = pl.program_id(0), pl.num_programs(0)
    slot = step % 2

    def tile_copy(tile, into, at):
        return pltpu.make_async_copy(
            ys_hbm.at[pl.ds(pl.multiple_of(tile * _ROWS, _ROWS), _ROWS)],
            stage.at[into, pl.ds(pl.multiple_of(at * _ROWS, _ROWS), _ROWS)],
            sems.at[into])

    def fetch(tiles, count, into):
        """Start the copies of ``count`` tiles of ``tiles`` into slot
        ``into``."""
        def one(s, carry):
            tile_copy(tiles[0, s], into, s).start()
            return carry

        jax.lax.fori_loop(0, count, one, 0)

    @pl.when(step == 0)
    def _first():
        fetch(tiles_ref, counts_ref[0], 0)

    @pl.when(step + 1 < steps)
    def _next():
        fetch(next_ref, counts_ref[step + 1], 1 - slot)

    count = counts_ref[step]

    def wait(s, carry):
        # every copy moves one tile of the same bytes: one wait a copy
        tile_copy(0, slot, 0).wait()
        return carry

    jax.lax.fori_loop(0, count, wait, 0)

    def widen(i, carry):
        at = pl.ds(pl.multiple_of(i * 2 * _ROWS, 2 * _ROWS), 2 * _ROWS)
        wide[at, :] = stage[slot, at, :].astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, (count + 1) // 2, widen, 0)

    def tokens(i, carry):
        base = i * _ROWS * top_k
        for p in range(_ROWS):
            for j in range(top_k):
                row = staged_ref[0, base + p * top_k + j]

                @pl.when(row >= 0)
                def _copy():
                    rows[j, p:p + 1, :] = wide[pl.ds(row, 1), :]

        at = pl.ds(pl.multiple_of(i * _ROWS, _ROWS), _ROWS)
        acc = jnp.zeros((_ROWS, y_ref.shape[1]), jnp.float32)
        for j in range(top_k):
            held = picks_ref[at, j:j + 1] >= 0
            acc = acc + jnp.where(held, rows[j] * w_ref[at, j:j + 1], 0.0)
        y_ref[at, :] = acc
        return carry

    jax.lax.fori_loop(0, block // _ROWS, tokens, 0)


def _staging(rows, expert, block: int, most: int, held: int):
    """``(tiles [blocks, most], counts [blocks], staged [blocks, block *
    k])`` of the picks' buffer rows ``rows [blocks * block, k]`` and their
    experts ``expert`` (``held`` where absent): the tiles each block
    stages, how many, and each pick's row in its block's staging (``-1``
    where absent). A block's picks of one expert are consecutive rows, so
    its tiles are a range an expert, from the least row's to the
    greatest's; the ranges are laid end to end in expert order. Every
    quantity is a masked reduction over the held experts, so nothing is
    sorted or scattered."""
    blocks = rows.shape[0] // block
    flat = rows.reshape(blocks, -1)
    mine = expert.reshape(blocks, -1)[..., None] == jnp.arange(held)
    lo = jnp.min(jnp.where(mine, flat[..., None], jnp.iinfo(jnp.int32).max),
                 axis=1) // _ROWS
    hi = jnp.max(jnp.where(mine, flat[..., None], -1), axis=1) // _ROWS
    # [blocks, held]: each expert's tiles, and where they start and end in
    # the block's staging
    count = jnp.where(hi >= 0, hi - lo + 1, 0)
    end = jnp.cumsum(count, axis=1, dtype=jnp.int32)
    start = end - count
    at = jnp.arange(most)[None, :, None]
    run = (start[:, None, :] <= at) & (at < end[:, None, :])
    tiles = jnp.sum(jnp.where(run, (lo - start)[:, None, :] + at, 0), axis=2)
    shift = jnp.sum(jnp.where(mine, ((start - lo) * _ROWS)[:, None, :], 0),
                    axis=2)
    staged = jnp.where(flat >= 0, flat + shift, -1)
    return tiles.astype(jnp.int32), end[:, -1], staged.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block", "held"))
def _combine_call(ys, back, expert, weights, *, block: int, held: int):
    """The kernel call and the index arithmetic around it (the picks'
    rows, ``-1`` where absent and padded to whole blocks; each block's
    tiles and each pick's staged row). Module-level and jitted so that
    equal call sites share a trace and a lowering."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, top_k = back.shape
    d = ys.shape[1]
    blocks = -(-n // block)
    most = staged_tiles(block, top_k, held)
    _obs_registry().counter(GRID_STEPS_COUNTER).add(blocks)
    pad = ((0, blocks * block - n), (0, 0))
    expert = jnp.pad(expert.astype(jnp.int32), pad, constant_values=held)
    rows = jnp.where(expert < held, jnp.pad(back.astype(jnp.int32), pad),
                     -1)
    weights = jnp.pad(weights.astype(jnp.float32), pad)
    tiles, counts, staged = _staging(rows, expert, block, most, held)
    picks = pl.BlockSpec((block, top_k), lambda i, *_: (i, 0))

    def smem(width, ahead=0):
        # a block of a [blocks, 1, width] table: one grid step's row
        return pl.BlockSpec(
            (None, 1, width),
            lambda i, *_: (jnp.minimum(i + ahead, blocks - 1), 0, 0),
            memory_space=pltpu.SMEM)

    vmem = _vmem_bytes(block, top_k, held, d, ys.dtype.itemsize) \
        + STAGING_VMEM_SLACK
    return pl.pallas_call(
        functools.partial(_combine_kernel, top_k=top_k, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(blocks,),
            in_specs=[smem(most), smem(most, ahead=1), smem(block * top_k),
                      picks, picks, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, d), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, most * _ROWS, d), ys.dtype),
                pltpu.VMEM((most * _ROWS, d), jnp.float32),
                pltpu.VMEM((top_k, _ROWS, d), jnp.float32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        name="moe_combine",
    )(counts, tiles[:, None], tiles[:, None], staged[:, None], rows,
      weights, ys)


def moe_combine(ys, back, expert, weights, *, held: int,
                impl: str = "auto"):
    """``y [N, d]`` float32: for every token the sum over its picks ``j``
    of an expert held here (``expert[t, j] < held``) of ``weights[t, j] *
    ys[back[t, j]]``, added in pick order (the module docstring has the
    kernel). ``ys [rows, d]`` holds the held pairs' outputs as
    ``moe_dropless`` lays them: sorted by expert, token-major within one;
    ``back`` ``[N, k]`` int32 rows of ``ys`` (any value where absent);
    ``expert`` ``[N, k]`` int32 the pick's expert among the ``held``, or
    ``held`` where it is held elsewhere; ``weights`` ``[N, k]`` float32."""
    n, top_k = back.shape
    block = block_tokens(n, top_k, held, ys.shape[1], ys.dtype.itemsize)
    kernel = _takes_kernel(impl, "moe_combine", block > 0,
                           (_BLOCKS[-1], top_k, ys.shape[1]),
                           STAGING_VMEM_BUDGET)
    _obs_registry().gauge(COMBINE_GAUGE).set(int(kernel))
    if not kernel:
        return moe_combine_reference(ys, back, expert < held, weights)
    return _combine_call(ys, back, expert, weights, block=block, held=held)
