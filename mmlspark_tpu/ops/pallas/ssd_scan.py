"""The Mamba-2 recurrence (the "state-space dual" form) as one chunked
Pallas pass of matrix products.

For every row, head ``h`` (of ``heads``, ``head_dim`` channels each) in
group ``g = h // (heads / groups)``, a state ``S [head_dim, state]`` is
carried over the sequence, with ONE scalar decay a head a position::

    a_t    = exp(dt_t[h] * A[h])                        (A negative)
    S_t    = a_t * S_{t-1} + dt_t[h] * x_t[h] (x) B_t[g]        S_{-1} = 0
    y_t[h] = S_t . C_t[g] + D[h] * x_t[h]

(``B_t`` / ``C_t [state]`` shared by the heads of a group). Position by
position that is ``L`` tiny sequential steps; in chunks of ``Q`` positions
it is matrix products, which is what the kernel computes. With ``cum_i`` the
sum of ``dt * A`` over the chunk's positions up to ``i``::

    y_i  = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j     within
         + exp(cum_i) * S_prev C_i                                  carried
         + D x_i
    S    = exp(cum_Q) S_prev + sum_j exp(cum_Q - cum_j) dt_j x_j (x) B_j

* the grid is ``(row, group, block of positions)``, the block axis
  sequential; a group's state, ``[state, heads of the group x head_dim]``
  float32 in VMEM scratch, is zeroed at a row's first block and carried to
  the next. It never exists in HBM. Inside a block a loop walks chunks of
  ``CHUNK`` positions;
* **the operands are read where they lie**: the caller's causal convolution
  leaves ``[x | B | C]`` in one wide array, and the kernel is handed that
  array three times with the block offset of each part in its index map.
  (Where a part's width is no whole number of lane tiles the parts are cut
  out and padded: a small model's shapes.)
* ``C B^T`` is one product a group a chunk, shared by its heads; the decay
  matrix ``exp(cum_i - cum_j)`` (masked above the diagonal BEFORE the
  exponential: a difference that would overflow is never exponentiated) is
  one ``[Q, Q]`` float32 tile a head; the heads that share a 128-lane tile
  of channels (two at ``head_dim`` 64) take their products on the whole
  tile, each with the other's lanes zeroed, so no slice is narrower than a
  lane tile;
* ``dt * A``, its cumulative sum within chunks, the decays and the state
  are float32; the products run on operands of ``x``'s type with float32
  accumulation. The per-head scalars reach the kernel as a small ``[2 x
  heads of a group, Q]`` float32 block a chunk (``dt`` and the cumulative
  sum, positions on lanes, made by array code from ``dt [rows, L, heads]``:
  4 bytes a head a position beside 2 x ``head_dim`` of ``x`` and ``y``); the
  kernel transposes it once a chunk to get the same scalars down the
  sublanes;
* a tail that fills no whole chunk is padded with ``dt = 0`` (the state
  passes through); the block length comes from the VMEM budget alone
  (:func:`block_positions`).

**One trace a shape**: the entry is ONE module-level ``jax.jit``
(:func:`_ssd_call`), as :mod:`~mmlspark_tpu.ops.pallas.causal_conv`'s.

``impl`` resolves as :func:`~mmlspark_tpu.ops.pallas.attention.resolve_impl`
does (the kernel on the TPU, the array-code form elsewhere; a CPU test asks
for the interpreter itself). :func:`ssd_scan_reference` is the same chunked
function as array code (a ``lax.scan`` over chunks). Shapes the kernel does
not take (a block that cannot fit the budget, a ``head_dim`` that neither
divides nor is a multiple of the lane tile) give way to it under ``auto``,
logged and counted (``ops.pallas.vmem_fallback{kernel=ssd_scan}``), and
raise under ``pallas``. Counted when the kernel call is traced:
``ops.pallas.ssd_scan_grid_steps``; gauge ``ops.pallas.ssd_scan_chunk``
(positions a block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import _takes_kernel
from mmlspark_tpu.ops.pallas.budget import VMEM_BUDGET

GRID_STEPS_COUNTER = "ops.pallas.ssd_scan_grid_steps"
CHUNK_GAUGE = "ops.pallas.ssd_scan_chunk"

_LANES = 128
# positions of one masked product: the published ``chunk_size``, one lane
# tile, and the size at which the decay matrix's elementwise work (Q a
# position a head) and the products' (fixed a position) are of one order
CHUNK = 128
# positions of the longest block: past it a grid step's fixed cost is
# already under a hundredth of its work
_MAX_BLOCK = 1024


def lane_tile(head_dim: int) -> int:
    """Channels the kernel works on at a time: whole heads that fill whole
    lane tiles (two heads of 64, one of 128 or 256); ``0`` where no such
    tile exists."""
    if head_dim % _LANES == 0:
        return head_dim
    return _LANES if _LANES % head_dim == 0 else 0


def _widths(heads: int, head_dim: int, groups: int, state: int) -> tuple:
    """``(channels of a group, padded to whole tiles; state padded to whole
    lane tiles)``."""
    tile = lane_tile(head_dim)
    width = heads // groups * head_dim
    return -(-width // tile) * tile, -(-state // _LANES) * _LANES


def block_positions(length: int, heads: int, head_dim: int, groups: int,
                    state: int, itemsize: int) -> int:
    """Positions of one block, from the shapes and the VMEM budget alone:
    a position holds a group's ``x`` and ``y`` and its ``B`` and ``C``
    (``itemsize`` each) double-buffered; half the budget is left to the
    state and the chunk's float32 temporaries. Of the lengths that fit, the
    longest that divides the sequence (in whole chunks), at most
    ``_MAX_BLOCK``; ``0`` where ``head_dim`` has no lane tile or not even
    one chunk fits."""
    if not lane_tile(head_dim):
        return 0
    wide, n = _widths(heads, head_dim, groups, state)
    held = 4 * n * wide + 6 * CHUNK * max(CHUNK, wide) * 4
    per_position = 2 * itemsize * (2 * wide + 2 * n)
    whole = -(-length // CHUNK)              # the sequence in chunks
    most = min((VMEM_BUDGET - held) // per_position, _MAX_BLOCK) // CHUNK
    return CHUNK * max((c for c in range(1, min(most, whole) + 1)
                        if whole % c == 0), default=0)


def _split(xbc, heads: int, head_dim: int, groups: int, state: int):
    """``x [rows, L, groups, per, head_dim]``, ``B`` and ``C`` ``[rows, L,
    groups, state]`` of the wide ``[x | B | C]``."""
    rows, length, _ = xbc.shape
    d_i, gn = heads * head_dim, groups * state
    x = xbc[..., :d_i].reshape(rows, length, groups, heads // groups,
                               head_dim)
    b = xbc[..., d_i:d_i + gn].reshape(rows, length, groups, state)
    c = xbc[..., d_i + gn:d_i + 2 * gn].reshape(rows, length, groups, state)
    return x, b, c


def ssd_scan_reference(xbc, dt, A, D, *, heads: int, head_dim: int,
                       groups: int, state: int):
    """The chunked form as array code, float32: a ``lax.scan`` over chunks
    of ``CHUNK`` positions that carries the state ``[rows, groups, heads of
    a group, head_dim, state]``; operands as :func:`ssd_scan`."""
    f32, hi, chunk = jnp.float32, jax.lax.Precision.HIGHEST, CHUNK
    rows, length, _ = xbc.shape
    per = heads // groups
    pad = -length % chunk
    if pad:
        xbc = jnp.pad(xbc, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    x, b, c = (a.astype(f32) for a in _split(xbc, heads, head_dim, groups,
                                             state))
    dt = dt.astype(f32).reshape(rows, -1, groups, per)
    a_log = dt * A.astype(f32).reshape(groups, per)

    def chunks(a):
        return jnp.moveaxis(
            a.reshape((rows, -1, chunk) + a.shape[2:]), 1, 0)

    tri = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    skip = D.astype(f32).reshape(groups, per, 1)

    def one(s, at):
        x_c, b_c, c_c, dt_c, a_c = at          # [rows, chunk, groups, ...]
        cum = jnp.cumsum(a_c, axis=1)
        diff = cum[:, :, None] - cum[:, None, :]        # [rows, i, j, g, h]
        decay = jnp.exp(jnp.where(tri[None, :, :, None, None], diff,
                                  -jnp.inf))
        cb = jnp.einsum("bign,bjgn->bijg", c_c, b_c, precision=hi)
        dtx = dt_c[..., None] * x_c
        y = jnp.einsum("bijg,bijgh,bjghp->bighp", cb, decay, dtx,
                       precision=hi)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bign,bghpn->bighp", c_c, s, precision=hi)
        tot = cum[:, -1]                                # [rows, g, h]
        s = jnp.exp(tot)[..., None, None] * s + jnp.einsum(
            "bjghp,bjgn,bjgh->bghpn", dtx, b_c,
            jnp.exp(tot[:, None] - cum), precision=hi)
        return s, y + skip * x_c

    s0 = jnp.zeros((rows, groups, per, head_dim, state), f32)
    _, y = jax.lax.scan(one, s0, tuple(map(chunks, (x, b, c, dt, a_log))))
    y = jnp.moveaxis(y, 0, 1).reshape(rows, -1, heads * head_dim)
    return y[:, :length]


def _ssd_kernel(x_ref, b_ref, c_ref, r_ref, d_ref, o_ref, st_ref, *,
                head_dim: int, tile: int):
    # grid (row, group, block). Blocks: x / out [1, block, wide] (a group's
    # channels, whole tiles); B / C [1, block, n]; r [1, 1, chunks, 2 per,
    # CHUNK] float32 (dt of the group's heads, then the cumulative sum of
    # dt * A within the chunk; positions on lanes); D [1, 1, wide] a
    # channel. Scratch [wide / tile, n, tile] float32: the state,
    # transposed, a lane tile of channels at a time
    import jax.experimental.pallas as pl

    f32, q = jnp.float32, CHUNK
    dtype = x_ref.dtype
    chunks, per = r_ref.shape[2], r_ref.shape[3] // 2
    tiles = x_ref.shape[2] // tile
    in_tile = max(1, tile // head_dim)          # heads that share a tile

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    head_of = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) // head_dim
    fill = jnp.zeros((-2 * per % _LANES, q), f32)

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=f32)

    def chunk(s, carry):
        rows = pl.ds(pl.multiple_of(s * q, q), q)
        r = r_ref[0, 0, s]                                  # [2 per, q]
        # the same scalars down the sublanes: column k dt of head k, column
        # per + k its cumulative sum
        cols = jnp.concatenate([r, fill], axis=0).T
        cc = c_ref[0, rows, :]
        bt = b_ref[0, rows, :].astype(f32).T.astype(dtype)  # [n, q]
        cb = dot(cc, bt)                                    # [q, q]
        for j in range(tiles):
            lanes = slice(j * tile, (j + 1) * tile)
            ks = [k for k in range(j * in_tile, (j + 1) * in_tile)
                  if k < per]

            def a_lane(first):
                """``[q, tile]``: on each head's lanes its own column."""
                out = jnp.broadcast_to(cols[:, first + ks[0]:
                                            first + ks[0] + 1], (q, tile))
                for k in ks[1:]:
                    out = jnp.where(head_of >= k - ks[0],
                                    cols[:, first + k:first + k + 1], out)
                return out

            x = x_ref[0, rows, lanes].astype(f32)
            dt_l, cum_l = a_lane(0), a_lane(per)
            dtx = x * dt_l
            st = st_ref[j]
            y = d_ref[0, :, lanes] * x \
                + jnp.exp(cum_l) * dot(cc, st.astype(dtype))
            for k in ks:
                diff = cols[:, per + k:per + k + 1] - r[per + k:per + k + 1]
                m = (cb * jnp.exp(jnp.where(tri, diff, -jnp.inf))
                     ).astype(dtype)
                part = dtx if len(ks) == 1 else jnp.where(
                    head_of == k - ks[0], dtx, 0.0)
                y = y + dot(m, part.astype(dtype))
            tot = cum_l[q - 1:q, :]                         # [1, tile]
            st_ref[j] = jnp.exp(tot) * st + dot(
                bt, (dtx * jnp.exp(tot - cum_l)).astype(dtype))
            o_ref[0, rows, lanes] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=(
    "heads", "head_dim", "groups", "state", "block"))
def _ssd_call(xbc, dt, A, D, *, heads: int, head_dim: int, groups: int,
              state: int, block: int):
    """The kernel call and the array code around it. Module-level and
    jitted so that equal call sites share a trace and a lowering."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, length, _ = xbc.shape
    per, d_i = heads // groups, heads * head_dim
    tile = lane_tile(head_dim)
    wide, n = _widths(heads, head_dim, groups, state)
    pad_l = -length % block
    blocks = (length + pad_l) // block
    _obs_registry().counter(GRID_STEPS_COUNTER).add(rows * groups * blocks)
    _obs_registry().gauge(CHUNK_GAUGE).set(block)

    # the per-head scalars, a chunk at a time: [rows, groups, chunks of the
    # row, 2 per, CHUNK], dt first, then cumsum(dt * A) within the chunk
    def tail(a):
        """``a`` with the positions that fill the last block, as zeros."""
        return jnp.pad(a, ((0, 0), (0, pad_l)) + ((0, 0),) * (a.ndim - 2)
                       ) if pad_l else a

    dt = tail(dt.astype(f32)).reshape(rows, -1, CHUNK, groups, per)
    cum = jnp.cumsum(dt * A.astype(f32).reshape(groups, per), axis=2)
    scalars = jnp.concatenate([dt, cum], axis=-1).transpose(0, 3, 1, 4, 2)
    skip = jnp.repeat(D.astype(f32), head_dim).reshape(groups, 1, -1)

    in_place = (wide == per * head_dim and n == state and d_i % n == 0)
    if in_place:
        # [x | B | C] read where they lie, by block offset
        xbc = tail(xbc)
        parts, offsets = (xbc, xbc, xbc), (0, d_i // n, d_i // n + groups)
    else:
        def padded(a, to):
            a = a.reshape(a.shape[:3] + (-1,))
            a = jnp.pad(tail(a), ((0, 0), (0, 0), (0, 0),
                                  (0, to - a.shape[3])))
            return a.reshape(rows, length + pad_l, groups * to)

        x, b, c = _split(xbc, heads, head_dim, groups, state)
        parts, offsets = ((padded(x, wide), padded(b, n), padded(c, n)),
                          (0, 0, 0))
        skip = jnp.pad(skip, ((0, 0), (0, 0), (0, wide - per * head_dim)))

    def moved(width, offset):
        return pl.BlockSpec((1, block, width),
                            lambda r, g, i: (r, i, offset + g))

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, head_dim=head_dim, tile=tile),
        grid=(rows, groups, blocks),
        in_specs=[
            moved(wide, offsets[0]), moved(n, offsets[1]),
            moved(n, offsets[2]),
            pl.BlockSpec((1, 1, block // CHUNK, 2 * per, CHUNK),
                         lambda r, g, i: (r, g, i, 0, 0)),
            pl.BlockSpec((1, 1, wide), lambda r, g, i: (g, 0, 0)),
        ],
        out_specs=moved(wide, 0),
        out_shape=jax.ShapeDtypeStruct(
            (rows, length + pad_l, groups * wide), xbc.dtype),
        scratch_shapes=[pltpu.VMEM((wide // tile, n, tile), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="ssd_scan",
    )(*parts, scalars, skip)
    if not in_place:
        out = out.reshape(rows, -1, groups, wide)[..., :per * head_dim]
        out = out.reshape(rows, -1, d_i)
    return out[:, :length] if pad_l else out


def ssd_scan(xbc, dt, A, D, *, heads: int, head_dim: int, groups: int,
             state: int, impl: str = "auto"):
    """The Mamba-2 recurrence with the skip fused in.

    ``xbc`` ``[rows, L, heads * head_dim + 2 * groups * state]``: the
    convolved ``[x | B | C]`` as they lie; ``dt`` ``[rows, L, heads]`` (the
    step sizes after softplus); ``A [heads]`` (negative); ``D [heads]``.
    Returns ``y [rows, L, heads * head_dim]`` in ``xbc``'s type; the
    decays, the state and the accumulation are float32 whatever the
    operands'."""
    if heads % groups:
        raise ValueError(f"{heads} heads in {groups} groups: a group is a "
                         "whole number of heads")
    block = block_positions(xbc.shape[1], heads, head_dim, groups, state,
                            xbc.dtype.itemsize)
    if _takes_kernel(impl, "ssd_scan", block > 0,
                     (CHUNK, heads // groups * head_dim, state)):
        return _ssd_call(xbc, dt, A, D, heads=heads, head_dim=head_dim,
                         groups=groups, state=state, block=block)
    return ssd_scan_reference(
        xbc, dt, A, D, heads=heads, head_dim=head_dim, groups=groups,
        state=state).astype(xbc.dtype)
