"""The selective state-space recurrence (Mamba-1) as one chunked Pallas pass.

For every row and channel ``c`` a state ``s [N]`` is carried over the
sequence::

    s_t = exp(delta_t[c] * A[c]) * s_{t-1} + (delta_t[c] * u_t[c]) * B_t
    y_t[c] = s_t . C_t + D[c] * u_t[c]          s_{-1} = 0
    out_t[c] = y_t[c] * silu(z_t[c])

(``A [d_i, N]`` negative, ``B_t`` / ``C_t [N]`` shared by a row's channels,
the skip ``D`` and the gate ``z`` fused in). Written as array code the two
products are ``[L, d_i, N]`` float32 tensors in HBM, or ``L`` tiny
sequential steps from HBM; the kernel keeps the state in VMEM instead:

* the grid is ``(row, channel block, sequence chunk)``, the chunk axis
  sequential; the state of a channel block, ``[N, 8, 128]`` float32 in
  VMEM scratch, is zeroed at a row's first chunk and carried to the next.
  Nothing of shape ``[L, d_i, N]`` ever exists;
* a channel block is ``8 x 128`` channels, ONE float32 register a state
  index: inside a chunk the positions are a loop whose carry is the ``N``
  state registers, every operation of it elementwise on whole registers.
  ``B_t[n]`` and ``C_t[n]`` are scalars read from SMEM, so nothing is
  broadcast across lanes or reduced across sublanes;
* the operands arrive as ``[chunk, 1024]`` blocks (positions on sublanes,
  as the projections that make them leave them: no copy in HBM). Each
  chunk is restaged once in VMEM, a 128-lane column at a time, so that a
  position's 1,024 channels are eight rows ``chunk`` apart, which one
  strided load brings into a register (and one strided store takes
  ``y_t`` back); ``delta * u`` is formed, and the skip and the gate are
  applied, on whole chunks around the loop;
* the chunk length comes from the VMEM budget alone
  (:func:`chunk_positions`); a tail that does not fill a chunk is padded
  with ``delta = 0``: then ``exp(0) = 1`` and ``delta * u * B = 0``, the
  state passes through unchanged, and no mask pass exists. Channels are
  padded to whole blocks the same way.

``impl`` resolves as :func:`~mmlspark_tpu.ops.pallas.attention.resolve_impl`
does (the kernel on the TPU, the reference elsewhere; a CPU test asks for
the interpreter itself). The XLA reference is a ``lax.scan`` over
positions. A chunk that cannot fit the budget gives way to it under
``auto``, logged and counted (``ops.pallas.vmem_fallback{kernel=
selective_scan}``), and raises under ``pallas``. Counted when a kernel
call is traced: ``ops.pallas.selective_scan_grid_steps``; gauge
``ops.pallas.selective_scan_chunk`` (positions a chunk).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import _takes_kernel
from mmlspark_tpu.ops.pallas.budget import VMEM_BUDGET

GRID_STEPS_COUNTER = "ops.pallas.selective_scan_grid_steps"
CHUNK_GAUGE = "ops.pallas.selective_scan_chunk"

_SUBLANES, _LANES = 8, 128
# channels of one block: one float32 register a state index
BLOCK_CHANNELS = _SUBLANES * _LANES
# a chunk's positions are a whole number of lane rows (the SMEM blocks of
# B and C hold positions on their last axis)
_CHUNK_STEP = 128
# positions the loop body is unrolled over
_UNROLL = 8


def chunk_positions(length: int, itemsize: int) -> int:
    """Positions of one chunk, from the sequence length, the operand's
    item size and the VMEM budget alone: per position a channel block
    holds ``u``, ``z`` and the output (``itemsize`` each) and ``delta``
    (float32) double-buffered, and three float32 staging rows; of the
    lengths that fit, the longest that divides the sequence (rounded up to
    whole lane rows); ``0`` where not even the smallest chunk fits."""
    per_position = BLOCK_CHANNELS * (2 * (3 * itemsize + 4) + 3 * 4)
    whole = -(-length // _CHUNK_STEP)        # the sequence in lane rows
    most = VMEM_BUDGET // per_position // _CHUNK_STEP
    # the longest that tiles the sequence: a padded tail is a copy in HBM
    return _CHUNK_STEP * max(
        (n for n in range(1, min(most, whole) + 1) if whole % n == 0),
        default=0)


def selective_scan_reference(u, delta, A, B, C, D, z):
    """The recurrence as a ``lax.scan`` over positions, float32; operands
    as :func:`selective_scan`."""
    f32 = jnp.float32
    u, delta, z = u.astype(f32), delta.astype(f32), z.astype(f32)
    A, B, C, D = A.astype(f32), B.astype(f32), C.astype(f32), D.astype(f32)

    def row(u_r, delta_r, b_r, c_r):
        def step(s, at):
            u_t, d_t, b_t, c_t = at
            s = jnp.exp(d_t[:, None] * A) * s \
                + (d_t * u_t)[:, None] * b_t[None, :]
            return s, jnp.sum(s * c_t[None, :], axis=-1)
        _, y = jax.lax.scan(step, jnp.zeros(A.shape, f32),
                            (u_r, delta_r, b_r, c_r))
        return y

    y = jax.vmap(row)(u, delta, B, C)
    return (y + D * u) * jax.nn.silu(z)


def _scan_kernel(b_ref, c_ref, u_ref, delta_ref, z_ref, a_ref, d_ref, o_ref,
                 state_ref, dl_ref, du_ref, y_ref, *, chunk: int, states: int):
    # grid (row, channel block, chunk). Blocks: u / delta / z / out
    # [1, chunk, 1024]; A [N, 8, 128] and D [8, 128] of this channel block
    # (channel 128 g + l of the block at [g, l]); B and C [1, N, chunk] in
    # SMEM. Staging: dl / du / y [8 * chunk, 128], row g * chunk + t the
    # 128-lane column g of position t
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state_ref[...] = jnp.zeros(state_ref.shape, jnp.float32)

    for g in range(_SUBLANES):
        rows, lanes = pl.ds(g * chunk, chunk), pl.ds(g * _LANES, _LANES)
        d = delta_ref[0, :, lanes]
        dl_ref[rows, :] = d
        du_ref[rows, :] = d * u_ref[0, :, lanes].astype(jnp.float32)

    def position(t, state):
        at = pl.ds(t, _SUBLANES, stride=chunk)
        d, du = dl_ref[at, :], du_ref[at, :]
        y, out = None, []
        for n in range(states):
            s = jnp.exp(d * a_ref[n]) * state[n] + du * b_ref[0, n, t]
            out.append(s)
            y = s * c_ref[0, n, t] if y is None else y + s * c_ref[0, n, t]
        y_ref[at, :] = y
        return tuple(out)

    def positions(i, state):
        for j in range(_UNROLL):
            state = position(i * _UNROLL + j, state)
        return state

    state = jax.lax.fori_loop(
        0, chunk // _UNROLL, positions,
        tuple(state_ref[n] for n in range(states)))
    for n in range(states):
        state_ref[n] = state[n]

    for g in range(_SUBLANES):
        rows, lanes = pl.ds(g * chunk, chunk), pl.ds(g * _LANES, _LANES)
        y = y_ref[rows, :] + d_ref[g:g + 1, :] \
            * u_ref[0, :, lanes].astype(jnp.float32)
        o_ref[0, :, lanes] = (y * jax.nn.silu(
            z_ref[0, :, lanes].astype(jnp.float32))).astype(o_ref.dtype)


def _scan_call(u, delta, A, B, C, D, z, chunk: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, length, channels = u.shape
    states = A.shape[1]
    pad_l, pad_c = -length % chunk, -channels % BLOCK_CHANNELS
    if pad_l or pad_c:
        # delta = 0 there: the state passes through, y = 0
        wide = ((0, 0), (0, pad_l), (0, pad_c))
        u, delta, z = (jnp.pad(a, wide) for a in (u, delta, z))
        B, C = (jnp.pad(a, ((0, 0), (0, pad_l), (0, 0))) for a in (B, C))
        A = jnp.pad(A, ((0, pad_c), (0, 0)))
        D = jnp.pad(D, ((0, pad_c),))
    blocks, chunks = u.shape[2] // BLOCK_CHANNELS, u.shape[1] // chunk
    _obs_registry().counter(GRID_STEPS_COUNTER).add(rows * blocks * chunks)
    _obs_registry().gauge(CHUNK_GAUGE).set(chunk)

    def wide_block(r, j, i):
        return (r, i, j)

    def scalars(r, j, i):
        return (r, 0, i)

    wide = pl.BlockSpec((1, chunk, BLOCK_CHANNELS), wide_block)
    staged = pltpu.VMEM((_SUBLANES * chunk, _LANES), jnp.float32)
    out = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, states=states),
        grid=(rows, blocks, chunks),
        in_specs=[
            pl.BlockSpec((1, states, chunk), scalars,
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, states, chunk), scalars,
                         memory_space=pltpu.SMEM),
            wide, wide, wide,
            pl.BlockSpec((states, _SUBLANES, _LANES),
                         lambda r, j, i: (0, j, 0)),
            pl.BlockSpec((_SUBLANES, _LANES), lambda r, j, i: (j, 0)),
        ],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(u.shape, u.dtype),
        scratch_shapes=[
            pltpu.VMEM((states, _SUBLANES, _LANES), jnp.float32),
            staged, staged, staged],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="selective_scan",
    )(B.astype(jnp.float32).transpose(0, 2, 1),
      C.astype(jnp.float32).transpose(0, 2, 1),
      u, delta.astype(jnp.float32), z,
      A.astype(jnp.float32).T.reshape(states, -1, _LANES),
      D.astype(jnp.float32).reshape(-1, _LANES))
    return out[:, :length, :channels] if pad_l or pad_c else out


def selective_scan(u, delta, A, B, C, D, z, impl: str = "auto"):
    """The selective scan with the skip and the gate fused in.

    ``u``, ``delta``, ``z``: ``[rows, L, d_i]`` (``u`` the convolved input,
    ``delta`` the step sizes after softplus, ``z`` the gate before its
    SiLU); ``A [d_i, N]`` (negative); ``B``, ``C``: ``[rows, L, N]``;
    ``D [d_i]``. Returns ``[rows, L, d_i]`` in ``u``'s type; the
    recurrence, the state and ``y`` are float32 whatever the operands'."""
    if z.dtype != u.dtype:
        raise ValueError(f"u is {u.dtype} and z {z.dtype}: the kernel "
                         "moves both in one type")
    chunk = chunk_positions(u.shape[1], u.dtype.itemsize)
    if _takes_kernel(impl, "selective_scan", chunk > 0,
                     (_CHUNK_STEP, BLOCK_CHANNELS)):
        return _scan_call(u, delta, A, B, C, D, z, chunk)
    return selective_scan_reference(u, delta, A, B, C, D, z).astype(u.dtype)
