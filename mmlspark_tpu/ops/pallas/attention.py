"""Fused flash-style attention (online-softmax tiling) as one Pallas pass.

The serving-path attention of :mod:`mmlspark_tpu.models.vit`, the local
block of :mod:`mmlspark_tpu.parallel.ring_attention`, and the q_len=1
KV-cache decode step of :mod:`mmlspark_tpu.serve.generate`. Under plain
XLA, attention materializes the ``[B, H, Tq, Tk]`` score matrix in HBM
three times over (scores → masked scores → softmax weights) before the
weighted sum; the kernel keeps one (batch, head) tile's Q/K/V blocks in
VMEM and accumulates the softmax online (running max + denominator, Dao
et al.'s FlashAttention recurrence — the same recurrence
``ring_attention`` already runs across ring hops, here applied across
K blocks inside one chip), so the score matrix never touches HBM.

Kernel discipline:

* ONE shared body — :func:`_online_update` (a single K/V block's
  online-softmax update over 2-D ``[T, D]`` tiles) and
  :func:`_flash_tile` (the block loop) are written over the ``xp``
  namespace, so the SAME code is the Pallas kernel body, the XLA
  reference (``vmap`` over batch × heads), and the numpy oracle —
  implementations cannot drift apart op by op;
* the kernels are compiled by Mosaic, never interpreted by choice of the
  wrapper: off-TPU the caller asks for the interpreter explicitly
  (``jax.experimental.pallas.tpu.force_tpu_interpret_mode()``, what
  tier-1 does), and without it a non-TPU backend refuses loudly;
* ``impl: auto | xla | pallas`` selects the backend (auto = kernel on
  TPU, reference elsewhere). ``flash_attention`` whose whole (batch,
  head) tile is past the VMEM budget takes the tiled kernel (queries
  tiled too, operands in the type they came in: below);
  ``attention_block_update`` past the budget under ``auto`` takes the
  reference and says so (a warning and the ``ops.pallas.vmem_fallback``
  counter), under ``pallas`` it raises.

The tiled kernel pays a (query tile, key block) pair only what its place
needs, decided when the call is traced from ``causal``, whether any key
can be masked (a ``kv_mask``, or ``Tk`` not a whole number of blocks) and
the pair's indices; there is nothing to choose:

* a grid step is a query tile against a **resident stretch** of keys and
  values (the whole window where it fits half the VMEM budget, equal
  stretches otherwise) and the key blocks are a loop inside it, the
  softmax's carry a value of that loop. A carry read from ``[bq, 1]``
  scratch every block is one lane of every register and is re-spread
  every block (on a v5e 0.7 us of a 1.9 us update), so scratch holds it
  only between the steps of a query tile with several stretches, and
  there lane-dense;
* an **interior** pair's mask is all true (no key row, and under
  ``causal`` the key block wholly below the query tile's first row): the
  shared body with ``keep=None``: no mask built, no select, no guard;
* a **diagonal** pair (``causal``, no key row, equal tiles, on the
  diagonal) builds ``col <= row`` from the tile's own iotas, the same
  triangle for every such pair;
* a **general** pair (a key row, or unequal tiles straddling the
  diagonal) runs the masked, guarded update: key row, offset triangle;
* under ``causal`` a pair wholly above the diagonal is never run, and a
  stretch with none to run is not a grid step: the grid is ``(batch,
  head, step)`` over prefetched tables (:func:`_tile_plan`) of each
  step's query tile, stretch, and how many blocks it runs with no mask
  and then under one. The pairs by kind and the steps of one (batch,
  head) are counted at trace time (``ops.pallas.attention_tiles{kind=}``,
  ``ops.pallas.attention_grid_steps``).

**Grouped key/value heads.** ``flash_attention`` takes ``k`` and ``v`` with
fewer heads than ``q`` (a divisor): query head ``h`` reads K/V head ``h //
(H / H_kv)`` through both kernels' block index maps (:func:`_kv_head`), so
no copy of K or V with the queries' head count is ever written, and the
consecutive grid steps of one group name the same block, which is not
fetched again. The group's size is a gauge set when a kernel call is traced
(``ops.pallas.attention_kv_group``); the XLA reference repeats K and V.

What reaches the kernel is shaped for the compiler: ``Tq`` is padded to
the f32 sublane tile and ``Tk`` to a whole number of ``block_k`` stripes
(padded keys are masked, padded query rows sliced away), the contraction
``Q·Kᵀ`` is a transposed-rhs ``dot_general`` (no in-kernel transpose),
and the mask is not an ``[B, Tq, Tk]`` tensor but the ``[B, 1, Tk]``
key-validity row plus the causal triangle rebuilt from iotas per stripe.

Masking semantics match ``parallel/ring_attention``: ``kv_mask`` is a
``[B, Tk]`` key-validity mask (True = real key), ``causal`` adds the
lower-triangular constraint, and fully-masked query rows yield EXACT
zeros (the guarded accumulator), not NaN.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.budget import (
    VMEM_BUDGET, lane_pad, note_vmem_fallback,
)

IMPLS = ("auto", "xla", "pallas")

# K-block width of the online-softmax loop: one MXU-lane-aligned stripe
# of the score tile per update
DEFAULT_BLOCK_K = 128

# the denominator guard for fully-masked query rows (exactly the
# ring/ulysses value, so the paths agree bit-for-bit on masked rows)
_DENOM_FLOOR = np.float32(1e-30)

# the f32 sublane tile: kernel query rows are padded to a multiple of it
# (a Tq=1 decode step becomes one aligned 8-row MXU pass)
_SUBLANES = 8
_LANES = 128


def _qk_t(q, ks, xp):
    """``q [Tq, D] · ksᵀ [D, Tk] → [Tq, Tk]`` contracting the last dim of
    both operands — the transposed-rhs matmul form, so the kernel never
    materializes ``ksᵀ`` (Mosaic lowers it to one ``tpu.matmul``)."""
    if xp is np:
        return np.dot(q, ks.T)
    return jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _p_v(p, vs, xp):
    """``p [Tq, Tk] · vs [Tk, D] → [Tq, D]`` float32. The weights take the
    values' type for the product (a no-op for the float32 tiles every
    whole-tile caller hands over; bf16 values keep the MXU on bf16
    operands with float32 accumulation)."""
    if xp is np:
        return np.dot(p, vs)
    return jax.lax.dot_general(p.astype(vs.dtype), vs,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _online_update(q, ks, vs, keep, m, denom, acc, scale, xp):
    """THE shared body: one K/V block's flash-attention update for one
    (batch, head) tile.

    ``q`` ``[Tq, D]`` f32, ``ks``/``vs`` ``[Tk, D]`` f32, ``keep``
    ``[Tq, Tk]`` bool, carry ``m``/``denom`` ``[Tq, 1]`` f32 and ``acc``
    ``[Tq, D]`` f32. Returns the updated ``(m, denom, acc)``. Also the
    per-hop local-block update of ``ring_attention`` (each ring step IS
    one such update with the resident K/V block).

    ``keep=None`` is the caller's word that every key of the block is
    kept (the tiled kernel's interior tiles): no select, and no guard,
    since a row that keeps a key has a finite maximum. For finite scores
    that is the masked arithmetic with its no-ops left out, equal to
    the bit."""
    scores = _qk_t(q, ks, xp) * scale
    if keep is not None:
        scores = xp.where(keep, scores, -xp.inf)
    blk_max = xp.max(scores, axis=-1, keepdims=True)
    m_new = xp.maximum(m, blk_max)
    if keep is None:
        corr = xp.exp(m - m_new)
        p = xp.exp(scores - m_new)
    else:
        # guard -inf - -inf (rows with every key masked so far)
        corr = xp.where(xp.isfinite(m), xp.exp(m - m_new), np.float32(0))
        p = xp.exp(xp.where(xp.isfinite(scores), scores - m_new, -xp.inf))
    acc = acc * corr + _p_v(p, vs, xp)
    denom = denom * corr + xp.sum(p, axis=-1, keepdims=True)
    return m_new, denom, acc


def _flash_tile(q, stripe, tk: int, scale, xp, block_k: int,
                dv: int | None = None):
    """Full attention for one (batch, head) tile via the online-softmax
    block loop: ``q`` ``[Tq, D]`` f32 against ``tk`` keys → ``[Tq, D]``
    f32. ``stripe(start, stop)`` yields one K stripe as ``(ks, vs,
    keep)`` — ``[w, D]`` f32 keys and values and the ``[Tq, w]`` bool
    mask: slices of the operands and of the materialized mask in the
    reference and the oracle; in the kernel, loads of exactly that stripe
    from the refs (the mask rebuilt from the key row and iotas), so a
    stripe never exists as a slice of a larger in-register value. The
    block loop is a static python loop (``tk``/``block_k`` are trace-time
    constants), so the SAME code unrolls identically in the kernel, the
    XLA reference, and the numpy oracle."""
    tq, d = q.shape
    m = xp.full((tq, 1), -xp.inf, np.float32)
    denom = xp.zeros((tq, 1), np.float32)
    acc = xp.zeros((tq, d if dv is None else dv), np.float32)
    for start in range(0, tk, block_k):
        ks, vs, keep = stripe(start, min(start + block_k, tk))
        m, denom, acc = _online_update(q, ks, vs, keep, m, denom, acc,
                                       scale, xp)
    return acc / xp.maximum(denom, _DENOM_FLOOR)


def _sliced(k, v, keep):
    """The reference's and the oracle's stripe source: plain slices."""
    return lambda a, b: (k[a:b], v[a:b], keep[:, a:b])


def _mask3(b: int, tq: int, tk: int, kv_mask, causal: bool):
    """The ``[B, Tq, Tk]`` int8 mask the reference and the oracle consume
    (True→1 = attend). Built with jnp (traced); callers on the host
    oracle path convert with numpy themselves via :func:`host_mask3`."""
    if kv_mask is None:
        keep = jnp.ones((b, tq, tk), bool)
    else:
        keep = jnp.broadcast_to(jnp.asarray(kv_mask, bool)[:, None, :],
                                (b, tq, tk))
    if causal:
        keep = keep & jnp.tril(jnp.ones((tq, tk), bool))[None]
    return keep.astype(jnp.int8)


def host_mask3(b: int, tq: int, tk: int, kv_mask, causal: bool
               ) -> np.ndarray:
    """Numpy twin of :func:`_mask3` for the oracle path."""
    if kv_mask is None:
        keep = np.ones((b, tq, tk), bool)
    else:
        keep = np.broadcast_to(np.asarray(kv_mask, bool)[:, None, :],
                               (b, tq, tk)).copy()
    if causal:
        keep = keep & np.tril(np.ones((tq, tk), bool))[None]
    return keep.astype(np.int8)


def _resolve_scale(scale, d: int) -> np.float32:
    """The f32 softmax scale — np.float32 so all implementations
    multiply by the bit-identical constant."""
    return np.float32(1.0 / np.sqrt(d) if scale is None else scale)


def flash_attention_reference(q, k, v, mask3, scale,
                              block_k: int = DEFAULT_BLOCK_K):
    """Pure-XLA anchor: the SAME ``_flash_tile`` body vmapped over
    (batch, heads). ``q``/``k``/``v`` ``[B, H, T, D]`` (any float
    dtype — upcast to f32 like the ring path), ``mask3`` ``[B, Tq, Tk]``
    int8. Returns ``[B, H, Tq, D]`` float32."""
    s = np.float32(scale)

    def tile(q2, k2, v2, keep2):
        return _flash_tile(q2.astype(jnp.float32),
                           _sliced(k2.astype(jnp.float32),
                                   v2.astype(jnp.float32), keep2 != 0),
                           k2.shape[0], s, jnp, block_k, v2.shape[1])

    over_h = jax.vmap(tile, in_axes=(0, 0, 0, None))
    return jax.vmap(over_h)(q, k, v, mask3)


def flash_attention_host(q, k, v, mask3, scale,
                         block_k: int = DEFAULT_BLOCK_K) -> np.ndarray:
    """Numpy oracle: the identical tile body, python-looped over
    (batch, heads)."""
    q = np.asarray(q, np.float32)
    k = np.asarray(k, np.float32)
    v = np.asarray(v, np.float32)
    mask3 = np.asarray(mask3)
    s = np.float32(scale)
    b, h, tq, d = q.shape
    out = np.empty((b, h, tq, d), np.float32)
    for bi in range(b):
        keep = mask3[bi] != 0
        for hi in range(h):
            out[bi, hi] = _flash_tile(
                q[bi, hi], _sliced(k[bi, hi], v[bi, hi], keep),
                k.shape[2], s, np, block_k)
    return out


# ---- the Pallas kernels ----

KV_GROUP_GAUGE = "ops.pallas.attention_kv_group"


def _kv_head(head, group: int):
    """The K/V head that query head ``head`` reads: grouped-query attention
    is a block index map, never a repeated copy of K or V. Consecutive grid
    steps of one group name the same block, which is then not fetched
    again."""
    return head if group == 1 else head // group


def _flash_kernel(q_ref, k_ref, v_ref, kv_ref, o_ref, *,
                  scale: np.float32, block_k: int, causal: bool):
    # one (batch, head) tile per program: refs arrive [1, 1, T, D] and
    # the key-validity row [1, 1, Tk] int32; the shared body works on
    # 2-D tiles, each K stripe loaded from the refs as it is needed
    q = q_ref[0, 0].astype(jnp.float32)
    tq = q.shape[0]

    def stripe(start, stop):
        width = stop - start
        keep = jnp.broadcast_to(kv_ref[0, :, start:stop],
                                (tq, width)) != 0
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (tq, width), 1)
            keep = keep & (col + start <= row)
        return (k_ref[0, 0, start:stop, :].astype(jnp.float32),
                v_ref[0, 0, start:stop, :].astype(jnp.float32), keep)

    o_ref[0, 0] = _flash_tile(q, stripe, k_ref.shape[2], scale, jnp,
                              block_k)


def _padded_operands(q, k, v, kv_mask, q_multiple: int, k_multiple: int):
    """Shape the operands for the compiler: ``Tq`` padded to a multiple of
    ``q_multiple``, ``Tk`` to one of ``k_multiple``, and the ``[B, Tk]``
    int32 key-validity row. Padded keys are masked out of every
    denominator; padded query rows are for the caller to slice away."""
    b, tq, tk = q.shape[0], q.shape[2], k.shape[2]
    pad_q = -tq % q_multiple
    pad_k = -tk % k_multiple
    kv_row = (jnp.ones((b, tk), jnp.int32) if kv_mask is None
              else jnp.asarray(kv_mask, bool).astype(jnp.int32))
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        kv_row = jnp.pad(kv_row, ((0, 0), (0, pad_k)))
    return q, k, v, kv_row


def _flash_call(q, k, v, kv_mask, causal: bool, scale, block_k: int):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    group = h // k.shape[1]
    # Tq to whole f32 sublane tiles, Tk to whole block_k stripes
    q, k, v, kv_row = _padded_operands(q, k, v, kv_mask, _SUBLANES, block_k)
    tq_p, tk_p = q.shape[2], k.shape[2]

    def tile(i, j):
        return (i, j, 0, 0)

    def kv_tile(i, j):
        return (i, _kv_head(j, group), 0, 0)

    kern = functools.partial(_flash_kernel, scale=np.float32(scale),
                             block_k=block_k, causal=causal)
    out = pl.pallas_call(
        kern,
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, tq_p, d), tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tk_p, d), kv_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tk_p, d), kv_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tk_p), lambda i, j: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, tq_p, d), tile,
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((b, h, tq_p, d), jnp.float32),
        name="flash_attention",
    )(q, k, v, kv_row.reshape(b, 1, tk_p))
    return out[:, :, :tq] if tq_p != tq else out


# ---- the tiled kernel: sequences whose (batch, head) tile outgrows VMEM ----

# query tile and key block of the tiled kernel: one online update per pair
TILE_Q = 512
TILE_K = 512

# what a (query tile, key block) pair's place asks of its update: an
# interior pair's mask is all true (none is built), a diagonal pair's is the
# one triangle every diagonal tile shares (the tile's own iotas), a general
# pair honours a key row and offsets
TILE_KINDS = ("interior", "diagonal", "general")
TILES_COUNTER = "ops.pallas.attention_tiles"
GRID_STEPS_COUNTER = "ops.pallas.attention_grid_steps"


def _resident_blocks(blocks: int, bk: int, d: int, dv: int,
                     itemsize: int) -> int:
    """Key blocks of one resident stretch: the keys and values of a grid
    step stay in VMEM and the blocks are a loop inside it, so a step costs
    its fixed price once a stretch, not once a block. Half the budget goes
    to the two double-buffered stretches (the score block's float32
    temporaries and the query and output tiles have the rest); a window
    past that is cut into equal stretches."""
    most = max(1, (VMEM_BUDGET // 2)
               // (2 * bk * (lane_pad(d) + lane_pad(dv)) * itemsize))
    return -(-blocks // -(-blocks // most))


def _tile_plan(nq: int, blocks: int, sub: int, bq: int, bk: int,
               causal: bool, key_row: bool) -> np.ndarray:
    """The grid steps of one (batch, head): int32 rows ``(query tile,
    stretch, mask-free blocks, masked blocks, last step of its query
    tile)``, one column a step, a query tile's steps consecutive. The
    ``blocks`` key blocks lie in stretches of ``sub`` (the last may be
    short). A step runs its stretch's leading ``mask-free`` blocks with no
    mask, then ``masked`` more under one; the blocks after those lie wholly
    above the causal diagonal and are not run, and a stretch with none to
    run is not a step. ``key_row``: some key may be masked (a ``kv_mask``,
    or padded keys), so no block is mask-free."""
    steps = []
    for i in range(nq):
        mine = []
        for j in range(-(-blocks // sub)):
            free = masked = 0
            for block in range(j * sub, min((j + 1) * sub, blocks)):
                if causal and block * bk > i * bq + bq - 1:
                    break
                if key_row or (causal and block * bk + bk - 1 > i * bq):
                    masked += 1
                else:
                    free += 1
            if free + masked:
                mine.append([i, j, free, masked, 0])
        mine[-1][-1] = 1
        steps += mine
    return np.asarray(steps, np.int32).T


def _tiled_kernel(qi_ref, kj_ref, free_ref, masked_ref, last_ref, q_ref,
                  k_ref, v_ref, *rest, scale: np.float32, causal: bool,
                  bq: int, bk: int, key_row: bool, masked_kind: str,
                  one_masked: bool):
    # grid (batch, head, step): a step is one query tile against one
    # resident stretch of keys, its place and block counts prefetched
    # (_tile_plan). The carry of the online softmax is a value across a
    # step's blocks. Operands stay in the type they came in (bf16 on the
    # MXU, float32 accumulation); the softmax is float32. ``rest``: the key
    # row's ref where the call has one, the output, and the carry's scratch
    # where a query tile has several steps
    import jax.experimental.pallas as pl

    kv_ref, (o_ref, *scratch) = (rest[0], rest[1:]) if key_row \
        else (None, rest)
    step = pl.program_id(2)
    qi, kj, free = qi_ref[step], kj_ref[step], free_ref[step]
    stretch = k_ref.shape[2]

    if scratch:
        # the row statistics cross grid steps lane-dense and come back
        # through a lane reduction: loaded from a [bq, 1] ref they are one
        # lane of every register, the loops below inherit that layout and
        # re-spread them every block (0.7 us of a 1.9 us update on a v5e)
        m_ref, d_ref, a_ref = scratch

        @pl.when(kj == 0)
        def _start():
            m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
            d_ref[...] = jnp.zeros(d_ref.shape, jnp.float32)
            a_ref[...] = jnp.zeros(a_ref.shape, jnp.float32)

        carry = (jnp.max(m_ref[...], axis=-1, keepdims=True),
                 jnp.max(d_ref[...], axis=-1, keepdims=True), a_ref[...])
    else:
        carry = (jnp.full((bq, 1), -jnp.inf, jnp.float32),
                 jnp.zeros((bq, 1), jnp.float32),
                 jnp.zeros((bq, v_ref.shape[3]), jnp.float32))

    def below(row_start, col_start):
        row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        return col + col_start <= row + row_start

    def mask(cols):
        if masked_kind == "diagonal":
            return below(0, 0)
        keep = None
        if key_row:
            keep = jnp.broadcast_to(kv_ref[0, :, cols], (bq, bk)) != 0
        if causal:
            tri = below(qi * bq, kj * stretch + cols.start)
            keep = tri if keep is None else keep & tri
        return keep

    def block(mask_of):
        def body(c, carry):
            cols = pl.ds(pl.multiple_of(c * bk, bk), bk)
            return _online_update(q_ref[0, 0], k_ref[0, 0, cols, :],
                                  v_ref[0, 0, cols, :], mask_of(cols),
                                  *carry, scale, jnp)
        return body

    carry = jax.lax.fori_loop(0, free, block(lambda cols: None), carry)
    if one_masked:
        # every step has exactly one (a diagonal in each query tile's one
        # step): no loop around it
        carry = block(mask)(free, carry)
    else:
        carry = jax.lax.fori_loop(free, free + masked_ref[step], block(mask),
                                  carry)

    def _finish():
        o_ref[0, 0] = carry[2] / jnp.maximum(carry[1], _DENOM_FLOOR)

    if scratch:
        m_ref[...] = jnp.broadcast_to(carry[0], m_ref.shape)
        d_ref[...] = jnp.broadcast_to(carry[1], d_ref.shape)
        a_ref[...] = carry[2]
        pl.when(last_ref[step] == 1)(_finish)
    else:
        _finish()


def _tiled_call(q, k, v, kv_mask, causal: bool, scale):
    """Flash attention with the queries tiled too: ``[B, H, Tq, D]`` against
    ``[B, H, Tk, D]`` keys and ``[B, H, Tk, Dv]`` values, any length. The
    score matrix never exists beyond one ``TILE_Q x TILE_K`` block in VMEM.
    A (query tile, key block) pair pays for what its place needs
    (:func:`_tile_plan`, decided here from ``causal``, whether any key can
    be masked, and the pair's indices): under ``causal`` the pairs above the
    diagonal are never run, with no key to mask the pairs below it run the
    update with no mask at all, and the key blocks of a query tile are a
    loop inside one grid step over keys that stay in VMEM. The pairs of one
    (batch, head) by kind and its grid steps are counted when the call is
    traced."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    group = h // k.shape[1]
    bq = min(TILE_Q, tq + (-tq % 16))
    bk = min(TILE_K, tk + (-tk % 128))
    q, k, v, kv_row = _padded_operands(q, k, v, kv_mask, bq, bk)
    tq_p, tk_p = q.shape[2], k.shape[2]
    key_row = kv_mask is not None or tk_p != tk
    sub = _resident_blocks(tk_p // bk, bk, d, dv, k.dtype.itemsize)
    plan = _tile_plan(tq_p // bq, tk_p // bk, sub, bq, bk, causal, key_row)
    masked_kind = ("diagonal" if causal and not key_row and bq == bk
                   else "general")
    steps = plan.shape[1]
    for kind, n in (("interior", plan[2].sum()), (masked_kind, plan[3].sum())):
        _obs_registry().counter(TILES_COUNTER, kind=kind).add(int(n))
    _obs_registry().counter(GRID_STEPS_COUNTER).add(steps)

    def query_tile(b_, h_, s, qi, *_):
        return (b_, h_, qi[s], 0)

    def key_stretch(b_, h_, s, qi, kj, *_):
        return (b_, _kv_head(h_, group), kj[s], 0)

    in_specs = [pl.BlockSpec((1, 1, bq, d), query_tile),
                pl.BlockSpec((1, 1, sub * bk, d), key_stretch),
                pl.BlockSpec((1, 1, sub * bk, dv), key_stretch)]
    operands = [q, k, v]
    if key_row:
        in_specs.append(pl.BlockSpec(
            (1, 1, sub * bk), lambda b_, h_, s, qi, kj, *_: (b_, 0, kj[s])))
        operands.append(kv_row.reshape(b, 1, tk_p))
    kern = functools.partial(
        _tiled_kernel, scale=np.float32(scale), causal=causal, bq=bq, bk=bk,
        key_row=key_row, masked_kind=masked_kind,
        one_masked=bool((plan[3] == 1).all()))
    # a query tile with several steps carries its softmax in scratch
    carried = [] if sub * bk >= tk_p else [
        pltpu.VMEM((bq, _LANES), jnp.float32),
        pltpu.VMEM((bq, _LANES), jnp.float32),
        pltpu.VMEM((bq, dv), jnp.float32)]
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(plan),
            grid=(b, h, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, bq, dv), query_tile),
            scratch_shapes=carried),
        out_shape=jax.ShapeDtypeStruct((b, h, tq_p, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_tiled",
    )(*plan, *operands)
    return out[:, :, :tq] if tq_p != tq else out


def _fits_vmem(tq: int, tk: int, d: int, block_k: int,
               mask_bytes: int = 0) -> bool:
    """Conservative per-(batch, head) VMEM bound, f32 operands assumed:
    the double-buffered Q/K/V/out blocks, their f32 working copies and
    the accumulator (lane dim padded to 128), three f32-sized score
    stripes of ``block_k``, plus ``mask_bytes`` per score element for
    a kernel that takes a materialized mask (the ring block update)."""
    d_pad = lane_pad(d)
    bk = lane_pad(min(block_k, tk))
    est = 4 * d_pad * (3 * (tq + 2 * tk) + 3 * tq) \
        + 4 * 3 * tq * bk + 2 * mask_bytes * tq * lane_pad(tk)
    return est < VMEM_BUDGET


def resolve_impl(impl: str) -> str:
    """``auto`` → the kernel on the TPU backend, the XLA reference
    elsewhere."""
    if impl not in IMPLS:
        raise ValueError(
            f"unknown attention impl {impl!r}; one of {IMPLS}")
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return impl


def _takes_kernel(impl: str, kernel: str, fits: bool, shape: tuple) -> bool:
    """The one dispatch decision of every wrapper below. A tile that
    does not fit VMEM never switches implementation quietly: under
    ``auto`` the reference runs and the miss is logged and counted,
    under an explicit ``pallas`` it raises."""
    if resolve_impl(impl) != "pallas":
        return False
    if fits:
        return True
    if impl == "pallas":
        raise ValueError(
            f"{kernel}: tile {shape} exceeds the VMEM budget and "
            "impl='pallas' was demanded; use impl='auto' or 'xla'")
    note_vmem_fallback(kernel, shape)
    return False


def flash_attention(q, k, v, kv_mask=None, causal: bool = False,
                    scale=None, impl: str = "auto",
                    block_k: int = DEFAULT_BLOCK_K):
    """Fused attention over ``[B, H, T, D]`` operands (bhtd layout —
    what :class:`~mmlspark_tpu.models.vit.BhtdSelfAttention` computes
    in). ``k`` and ``v`` may have fewer heads, ``[B, H_kv, Tk, D]`` with
    ``H_kv`` dividing ``H`` (grouped-query attention): query head ``h``
    meets K/V head ``h // (H / H_kv)``, read where it lies (the kernels'
    block index maps; ``ops.pallas.attention_kv_group`` holds ``H / H_kv``
    of the last call traced). ``kv_mask``: ``[B, Tk]`` bool key-validity
    mask (True = real key); ``causal`` adds the triangular constraint.
    Returns ``[B, H, Tq, D]`` float32 (callers cast back to their compute
    dtype); fully-masked query rows are exact zeros."""
    b, h, tq, d = q.shape
    tk = k.shape[2]
    if k.shape[1] != v.shape[1] or h % k.shape[1]:
        raise ValueError(f"{h} query heads against {k.shape[1]} key and "
                         f"{v.shape[1]} value heads: K and V need the same "
                         "head count, a divisor of the queries'")
    group = h // k.shape[1]
    s = _resolve_scale(scale, d)
    if resolve_impl(impl) == "pallas":
        _obs_registry().gauge(KV_GROUP_GAUGE).set(group)
        if _fits_vmem(tq, tk, d, block_k) and v.shape[3] == d:
            return _flash_call(q, k, v, kv_mask, causal, s, block_k)
        # the whole (batch, head) tile outgrows VMEM: tile the queries too
        return _tiled_call(q, k, v, kv_mask, causal, s)
    if group > 1:        # the reference meets every query head with a copy
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    return flash_attention_reference(
        q, k, v, _mask3(b, tq, tk, kv_mask, causal), s, block_k)


def decode_attention(q, k, v, kv_mask=None, scale=None,
                     impl: str = "auto", block_k: int = DEFAULT_BLOCK_K):
    """Single-token decode attention against cached K/V — flash
    attention at ``Tq=1``, through the same kernel.

    Autoregressive serving (serve/generate.py) holds a slot-major
    KV-cache and issues ONE query row per slot per token step: ``q``
    ``[S, H, D]``, ``k``/``v`` ``[S, H, Tk, D]`` (one slot's layer-slice
    per row), ``kv_mask`` ``[S, Tk]`` bool (True = valid cached
    position; typically ``arange(Tk) <= position``) standing in for the
    causal constraint (the cache never holds a future position).
    Returns ``[S, H, D]`` float32; a fully-masked slot (inactive,
    length 0) yields EXACT zeros, which is what lets inactive slots
    ride the fixed-shape decode program without polluting anything."""
    return flash_attention(q[:, :, None, :], k, v, kv_mask=kv_mask,
                           scale=scale, impl=impl, block_k=block_k)[:, :, 0]


# ---- the ring-hop local block: one online update as a kernel ----

def _update_kernel(q_ref, k_ref, v_ref, mask_ref, m_ref, d_ref, a_ref,
                   mo_ref, do_ref, ao_ref, *, scale: np.float32):
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    keep = mask_ref[0] != 0
    m, denom, acc = _online_update(q, k, v, keep, m_ref[0, 0],
                                   d_ref[0, 0], a_ref[0, 0], scale, jnp)
    mo_ref[0, 0] = m
    do_ref[0, 0] = denom
    ao_ref[0, 0] = acc


def _update_call(q4, k4, v4, mask3, m, denom, acc, scale):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, tq, d = q4.shape
    tk = k4.shape[2]

    def tile4(i, j):
        return (i, j, 0, 0)

    def tile_mask(i, j):
        return (i, 0, 0)

    kern = functools.partial(_update_kernel, scale=np.float32(scale))
    spec4 = lambda last: pl.BlockSpec((1, 1, tq, last), tile4,  # noqa: E731
                                      memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        grid=(b, h),
        in_specs=[
            spec4(d),
            pl.BlockSpec((1, 1, tk, d), tile4, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, tk, d), tile4, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tq, tk), tile_mask,
                         memory_space=pltpu.VMEM),
            spec4(1), spec4(1), spec4(d),
        ],
        out_specs=(spec4(1), spec4(1), spec4(d)),
        out_shape=(jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, tq, 1), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, tq, d), jnp.float32)),
        name="attention_block_update",
    )(q4, k4, v4, mask3, m, denom, acc)


def attention_block_update(q4, k4, v4, keep3, m, denom, acc, scale,
                           impl: str = "xla"):
    """One flash block update over batched ``[B, H, T, D]`` operands —
    ``ring_attention``'s per-hop local block behind its ``impl`` flag.

    ``keep3``: ``[B, Tq, Tk]`` bool (shared across heads). Carry
    ``m``/``denom`` ``[B, H, Tq, 1]``, ``acc`` ``[B, H, Tq, D]``, all
    f32. ``impl="xla"`` runs the shared body vmapped (exactly the
    historical inline update); ``impl="pallas"`` runs it as one fused
    kernel per (batch, head) tile — the score block never leaves VMEM.
    The kernel takes the mask as int32 (one 32-bit tile layout for
    every operand it compares or selects on).
    """
    s = np.float32(scale)
    tq, tk, d = q4.shape[2], k4.shape[2], q4.shape[3]
    if _takes_kernel(impl, "attention_block_update",
                     _fits_vmem(tq, tk, d, tk, mask_bytes=4),
                     (tq, tk, d)):
        return _update_call(q4, k4, v4, keep3.astype(jnp.int32),
                            m, denom, acc, s)

    def upd(q2, k2, v2, keep2, m2, d2, a2):
        return _online_update(q2, k2, v2, keep2, m2, d2, a2, s, jnp)

    over_h = jax.vmap(upd, in_axes=(0, 0, 0, None, 0, 0, 0))
    return jax.vmap(over_h)(q4, k4, v4, keep3, m, denom, acc)
