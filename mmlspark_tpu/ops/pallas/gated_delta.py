"""The gated delta rule (Gated DeltaNet's recurrence) as one chunked Pallas
pass of matrix products.

For every row and value head ``h``, which reads key head ``h // (value
heads / key heads)``, a state ``S [key_dim, value_dim]`` is carried over the
sequence. With ``q``, ``k`` the l2-normalised query and key (``x / sqrt(|x|^2
+ 1e-6)``, the query also times ``key_dim^-1/2``), ``alpha_t = exp(g_t)`` and
``beta_t`` scalars of the head::

    S   <- alpha_t S                                           S_{-1} = 0
    d_t  = beta_t (v_t - S^T k_t)          (read along the key, corrected)
    S   <- S + k_t d_t^T
    o_t  = S^T q_t

Position by position that is ``L`` tiny sequential steps. In chunks of ``C``
positions it is matrix products plus one triangular solve a chunk (the WY /
UT form). With ``c_i`` the cumulative sum of ``g`` within the chunk up to
``i``, ``D_ij = exp(c_i - c_j)`` for ``i >= j`` (0 above), ``A`` the strictly
lower ``[C, C]`` matrix ``beta_i (k_i . k_j) D_ij`` and ``T = (I + A)^-1``::

    W = T (beta k * exp(c))        U = T (beta v)        N = U - W S
    o = (q * exp(c)) S + (q k^T * D) N
    S = exp(c_C) S + (k * exp(c_C - c))^T N

* the grid is ``(row, key head, block of positions)``, the block axis
  sequential; the states of the key head's value heads, ``[heads a key head,
  key_dim, value_dim]`` float32 in VMEM scratch, are zeroed at a row's first
  block and carried to the next. They never exist in HBM. Inside a block a
  loop walks chunks of ``CHUNK`` positions;
* **the operands are read where they lie**: the caller's causal convolution
  leaves ``[q_j | k_j | v of j's value heads]`` for every key head ``j`` side
  by side in one wide array, and the kernel is handed that array three times
  with each part's block offset in its index map: a key head's ``q`` and
  ``k`` are read once for all its value heads, and nothing is repeated;
* **a chunk's value heads are stacked**: row ``h C + t`` of a chunk's
  ``[heads a key head x C, ...]`` operands is value head ``h``'s position
  ``t`` (the key head's ``q`` and ``k`` repeated down the rows in VMEM), so
  the triangle ``A``, its inverse ``T``, ``W``, ``U`` and the masked ``q k^T``
  of all the key head's value heads are ONE product each, block-diagonal
  where square (a 128-wide tile for two heads of a 64 chunk: the MXU's
  width, where one head's 64 would fill half of it); only the state's
  products go a head at a time. The decays are masked above the diagonal
  and off the head's block BEFORE the exponential (no difference is
  exponentiated that could overflow); ``T`` comes from doubling
  (:func:`unit_lower_inverse`: ``log2 C`` steps of two products, each step
  inverting twice as wide diagonal blocks from the last), never a loop over
  rows;
* the l2 norms are taken inside the kernel in float32; ``g``, its
  cumulative sums, the decays, ``T`` and the states are float32; the
  products, the doubling's too, run on operands of the wide array's type
  with float32 accumulation (``T``, ``S`` and ``N`` rounded to it as
  operands, as the products that use ``T`` round it anyway). The per-head
  scalars reach the kernel as small float32 blocks made by array code from
  ``g`` and ``beta`` ``[rows, L, value heads]``, a ``[2, heads a key head x
  C]`` block a chunk with positions on lanes (8 bytes a value head a
  position beside the 768 of ``q``, ``k``, ``v`` and the output of a key
  head); the kernel transposes it once a chunk to get the same scalars down
  the sublanes;
* a tail that fills no whole block is padded with zero keys, ``beta = 0``
  and ``g = 0``: the state passes through; the block length comes from the
  VMEM budget alone (:func:`block_positions`).

**One trace a shape**: the entry is ONE module-level ``jax.jit``
(:func:`_gdn_call`), as :mod:`~mmlspark_tpu.ops.pallas.ssd_scan`'s.

``impl`` resolves as :func:`~mmlspark_tpu.ops.pallas.attention.resolve_impl`
does (the kernel on the TPU, the array-code form elsewhere; a CPU test asks
for the interpreter itself). :func:`gated_delta_reference` is the same
chunked function as array code (a ``lax.scan`` over chunks, float32 at
``HIGHEST``). Shapes the kernel does not take (a head that is no whole
number of lane tiles, a block that cannot fit the budget) give way to it
under ``auto``, logged and counted (``ops.pallas.vmem_fallback{kernel=
gated_delta}``), and raise under ``pallas``. Counted when the kernel call is
traced: ``ops.pallas.gated_delta_grid_steps``; gauge
``ops.pallas.gated_delta_chunk`` (positions a block).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import _takes_kernel
from mmlspark_tpu.ops.pallas.budget import VMEM_BUDGET

GRID_STEPS_COUNTER = "ops.pallas.gated_delta_grid_steps"
CHUNK_GAUGE = "ops.pallas.gated_delta_chunk"

_LANES = 128
# positions of one chunk: the public kernel's chunk, and where the
# triangular solve (C^3 a chunk) stays under the products' (C^2 x heads)
CHUNK = 64
# positions of the longest block: past it a grid step's fixed cost is
# already under a hundredth of its work
_MAX_BLOCK = 1024
# the kernel's triangular solve: its products on bfloat16 operands, as the
# products that use its inverse (which is rounded to the operands' type)
_SOLVE_PRECISION = jax.lax.Precision.DEFAULT
# the l2 norms' guard (the family's public implementation)
L2_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def block_positions(length: int, per: int, key_dim: int, value_dim: int,
                    itemsize: int) -> int:
    """Positions of one block, from the shapes and the VMEM budget alone: a
    position holds a key head's ``q``, ``k``, its value heads' ``v`` and
    outputs (``itemsize`` each) and its scalars (float32, padded to a
    sublane tile), double-buffered; the states and a chunk's stacked
    float32 temporaries are held once. Of the lengths that fit, the longest
    that divides the sequence (in whole chunks), at most ``_MAX_BLOCK``;
    ``0`` where a head is no whole number of lane tiles or not even one
    chunk fits, or where the parts of a key head's channels are no whole
    number of blocks of their own width (the kernel reads them in place)."""
    width = 2 * key_dim + per * value_dim
    if (key_dim % _LANES or value_dim % _LANES
            or width % (per * value_dim) or 2 * key_dim % (per * value_dim)):
        return 0
    stacked = per * CHUNK
    held = (4 * per * key_dim * value_dim
            + 16 * stacked * max(stacked, key_dim, value_dim) * 4)
    per_position = 2 * (itemsize * (2 * key_dim + 2 * per * value_dim)
                        + 4 * 8 * per)
    whole = -(-length // CHUNK)
    most = min((VMEM_BUDGET - held) // per_position, _MAX_BLOCK) // CHUNK
    return CHUNK * max((c for c in range(1, min(most, whole) + 1)
                        if whole % c == 0), default=0)


def unit_lower_inverse(a, block: int | None = None, precision=HIGHEST):
    """``(I + a)^-1`` of strictly lower ``a [..., C, C]``, float32, where
    ``a`` is block-diagonal in blocks of ``block`` (a power of two; all of
    ``C`` by default): starting from ``I`` (the inverse of the 1-wide
    diagonal blocks), each step makes the inverse of twice as wide diagonal
    blocks from the last, ``T <- T - T B T`` with ``B`` the lower-left blocks
    of ``a`` that the doubling joins (``[[P, 0], [B, Q]]^-1`` has ``-Q^-1 B
    P^-1`` there). Every entry it forms is one of the inverse's; written
    once for the kernel's body and the array form."""
    c = a.shape[-1]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    t = jnp.broadcast_to(jnp.where(i == j, 1.0, 0.0), a.shape)

    def dot(x, y):
        return jnp.matmul(x, y, precision=precision,
                          preferred_element_type=jnp.float32)

    width = 1
    while width < (block or c):
        # row in the second half of its 2-width block, column in the first
        joined = (((i & width) != 0) & ((j & width) == 0)
                  & ((i | (2 * width - 1)) == (j | (2 * width - 1))))
        t = t - dot(t, dot(jnp.where(joined, a, 0.0), t))
        width *= 2
    return t


def l2_normed(x):
    """``x / sqrt(|x|^2 + 1e-6)`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _split(qkv, key_heads: int, key_dim: int, per: int, value_dim: int):
    """``q``, ``k`` ``[rows, L, key heads, key_dim]`` and ``v`` ``[rows, L,
    key heads, per, value_dim]`` of the key-head-grouped wide array."""
    rows, length, _ = qkv.shape
    grouped = qkv.reshape(rows, length, key_heads, -1)
    q, k = grouped[..., :key_dim], grouped[..., key_dim:2 * key_dim]
    v = grouped[..., 2 * key_dim:].reshape(rows, length, key_heads, per,
                                           value_dim)
    return q, k, v


def gated_delta_reference(qkv, g, beta, *, key_heads: int, key_dim: int,
                          value_heads: int, value_dim: int):
    """The chunked form as array code, float32 at ``HIGHEST``: a
    ``lax.scan`` over chunks of ``CHUNK`` positions that carries the states
    ``[rows, value heads, key_dim, value_dim]``; operands as
    :func:`gated_delta`; returns float32."""
    f32, chunk = jnp.float32, CHUNK
    rows, length, _ = qkv.shape
    per = value_heads // key_heads
    pad = -length % chunk
    if pad:
        qkv = jnp.pad(qkv, ((0, 0), (0, pad), (0, 0)))
        g = jnp.pad(g, ((0, 0), (0, pad), (0, 0)))
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    q, k, v = _split(qkv, key_heads, key_dim, per, value_dim)
    q = l2_normed(q) * key_dim ** -0.5
    k = l2_normed(k)
    # every value head's copy of its key head: [rows, L, value heads, dim]
    q, k = (jnp.repeat(a, per, axis=2) for a in (q, k))
    v = v.astype(f32).reshape(rows, -1, value_heads, value_dim)

    def chunks(a):
        return jnp.moveaxis(a.reshape((rows, -1, chunk) + a.shape[2:]), 1, 0)

    i = jnp.arange(chunk)[:, None]
    j = jnp.arange(chunk)[None, :]

    def dot(spec, *ops):
        return jnp.einsum(spec, *ops, precision=HIGHEST)

    def one(s, at):
        q_c, k_c, v_c, g_c, b_c = at        # [rows, C, heads, ...]
        cum = jnp.moveaxis(jnp.cumsum(g_c.astype(f32), axis=1), 1, -1)
        b_c = jnp.moveaxis(b_c.astype(f32), 1, -1)      # [rows, heads, C]
        decay = jnp.exp(jnp.where(i >= j, cum[..., :, None]
                                  - cum[..., None, :], -jnp.inf))
        kk = dot("bihd,bjhd->bhij", k_c, k_c)
        a = jnp.where(i > j, b_c[..., :, None] * kk * decay, 0.0)
        t = unit_lower_inverse(a)
        e = jnp.exp(cum)                                # [rows, heads, C]
        w = dot("bhij,bhjd->bhid", t, (b_c * e)[..., None]
                * jnp.moveaxis(k_c, 1, 2))
        u = dot("bhij,bhjd->bhid", t, b_c[..., None]
                * jnp.moveaxis(v_c, 1, 2))
        new = u - dot("bhid,bhde->bhie", w, s)
        qk = dot("bihd,bjhd->bhij", q_c, k_c) * decay
        o = e[..., None] * dot("bihd,bhde->bhie", q_c, s) \
            + dot("bhij,bhje->bhie", qk, new)
        last = cum[..., -1:]                            # [rows, heads, 1]
        s = jnp.exp(last)[..., None] * s + dot(
            "bjhd,bhj,bhje->bhde", k_c, jnp.exp(last - cum), new)
        return s, jnp.moveaxis(o, 2, 1)                 # [rows, C, heads, dv]

    s0 = jnp.zeros((rows, value_heads, key_dim, value_dim), f32)
    _, o = jax.lax.scan(one, s0, tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1).reshape(rows, -1, value_heads * value_dim)
    return o[:, :length]


def _gdn_kernel(q_ref, k_ref, v_ref, r_ref, o_ref, st_ref, *,
                per: int, value_dim: int):
    # grid (row, key head, block). Blocks: q / k [1, block, key_dim]; v /
    # out [1, block, per x value_dim] (the key head's value heads side by
    # side); r [1, 1, chunks, 2, per x CHUNK] float32 (a chunk's cumulative
    # sums of g, then its betas, value head by value head on lanes).
    # Scratch [per, key_dim, value_dim] float32: the states. A chunk's
    # value heads are STACKED: row h C + t is value head h's position t, so
    # that what each head needs of the chunk (the triangle, its inverse, W,
    # U, the masked q k^T) is one [per C, ...] product, block-diagonal where
    # it is square
    import jax.experimental.pallas as pl

    f32, c = jnp.float32, CHUNK
    m = per * c
    dtype = v_ref.dtype
    chunks = r_ref.shape[2]
    scale = q_ref.shape[2] ** -0.5

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    i = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    same = (i ^ j) < c              # one value head's block (c a power of 2)
    row = jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)
    # the two rows of scalars padded to a sublane tile, to be transposed
    fill = jnp.zeros((8 - 2, m), f32)

    def dot(a, b):
        return jnp.dot(a, b, preferred_element_type=f32)

    def dot_t(a, b):
        """``a b^T`` without a transposed copy of ``b``."""
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    def chunk(s, carry):
        rows = pl.ds(pl.multiple_of(s * c, c), c)
        q = l2_normed(q_ref[0, rows, :]) * scale
        k = l2_normed(k_ref[0, rows, :])
        qd = q.astype(dtype)
        k2 = jnp.concatenate([k] * per, axis=0)         # [m, key_dim]
        k2d = k2.astype(dtype)
        r = r_ref[0, 0, s]                              # [2, m]
        # the same scalars down the sublanes: [m, 1] each
        cols = jnp.concatenate([r, fill], axis=0).T
        cum, beta = cols[:, 0:1], cols[:, 1:2]
        decay = jnp.exp(jnp.where(same & (i >= j), cum - r[0:1, :], -jnp.inf))
        t = unit_lower_inverse(
            jnp.where(same & (i > j), beta * dot_t(k2d, k2d) * decay, 0.0),
            block=c, precision=_SOLVE_PRECISION).astype(dtype)
        e = jnp.exp(cum)
        v2 = jnp.concatenate([v_ref[0, rows, h * value_dim:(h + 1) * value_dim]
                              for h in range(per)], axis=0).astype(f32)
        w = dot(t, (beta * e * k2).astype(dtype)).astype(dtype)
        u = dot(t, (beta * v2).astype(dtype))
        qk = (dot_t(jnp.concatenate([qd] * per, axis=0), k2d)
              * decay).astype(dtype)
        # each value head's cumulative sum at its chunk's last position
        lasts = [cols[h * c + c - 1:h * c + c, 0:1] for h in range(per)]
        last = lasts[0]
        for h in range(1, per):
            last = jnp.where(row >= h * c, lasts[h], last)
        kt = (k2 * jnp.exp(last - cum)).astype(dtype)  # k decayed to the end
        news, reads = [], []
        for h in range(per):
            at = slice(h * c, (h + 1) * c)
            st = st_ref[h]
            sd = st.astype(dtype)
            new = (u[at] - dot(w[at], sd)).astype(dtype)
            news.append(new)
            reads.append(dot(qd, sd))
            # [1, 1] to [1, value_dim] first: Mosaic broadcasts along one
            # axis at a time
            grow = jnp.exp(jnp.broadcast_to(lasts[h], (1, value_dim)))
            st_ref[h] = grow * st + dot(kt[at].T, new)
        o = e * jnp.concatenate(reads, axis=0) \
            + dot(qk, jnp.concatenate(news, axis=0))
        for h in range(per):
            o_ref[0, rows, h * value_dim:(h + 1) * value_dim] = \
                o[h * c:(h + 1) * c].astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, chunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=(
    "key_heads", "key_dim", "value_heads", "value_dim", "block"))
def _gdn_call(qkv, g, beta, *, key_heads: int, key_dim: int,
              value_heads: int, value_dim: int, block: int):
    """The kernel call and the array code around it. Module-level and
    jitted so that equal call sites share a trace and a lowering."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    rows, length, _ = qkv.shape
    per = value_heads // key_heads
    pad_l = -length % block
    blocks = (length + pad_l) // block
    _obs_registry().counter(GRID_STEPS_COUNTER).add(rows * key_heads * blocks)
    _obs_registry().gauge(CHUNK_GAUGE).set(block)

    def tail(a):
        """``a`` with the positions that fill the last block, as zeros."""
        return jnp.pad(a, ((0, 0), (0, pad_l), (0, 0))) if pad_l else a

    # the scalars of a key head's value heads, a chunk at a time: [rows,
    # key heads, chunks of the row, 2, per x CHUNK], the cumulative sums of
    # g within the chunk, then beta, each value head's positions in turn
    cum = jnp.cumsum(tail(g.astype(f32)).reshape(rows, -1, CHUNK,
                                                 value_heads), axis=2)
    b = tail(beta.astype(f32)).reshape(rows, -1, CHUNK, value_heads)
    scalars = jnp.stack(
        [a.reshape(rows, -1, CHUNK, key_heads, per) for a in (cum, b)],
        axis=-1).transpose(0, 3, 1, 5, 4, 2).reshape(
            rows, key_heads, -1, 2, per * CHUNK)
    qkv = tail(qkv)
    width = 2 * key_dim + per * value_dim       # a key head's channels

    def moved(size, offset):
        return pl.BlockSpec((1, block, size),
                            lambda r, h, i: (r, i, h * (width // size)
                                             + offset))

    out = pl.pallas_call(
        functools.partial(_gdn_kernel, per=per, value_dim=value_dim),
        grid=(rows, key_heads, blocks),
        in_specs=[
            moved(key_dim, 0), moved(key_dim, 1),
            moved(per * value_dim, 2 * key_dim // (per * value_dim)),
            pl.BlockSpec((1, 1, block // CHUNK, 2, per * CHUNK),
                         lambda r, h, i: (r, h, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block, per * value_dim),
                               lambda r, h, i: (r, i, h)),
        out_shape=jax.ShapeDtypeStruct(
            (rows, length + pad_l, value_heads * value_dim), qkv.dtype),
        scratch_shapes=[pltpu.VMEM((per, key_dim, value_dim), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="gated_delta",
    )(qkv, qkv, qkv, scalars)
    return out[:, :length] if pad_l else out


def gated_delta(qkv, g, beta, *, key_heads: int, key_dim: int,
                value_heads: int, value_dim: int, impl: str = "auto"):
    """The gated delta rule with the l2 norms of ``q`` and ``k`` fused in.

    ``qkv`` ``[rows, L, key_heads x (2 key_dim + per x value_dim)]``: the
    convolved queries, keys and values grouped by key head, ``[q_j | k_j |
    v of the value heads j * per .. j * per + per - 1]`` for each key head
    ``j`` (``per = value_heads / key_heads``); ``g`` ``[rows, L,
    value_heads]``: the log decays (``alpha = exp(g)``, ``g <= 0``); ``beta``
    ``[rows, L, value_heads]``. Returns ``o [rows, L, value_heads x
    value_dim]`` (value heads in order) in ``qkv``'s type; the decays, the
    solve, the states and the accumulation are float32 whatever the
    operands'."""
    if value_heads % key_heads:
        raise ValueError(f"{value_heads} value heads on {key_heads} key "
                         "heads: a key head serves a whole number")
    per = value_heads // key_heads
    block = block_positions(qkv.shape[1], per, key_dim, value_dim,
                            qkv.dtype.itemsize)
    if _takes_kernel(impl, "gated_delta", block > 0,
                     (CHUNK, key_dim, per * value_dim)):
        return _gdn_call(qkv, g, beta, key_heads=key_heads, key_dim=key_dim,
                         value_heads=value_heads, value_dim=value_dim,
                         block=block)
    return gated_delta_reference(
        qkv, g, beta, key_heads=key_heads, key_dim=key_dim,
        value_heads=value_heads, value_dim=value_dim).astype(qkv.dtype)
