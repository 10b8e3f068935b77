"""Expert parallelism over the ``ep`` mesh axis — a Switch-style
mixture-of-experts layer with all-to-all token dispatch.

**Beyond reference parity by design.** The reference has no MoE/expert
parallelism (SURVEY §2.6: EP "No"). The TPU-native formulation is the
Mesh-TensorFlow / Switch-Transformer dispatch algebra expressed as one
``shard_map`` over ``ep``:

* tokens shard over ``ep`` (each shard routes its own slice); expert
  parameters shard over ``ep`` on the expert axis (each shard OWNS
  ``E / ep`` experts),
* top-1 gating with a fixed per-expert **capacity** shared by the whole
  ``ep`` ring: slot positions are assigned *globally* — each shard
  ``all_gather``s the per-expert routed counts, offsets its local cumsum
  ranks by the lower shards' counts, and keeps tokens whose global rank
  fits the capacity (overflow tokens dropped — they contribute zero and
  pass through the residual). Per-shard capacity splits were the
  pad-capacity bug class: a token's survival depended on which shard its
  padding landed on, not on the global expert load,
* each shard scatters its ``[E, C, d]`` dispatch buffer with
  ``psum_scatter`` over ``ep`` — global slots are disjoint across source
  shards, so the reduce-scatter IS the union and every shard receives
  exactly its own experts' fully-populated slots — applies its local
  expert FFNs, and ``all_gather``s the expert outputs back to the source
  shards for the combine,
* a load-balancing auxiliary loss (mean gate prob × token fraction per
  expert, Switch §2.2 style) is returned alongside the outputs,
* everything is differentiable; numerics match a dense (every-expert)
  reference exactly when capacity is ample (asserted on the CPU mesh).

**Declared sharding contract** (verified statically by
:mod:`mmlspark_tpu.analysis.spmd`, pinned against the lowered program
in tests/test_spmd.py): tokens/mask ``P(('dp','fsdp','ep'))``, expert
stacks ``P('ep')``, gate replicated; collective schedule
``all_gather(ep)`` counts → ``psum_scatter(ep)`` dispatch →
``all_gather(ep)`` outputs → 3 × ``psum(dp,fsdp,ep)`` aux. The
capacity-dispatch rule (SPMD104/JX204) requires exactly the leading
count exchange this layout performs.
"""

from __future__ import annotations

import functools
from typing import Any

import numpy as np

EXPERT_IMPLS = ("auto", "ragged", "gmm")
ROW_TILE_GAUGE = "moe.row_tile"

# the Pallas grouped product's tiles: rows of the sorted pairs, and the
# elements of a weight tile (the contraction whole up to GMM_WHOLE_K, else
# within half of that). The kernel's grid visits a row tile once for every
# group with a row in it: the tile is computed whole (the store is masked,
# the product is not) and that group's weight tiles are read again. So
# where an expert expects a few tiles of pairs at most, ``moe_dropless``
# starts every expert on a tile boundary, at the one of GMM_ROWS and
# ALIGNED_ROWS rows that ``row_tile`` picks from the expected load. Read on
# one v5e chip (PERF.md section 6, PR 39): at the Mistral cell's real loads
# (~256 pairs an expert, 109-480), [rows, 4096] x [192, 4096, 2048], a call
# of the gate product took 1.71 ms packed at 256 rows (63 visits) and
# aligned 1.98 / 1.32 / 1.15 / 1.30 / 1.81 ms at 128 / 256 / 320 / 384 /
# 512 rows (80 / 47 / 33 / 32 / 32 visits): at 320 an expert takes one tile
# in most steps and a visit's product just outweighs its weight read. A
# packed buffer keeps GMM_ROWS: 256 rows beat 128 and 512 there (PR 28).
# GMM_VMEM is Mosaic's scoped VMEM, which the tiles have to fit
# (``_gmm_vmem``): beside 320 rows and a float32 result it holds a [2048,
# 1024] weight tile, not [1024, 2048], and neither beside 384 rows.
# ``gmm_tiles`` derives the tiles of any shape from these: whole lane rows
# that divide the axis
GMM_ROWS = 256
ALIGNED_ROWS = 320
GMM_WEIGHT_TILE = 2 ** 21
GMM_WHOLE_K = 2048
GMM_VMEM = 2 ** 24


def init_moe_params(key, num_experts: int, d_model: int, d_hidden: int,
                    dtype=None) -> dict:
    """Gate + stacked expert-FFN params (expert axis leading)."""
    import jax
    import jax.numpy as jnp

    dtype = dtype or jnp.float32
    kg, k1, k2 = jax.random.split(key, 3)
    s1 = 1.0 / np.sqrt(d_model)
    s2 = 1.0 / np.sqrt(d_hidden)
    return {
        "gate": jax.random.normal(kg, (d_model, num_experts), dtype) * s1,
        "w_in": jax.random.normal(
            k1, (num_experts, d_model, d_hidden), dtype) * s1,
        "b_in": jnp.zeros((num_experts, d_hidden), dtype),
        "w_out": jax.random.normal(
            k2, (num_experts, d_hidden, d_model), dtype) * s2,
        "b_out": jnp.zeros((num_experts, d_model), dtype),
    }


def moe_param_spec(mesh, params) -> Any:
    """Shardings for the param dict — derived from the SAME layout
    :func:`moe_in_specs` hands to shard_map, so device placement can
    never drift from the kernel's expectations."""
    from jax.sharding import NamedSharding

    specs = moe_in_specs()
    return {k: NamedSharding(mesh, specs[k]) for k in params}


def _expert_ffn(params_e, x):
    """One expert's FFN on [n, d] tokens; params_e carries that expert's
    slices (no expert axis)."""
    import jax.numpy as jnp
    h = jnp.maximum(x @ params_e["w_in"] + params_e["b_in"], 0.0)
    return h @ params_e["w_out"] + params_e["b_out"]


def moe_apply(params: dict, x: Any, mesh, capacity_factor: float = 2.0,
              token_mask: Any = None) -> tuple[Any, Any]:
    """Route ``x`` ``[N, d]`` through expert-parallel top-1 MoE.

    Returns ``(y, aux_loss)`` — ``y[i]`` is ``gate_i · expert(x_i)`` for
    routed tokens and 0 for capacity-dropped ones (callers add the
    residual), ``aux_loss`` is the Switch load-balancing scalar.

    ``token_mask`` (``[N]``, 1 = real token): masked-out (padding) tokens
    never claim capacity slots, output exact zeros, and are excluded from
    the aux statistics — so a sequence's real-token routing does not
    depend on how much padding its bucket added (the padding invariant
    the sequence models promise).

    ``N`` must divide by the ``dp × fsdp × ep`` extent (tokens shard over
    the data axes AND ``ep``, so a dp×ep mesh splits work instead of
    replicating it); the expert count is the leading dim of the stacked
    expert params and must divide by ``ep``.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    ep = mesh.shape["ep"]
    dp_ext = mesh.shape["dp"] * mesh.shape["fsdp"]
    E = int(params["w_in"].shape[0])
    d = int(x.shape[-1])
    N = int(x.shape[0])
    if E % ep:
        raise ValueError(f"{E} experts not divisible by ep={ep}")
    if N % (ep * dp_ext):
        raise ValueError(
            f"{N} tokens not divisible by dp*fsdp*ep = {ep * dp_ext}")
    n_local = N // (ep * dp_ext)
    # per-expert slots for the WHOLE ep ring (fixed shape for XLA). The
    # budget must be global: splitting it per source shard makes a
    # token's survival depend on how the batch (and its padding) lands
    # across shards instead of on the expert's global load — the
    # pad-capacity bug the SPMD verifier's divisibility check flags
    C = max(1, int(np.ceil(capacity_factor * n_local * ep / E)))
    e_local = E // ep
    if token_mask is None:
        token_mask = jnp.ones((N,), jnp.float32)
    token_axes = ("dp", "fsdp", "ep")

    def shard_fn(p, xs, m):
        # xs: [n_local, d] this shard's tokens; m: [n_local] 0/1 mask
        m = m.astype(jnp.float32)
        logits = xs @ p["gate"]                       # [n, E]
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)           # [n] top-1
        gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0]
        # routing bookkeeping in int32/f32 REGARDLESS of the token dtype:
        # a bf16 cumsum saturates at 256, silently aliasing slot positions.
        # Masked tokens zero their one-hot row up front: they claim no
        # capacity and vanish from dispatch, combine, and aux alike
        onehot_i = jax.nn.one_hot(expert, E, dtype=jnp.int32) \
            * m.astype(jnp.int32)[:, None]                      # [n, E]
        # GLOBAL position of each token within its expert's capacity
        # slots: local cumsum rank + the routed counts of every lower
        # ep shard (one all_gather of a tiny [E] int vector). This is
        # the cross-shard count exchange that makes capacity a property
        # of the expert, not of where the token (or its padding) landed
        counts = onehot_i.sum(axis=0)                            # [E]
        counts_all = jax.lax.all_gather(counts, "ep")            # [ep, E]
        me = jax.lax.axis_index("ep")
        before = (jnp.arange(ep) < me)[:, None].astype(jnp.int32)
        offset = (counts_all * before).sum(axis=0)               # [E]
        pos = (jnp.cumsum(onehot_i, axis=0) - onehot_i) * onehot_i
        pos = jnp.sum(pos, axis=-1)                              # [n] int32
        pos = pos + (onehot_i * offset[None, :]).sum(axis=-1)
        keep = pos < C
        # dispatch tensor [n, E, C]: one-hot over (expert, global slot)
        onehot = onehot_i.astype(jnp.float32)
        slot = jax.nn.one_hot(pos, C, dtype=jnp.float32) \
            * keep[:, None].astype(jnp.float32)
        dispatch = onehot[:, :, None] * slot[:, None, :]        # [n, E, C]
        slots = jnp.einsum("nec,nd->ecd", dispatch,
                           xs.astype(jnp.float32)).astype(xs.dtype)
        # deliver every expert's slots to its owning shard: global slots
        # are disjoint across source shards, so the reduce-scatter's sum
        # is the union, and each shard receives [e_local, C, d]
        slots = jax.lax.psum_scatter(slots.reshape(ep, e_local, C, d),
                                     "ep", scatter_dimension=0,
                                     tiled=False)
        # apply local experts to their C slots (scan unstacks the
        # expert axis of params and slots together; reverse-mode safe)
        stacked_pe = {k: p[k] for k in ("w_in", "b_in", "w_out", "b_out")}

        def one_expert(_, args):
            pe, slot = args
            return None, _expert_ffn(pe, slot)

        _, outs = jax.lax.scan(one_expert, None, (stacked_pe, slots))
        # route back: every source shard combines from the full expert
        # set, so gather the [e_local, C, d] outputs into [E, C, d]
        outs = jax.lax.all_gather(outs, "ep")                   # [ep,el,C,d]
        outs = outs.reshape(E, C, d)
        y = (jnp.einsum("nec,ecd->nd", dispatch,
                        outs.astype(jnp.float32))
             * gate.astype(jnp.float32)[:, None]).astype(xs.dtype)
        # Switch load-balance loss over REAL tokens only: global masked
        # means via psum of (numerator, count)
        cnt = jnp.maximum(jax.lax.psum(m.sum(), token_axes), 1.0)
        frac = jax.lax.psum(onehot.sum(axis=0), token_axes) / cnt
        mean_p = jax.lax.psum(
            (probs.astype(jnp.float32) * m[:, None]).sum(axis=0),
            token_axes) / cnt
        aux = E * jnp.sum(frac * mean_p)
        return y, aux[None]

    y, aux = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(moe_in_specs(), P(token_axes), P(token_axes)),
        out_specs=(P(token_axes), P()),
        check_vma=False,
    )(params, x, token_mask)
    return y, aux[0]


def moe_in_specs() -> Any:
    from jax.sharding import PartitionSpec as P
    return {"gate": P(), "w_in": P("ep"), "b_in": P("ep"),
            "w_out": P("ep"), "b_out": P("ep")}


def moe_dense(params: dict, x: Any, token_mask: Any = None
              ) -> tuple[Any, Any]:
    """Dense top-1 MoE: every token through its argmax expert, no
    capacity, no parallelism. Returns ``(y, aux)`` with the same Switch
    load-balance aux as :func:`moe_apply` — the single-device execution
    path for MoE models (and the oracle the parallel path must match
    when capacity is ample). ``token_mask`` as in :func:`moe_apply`:
    masked tokens output zero and are excluded from the aux statistics."""
    import jax
    import jax.numpy as jnp

    m = (jnp.ones((x.shape[0],), jnp.float32) if token_mask is None
         else token_mask.astype(jnp.float32))
    probs = jax.nn.softmax(x @ params["gate"], axis=-1)
    expert = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, expert[:, None], axis=1)[:, 0] * m
    E = params["w_in"].shape[0]
    outs = []
    for e in range(E):
        pe = {k: params[k][e] for k in ("w_in", "b_in", "w_out", "b_out")}
        outs.append(_expert_ffn(pe, x))
    dense = jnp.stack(outs, axis=1)                   # [N, E, d]
    sel = jnp.take_along_axis(
        dense, expert[:, None, None].repeat(dense.shape[-1], -1), 1)[:, 0]
    cnt = jnp.maximum(m.sum(), 1.0)
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32) * m[:, None]
    frac = onehot.sum(axis=0) / cnt
    mean_p = (probs.astype(jnp.float32) * m[:, None]).sum(axis=0) / cnt
    aux = E * jnp.sum(frac * mean_p)
    return sel * gate[:, None], aux


def moe_reference(params: dict, x: Any) -> Any:
    """Back-compat oracle wrapper: just the outputs of :func:`moe_dense`."""
    return moe_dense(params, x)[0]


# ---- the dropless top-k layer of one expert-parallel share ----

ROUTER_SCORES = ("softmax", "sigmoid")
# what an expert applies between its products (``relu2``: the squared ReLU)
ACTIVATIONS = ("silu", "relu2")


def _activation(name: str):
    import jax
    import jax.numpy as jnp

    return jax.nn.silu if name == "silu" else (
        lambda v: jnp.square(jax.nn.relu(v)))


def route_topk(x: Any, router: Any, top_k: int, norm_topk: bool = True,
               scaling: float = 1.0, score: str = "softmax",
               bias: Any = None, norm_eps: float = 0.0) -> tuple[Any, Any]:
    """``(picks [N, k] int32, weights [N, k] float32)``: the router's
    logits over its whole width in float32, scored by ``score`` (a softmax
    over the width, or a sigmoid of each logit); the ``top_k`` largest of
    ``score + bias`` (``bias`` ``[E]``: a selection bias, which moves the
    picks and never the weights) are the picks; their weights are the
    unbiased scores, normalised over the picks (``norm_topk``: divided by
    their sum plus ``norm_eps``) and times ``scaling``."""
    import jax
    import jax.numpy as jnp

    if score not in ROUTER_SCORES:
        raise ValueError(f"unknown router score {score!r}; one of "
                         f"{ROUTER_SCORES}")
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if score == "softmax"
              else jax.nn.sigmoid(logits))
    if bias is None:
        weights, picks = jax.lax.top_k(scores, top_k)
    else:
        _, picks = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, picks, axis=-1)
    if norm_topk:
        total = jnp.sum(weights, axis=-1, keepdims=True)
        weights = weights / (total + norm_eps if norm_eps else total)
    return picks.astype(jnp.int32), weights * scaling


def _lane_tile(width: int, most: int) -> int:
    """The tile of a grouped product's contraction or result axis of
    ``width`` elements, at most ``most``: the axis whole where it fits;
    else the largest whole number of 128-lane rows within ``most`` that
    divides it (1792 = 14 x 128 under 1024: 896), so that no tile is part
    empty; else the largest whole number of lane rows within ``most``."""
    if width <= most:
        return width
    lanes = most // 128
    return 128 * next((t for t in range(lanes, 0, -1)
                       if width % (128 * t) == 0), lanes)


def _gmm_vmem(tm: int, tk: int, tn: int) -> int:
    """The bytes of scoped VMEM Mosaic allots the grouped product at these
    tiles, as its refusals state them (17.02 MiB at ``(384, 2048, 1024)``):
    three row tiles and two weight tiles in bfloat16, the result tile in
    float32 twice and its accumulator."""
    return 6 * tm * tk + 4 * tk * tn + 12 * tm * tn


def gmm_tiles(m: int, k: int, n: int, rows: int = GMM_ROWS
              ) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of the Pallas grouped product ``[m, k] x [G, k,
    n]``, from the shapes alone: ``rows`` rows (``GMM_ROWS``, or the tile
    the buffer's groups are aligned to); the contraction whole up to
    ``GMM_WHOLE_K`` and within half of it past that; the result's tile
    within what ``GMM_WEIGHT_TILE`` elements leave beside ``tk``; both by
    :func:`_lane_tile`, so whole lane rows that divide their axis. Where a
    long contraction's tiles pass ``GMM_VMEM`` beside ``rows`` rows (more
    rows than ``GMM_ROWS``), it is cut within ``GMM_WHOLE_K`` instead,
    which halves the result tile (where that does not fit either, Mosaic
    refuses the program)."""
    tm = min(rows, m)
    for most in ((GMM_WHOLE_K // 2, GMM_WHOLE_K) if k > GMM_WHOLE_K
                 else (GMM_WHOLE_K,)):
        tk = _lane_tile(k, most)
        tn = _lane_tile(n, GMM_WEIGHT_TILE // tk)
        if _gmm_vmem(tm, tk, tn) <= GMM_VMEM:
            break
    return tm, tk, tn


def _grouped_dot(lhs, rhs, group_sizes, impl: str, out_dtype=None,
                 rows: int = GMM_ROWS):
    """``lhs [M, K]`` against ``rhs [G, K, N]``: row ``i`` meets the matrix
    of the group it lies in (groups are consecutive row ranges of
    ``group_sizes``); accumulated in float32, stored as ``out_dtype``
    (float32 unless given), the kernel's row tile ``rows``. Rows past the
    last group are unspecified: the caller masks them."""
    import jax
    import jax.numpy as jnp

    if impl == "auto":
        impl = "gmm" if jax.default_backend() == "tpu" else "ragged"
    out_dtype = jnp.float32 if out_dtype is None else out_dtype
    if impl == "ragged":
        return jax.lax.ragged_dot(
            lhs, rhs, group_sizes,
            preferred_element_type=jnp.float32).astype(out_dtype)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    # the kernel's dynamic grid visits only the tiles that hold rows of a
    # group, so the work follows the pairs really routed here
    (m, k), n = lhs.shape, rhs.shape[2]
    return gmm(lhs, rhs, group_sizes, out_dtype, gmm_tiles(m, k, n, rows))


def row_tile(pairs: int, width: int) -> int:
    """The rows every held expert's pairs are padded to a whole number of
    in the sorted buffer, so that every group starts on a tile boundary and
    the grouped product visits no tile twice; 1 where the pairs stay packed
    (no padding). From the expected pairs an expert ``pairs / width``
    alone: the one of ``GMM_ROWS`` and ``ALIGNED_ROWS`` that holds 1.25x
    that load in the fewer rows (the larger on a tie: fewer visits), which
    is the faster on the chip at each of the six loads read, 128 to 1,024
    pairs an expert; packed under half a tile an expert (every expert
    takes a whole tile for less than half of one: packed won at 96 and
    under, lost by 13 % at 128), from four tiles up (a shared tile is at
    most one visit in five and the padding costs the operations around the
    products more rows than it saves the products visits: aligned won by
    5 % at 1,024 and lost by 5 % at 2,048), and where all the pairs fit one
    tile (PERF.md section 6, PR 39)."""
    if (pairs <= ALIGNED_ROWS
            or not GMM_ROWS * width <= 2 * pairs < 8 * ALIGNED_ROWS * width):
        return 1
    room = -(-5 * pairs // (4 * width))
    return min(ALIGNED_ROWS, GMM_ROWS, key=lambda t: -(-room // t) * t)


def bucket_ladder(pairs: int, held: int, width: int) -> tuple[int, ...]:
    """The static row counts :func:`moe_dropless` may give its sorted
    buffer, from shapes alone, with ``pairs = tokens * top_k`` and ``held``
    of the router's ``width`` experts here. Where the buffer is aligned
    (:func:`row_tile`) it holds **padded** rows: every held expert's pairs
    fill whole row tiles, so a load of ``count`` held pairs takes up to
    ``count + held * (tile - 1)`` rows. The first rung holds 1.25x the
    expected ``pairs * held / width`` pairs and three eighths of a tile of
    padding an expert, rounded up to whole row tiles (at the Mistral cell,
    44 tiles: 1,152 of its layer-steps over twelve seeds took 32-41; the
    loads read at 128 to 1,024 pairs an expert fit theirs too); the others
    are whole multiples of the first: twice it, and the fewest that hold
    the worst load of all ``pairs`` (every pair on a held expert and,
    aligned, every expert's last tile holding one row), so every load fits
    the last. A single rung, the worst load, where the first rung would
    hold it: all ``pairs`` within a row tile, or every expert held and the
    pairs packed or a few tiles an expert."""
    tile = row_tile(pairs, width)
    rows = tile if tile > 1 else GMM_ROWS
    worst = (pairs + held * (tile - 1)) // tile * tile
    first = -(-5 * pairs * held // (4 * width)) + held * (3 * tile // 8)
    first = min(worst, -(-first // rows) * rows)
    most = -(-worst // first)       # first rungs that hold the worst load
    return tuple(first * k for k in sorted({1, min(2, most), most}))


def row_tile_visits(sizes, tile: int) -> int:
    """The row tiles the grouped product visits for consecutive groups of
    ``sizes`` rows (host integers) at row tile ``tile``: a tile is visited
    once for every group with a row in it, computed whole and with that
    group's matrix read again each time. ``sum(ceil(size / tile))`` where
    every group starts on a tile boundary (the sizes :func:`moe_dropless`
    hands the kernel); up to one more a group where they start anywhere.
    ``held pairs / (visits * tile)`` is the share of the computed rows that
    are pairs."""
    sizes = np.asarray(sizes, np.int64)
    ends = np.cumsum(sizes)
    tiles = -(-ends // tile) - (ends - sizes) // tile
    return int(tiles[sizes > 0].sum())


def _pad_below(pos, bounds, pad):
    """For each of ``pos`` the sum of ``pad[j]`` over the ``j`` whose
    ``bounds[j] <= pos``: the padding rows that lie before a position."""
    import jax.numpy as jnp

    return jnp.sum(jnp.where(bounds <= pos[:, None], pad, 0), axis=1)


def moe_dropless(x: Any, router: Any, experts: dict, *, top_k: int,
                 first_expert: int = 0, norm_topk: bool = True,
                 scaling: float = 1.0, impl: str = "auto",
                 layer: Any = None, score: str = "softmax",
                 bias: Any = None, norm_eps: float = 0.0,
                 activation: str | None = None) -> tuple[Any, Any, Any]:
    """The routed part of a top-k expert layer on the share that holds
    experts ``[first_expert, first_expert + held)`` of the router's width.

    ``x`` ``[N, d]``; ``router`` ``[d, E]``; ``experts`` holds the held
    experts' stacks ``up`` ``[held, d, f]`` and ``down`` ``[held, f, d]``,
    and the expert's form follows what it is handed: with a ``gate`` stack
    beside them the expert is gated, ``down(act(x gate) * (x up))``, three
    grouped products; without one it is ungated, ``down(act(x up))``, two.
    ``activation`` is one of ``ACTIVATIONS`` (``None``: ``silu`` for a gated
    expert, ``relu2``, the squared ReLU, for an ungated one). Or, with
    ``layer`` (an index, which may be traced),
    the stacks of ALL layers with a leading layer axis: the grouped product
    then reads layer ``layer``'s matrices where they lie (its groups are
    that layer's; every other layer's are empty), where slicing the layer
    out first would copy its weights every step, as a scan over stacked
    weights does for an operand of a kernel. Every token routes over all
    ``E`` (``score``, ``bias`` and ``norm_eps`` as in :func:`route_topk`);
    the (token, expert)
    pairs that land on a held expert are sorted by expert (the absent ones
    last) and run through one grouped matrix product per stack; a pick of an
    absent expert adds nothing here (its chip adds it in the deployment).

    **The sorted buffer's layout.** Where an expert expects from half a row
    tile to four of pairs (:func:`row_tile`, which picks the tile from that
    load), each held expert's pairs occupy a whole number of row tiles: its
    group is its count rounded up to the tile (an expert with no pair keeps
    0), so every group starts on a tile boundary, the kernel visits each
    tile once, and reads an expert's matrix once a tile of its own
    (:func:`row_tile_visits`). A pair's
    buffer row is its rank in the sorted order plus the padding of the
    experts before its own; a buffer row finds its pair the other way
    round, from the padded ends. A padding row reads some valid token: it
    lies in a tile that is computed whole anyway, and is never read back.
    Elsewhere the pairs are packed: a group starts where the one before it
    ended.

    The buffer, and everything between the sort and the combine, has
    the row count of a **rung** of :func:`bucket_ladder`: the first that
    holds this call's (padded) rows, chosen on the device
    (``lax.switch``, one branch per rung). The rows of a rung are a prefix
    of the buffer's order, so they hold every held pair; each token then
    gathers its held picks' rows, pick by pick, and adds them weighted in
    float32 (an absent pick is selected away; the rows past the buffer's
    last group are never read). No capacity: a call whose rows pass a rung
    takes the next, and the last rung has room for the worst load of all
    pairs, so no token is dropped at any load; a rung above the first is a
    whole number of first rungs and is worked one at a time, so the rare
    larger buffers need no more scratch, and no other kernel, than the
    usual one; its rows past the last pair read a clipped pair that no
    token reads back. With one rung (the first would hold the worst
    load: every expert held and the pairs packed or a few tiles an expert,
    or fewer pairs than a row tile) the program has no conditional.

    Returns ``(y [N, d] float32, picks [N, k], bucket () int32)``: ``y =
    sum over held picks of w_k E_k(x)``, the weights normalised over all
    ``top_k`` picks; ``bucket`` is the index of the rung taken.
    This is what expert parallelism over ``ep`` asks of one shard; the
    exchange that would bring other shards' tokens here is not part of it.
    Gauge, set when traced: ``moe.row_tile``.
    """
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.obs.metrics import registry

    if impl not in EXPERT_IMPLS:
        raise ValueError(f"unknown expert impl {impl!r}; one of "
                         f"{EXPERT_IMPLS}")
    n, d = x.shape
    gated = "gate" in experts
    if activation is None:
        activation = "silu" if gated else "relu2"
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown expert activation {activation!r}; one "
                         f"of {sorted(ACTIVATIONS)}")
    held = experts["down"].shape[-3]
    tile = row_tile(n * top_k, router.shape[1])
    tm = tile if tile > 1 else GMM_ROWS     # the kernel's row tile
    registry().gauge(ROW_TILE_GAUGE).set(tile)
    picks, weights = route_topk(x, router, top_k, norm_topk, scaling, score,
                                bias, norm_eps)
    local = picks - first_expert
    group = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    # pairs of held experts first, by expert; the absent ones last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32),
                    axis=0)[:held]
    # each expert's rows in the buffer: its pairs and the padding that
    # fills its last tile
    padded = -(-sizes // tile) * tile
    pad = padded - sizes
    ends = jnp.cumsum(padded)
    groups = padded
    if layer is not None:
        layers = experts["down"].shape[0]
        groups = jnp.zeros((layers, held), jnp.int32).at[layer].set(
            padded).reshape(-1)
        experts = {k: v.reshape((layers * held,) + v.shape[2:])
                   for k, v in experts.items()}
    group_ends = jnp.cumsum(groups)
    dtype = x.dtype
    # the buffer row of each (token, pick): its sorted rank past the padding
    # of the experts before its own; an absent pick reads row 0 and is
    # selected away
    present = (group < held).reshape(n, top_k)
    row_of = jnp.argsort(order)
    if tile > 1:
        row_of = row_of + _pad_below(group, jnp.arange(1, held + 1), pad)
    back = jnp.where(present, row_of.reshape(n, top_k), 0)

    def products(lo, rows: int):
        """The expert outputs of the buffer rows ``[lo, lo + rows)``, whose
        groups are the parts of the padded groups that lie in that range."""
        part = (jnp.clip(group_ends, lo, lo + rows)
                - jnp.clip(group_ends - groups, lo, lo + rows))
        # a row's pair: its sorted rank is the row less the padding before
        # it (a padding row lands on a neighbour's pair; a row past the
        # last pair is clipped to it)
        row = lo + jnp.arange(rows)
        if tile > 1:
            row = row - _pad_below(row, ends, pad)
        pair = jnp.take(order, row, mode="clip")
        xs = jnp.take(x, pair // top_k, axis=0, mode="clip")
        act = _activation(activation)
        if gated:
            gate = _grouped_dot(xs, experts["gate"], part, impl, rows=tm)
            up = _grouped_dot(xs, experts["up"], part, impl, rows=tm)
            hidden = (act(gate) * up).astype(dtype)
        else:
            hidden = act(_grouped_dot(xs, experts["up"], part, impl,
                                      rows=tm)).astype(dtype)
        return _grouped_dot(hidden, experts["down"], part, impl, dtype,
                            rows=tm)

    ladder = bucket_ladder(n * top_k, held, router.shape[1])

    def on_rungs(count: int):
        """The layer over the first ``count`` first rungs of the buffer, a
        rung at a time."""
        if count == 1:
            ys = products(0, ladder[0])
        else:
            ys = jax.lax.map(lambda lo: products(lo, ladder[0]),
                             jnp.arange(count) * ladder[0]
                             ).reshape(count * ladder[0], d)
        # a held pair's row lies under the last group's end, so no row past
        # it (unspecified) is read
        y = jnp.zeros((n, d), jnp.float32)
        for j in range(top_k):
            row = jnp.take(ys, back[:, j], axis=0, mode="clip")
            y = y + jnp.where(present[:, j, None],
                              row.astype(jnp.float32) * weights[:, j, None],
                              0.0)
        return y

    branches = [functools.partial(on_rungs, rows // ladder[0])
                for rows in ladder]
    if len(ladder) == 1:
        return branches[0](), picks, jnp.zeros((), jnp.int32)
    bucket = jnp.sum(ends[-1] > jnp.asarray(ladder[:-1])).astype(jnp.int32)
    return jax.lax.switch(bucket, branches), picks, bucket
