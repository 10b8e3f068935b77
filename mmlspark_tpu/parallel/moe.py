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

# the Pallas grouped product's tiles: rows of the sorted pairs, and the
# elements of a weight tile (the contraction whole up to GMM_WHOLE_K, else
# within half of that). Read on one v5e chip at [32768, 4096] x [192, 4096,
# 2048] with ~256 rows a group: 256 rows beat 128 and 512 (a row tile is
# computed whole for every group that touches it), and these weight tiles
# beat the smaller ones (PERF.md section 6, PR 28). ``gmm_tiles`` derives
# the tiles of any shape from them: whole lane rows that divide the axis
GMM_ROWS = 256
GMM_WEIGHT_TILE = 2 ** 21
GMM_WHOLE_K = 2048


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


def gmm_tiles(m: int, k: int, n: int) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` of the Pallas grouped product ``[m, k] x [G, k,
    n]``, from the shapes alone: ``GMM_ROWS`` rows; the contraction whole
    up to ``GMM_WHOLE_K`` and within half of it past that; the result's
    tile within what ``GMM_WEIGHT_TILE`` elements leave beside ``tk``; both
    by :func:`_lane_tile`, so whole lane rows that divide their axis."""
    tk = _lane_tile(k, GMM_WHOLE_K if k <= GMM_WHOLE_K else GMM_WHOLE_K // 2)
    return min(GMM_ROWS, m), tk, _lane_tile(n, GMM_WEIGHT_TILE // tk)


def _grouped_dot(lhs, rhs, group_sizes, impl: str, out_dtype=None):
    """``lhs [M, K]`` against ``rhs [G, K, N]``: row ``i`` meets the matrix
    of the group it lies in (groups are consecutive row ranges of
    ``group_sizes``); accumulated in float32, stored as ``out_dtype``
    (float32 unless given). Rows past the last group are unspecified: the
    caller masks them."""
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
    return gmm(lhs, rhs, group_sizes, out_dtype, gmm_tiles(m, k, n))


def bucket_ladder(pairs: int, held: int, width: int) -> tuple[int, ...]:
    """The static row counts :func:`moe_dropless` may give its sorted
    buffer, from shapes alone: with ``pairs = tokens * top_k`` and ``held``
    of the router's ``width`` experts here, about 1.25x and 2x the
    expected ``pairs * held / width`` and ``pairs`` itself, each rounded up
    to whole row tiles (``GMM_ROWS``) and clipped to ``pairs``, duplicates
    dropped. Increasing; the last rung is ``pairs``, so every load fits
    one; a single rung when every expert is held or ``pairs`` is under a
    row tile."""
    def rung(num: int, den: int) -> int:
        tiles = -(-num // (den * GMM_ROWS))
        return min(pairs, tiles * GMM_ROWS)
    return tuple(sorted({rung(5 * pairs * held, 4 * width),
                         rung(2 * pairs * held, width), pairs}))


def _piece(rows: int, most: int) -> int:
    """The rows of the largest equal whole-tile pieces of a ``rows``-row
    buffer that are at most ``most`` rows each (``rows`` itself where it
    is no whole number of row tiles)."""
    tiles, rest = divmod(rows, GMM_ROWS)
    if rest or rows <= most:
        return rows
    return next(rows // k for k in range(2, tiles + 1)
                if tiles % k == 0 and rows // k <= most)


def moe_dropless(x: Any, router: Any, experts: dict, *, top_k: int,
                 first_expert: int = 0, norm_topk: bool = True,
                 scaling: float = 1.0, impl: str = "auto",
                 layer: Any = None, score: str = "softmax",
                 bias: Any = None, norm_eps: float = 0.0
                 ) -> tuple[Any, Any, Any]:
    """The routed part of a top-k expert layer on the share that holds
    experts ``[first_expert, first_expert + held)`` of the router's width.

    ``x`` ``[N, d]``; ``router`` ``[d, E]``; ``experts`` holds the held
    experts' gated-SiLU stacks ``gate``/``up`` ``[held, d, f]`` and ``down``
    ``[held, f, d]`` — or, with ``layer`` (an index, which may be traced),
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

    The sorted buffer, and everything between the sort and the combine, has
    the row count of a **rung** of :func:`bucket_ladder`: the first that
    holds this call's count of held pairs, chosen on the device
    (``lax.switch``, one branch per rung). The rows of a rung are a prefix
    of the sorted order, so they hold every held pair; each token then
    gathers its held picks' rows, pick by pick, and adds them weighted in
    float32 (an absent pick is selected away; the rows past the count are
    never read). No capacity: a call whose count passes a rung takes the
    next, and the last rung has room for every pair, so no token is dropped
    at any load; a rung above the first is worked in equal pieces no larger
    than the first, so the rare larger buffers need no more scratch than
    the usual one. With one rung (every expert held, or fewer pairs than a
    row tile) the program has no conditional.

    Returns ``(y [N, d] float32, picks [N, k], bucket () int32)``: ``y =
    sum over held picks of w_k E_k(x)``, the weights normalised over all
    ``top_k`` picks; ``bucket`` is the index of the rung taken.
    This is what expert parallelism over ``ep`` asks of one shard; the
    exchange that would bring other shards' tokens here is not part of it.
    """
    import jax
    import jax.numpy as jnp

    if impl not in EXPERT_IMPLS:
        raise ValueError(f"unknown expert impl {impl!r}; one of "
                         f"{EXPERT_IMPLS}")
    n, d = x.shape
    held = experts["gate"].shape[-3]
    picks, weights = route_topk(x, router, top_k, norm_topk, scaling, score,
                                bias, norm_eps)
    local = picks - first_expert
    group = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    # pairs of held experts first, by expert; the absent ones last
    order = jnp.argsort(group, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32),
                    axis=0)[:held]
    count = jnp.sum(sizes)
    if layer is not None:
        layers = experts["gate"].shape[0]
        sizes = jnp.zeros((layers, held), jnp.int32).at[layer].set(
            sizes).reshape(-1)
        experts = {k: v.reshape((layers * held,) + v.shape[2:])
                   for k, v in experts.items()}
    dtype = x.dtype
    # the sorted row of each (token, pick); an absent pick reads row 0 and
    # is selected away
    present = (group < held).reshape(n, top_k)
    back = jnp.where(present, jnp.argsort(order).reshape(n, top_k), 0)
    ends = jnp.cumsum(sizes)

    def products(lo, rows: int):
        """The expert outputs of the sorted pairs ``[lo, lo + rows)``, whose
        groups are the parts of the sorted groups that lie in that range."""
        part = (jnp.clip(ends, lo, lo + rows)
                - jnp.clip(ends - sizes, lo, lo + rows))
        pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
        xs = jnp.take(x, pair // top_k, axis=0, mode="clip")
        gate = _grouped_dot(xs, experts["gate"], part, impl)
        up = _grouped_dot(xs, experts["up"], part, impl)
        act = (jax.nn.silu(gate) * up).astype(dtype)
        return _grouped_dot(act, experts["down"], part, impl, dtype)

    def on_rows(rows: int, piece: int):
        """The layer over the first ``rows`` sorted pairs, ``piece`` rows
        at a time."""
        if piece == rows:
            ys = products(0, rows)
        else:
            ys = jax.lax.map(lambda lo: products(lo, piece),
                             jnp.arange(0, rows, piece)).reshape(rows, d)
        # a held pair's row lies under the count, so no row past it
        # (unspecified) is read
        y = jnp.zeros((n, d), jnp.float32)
        for j in range(top_k):
            row = jnp.take(ys, back[:, j], axis=0, mode="clip")
            y = y + jnp.where(present[:, j, None],
                              row.astype(jnp.float32) * weights[:, j, None],
                              0.0)
        return y

    ladder = bucket_ladder(n * top_k, held, router.shape[1])
    # a rung above the first runs in pieces no larger than the first, so the
    # rare larger buffers need no more scratch than the usual one
    branches = [functools.partial(on_rows, rows, _piece(rows, ladder[0]))
                for rows in ladder]
    if len(ladder) == 1:
        return branches[0](), picks, jnp.zeros((), jnp.int32)
    bucket = jnp.sum(count > jnp.asarray(ladder[:-1])).astype(jnp.int32)
    return jax.lax.switch(bucket, branches), picks, bucket
