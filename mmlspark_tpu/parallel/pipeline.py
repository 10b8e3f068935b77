"""Pipeline parallelism over the ``pp`` mesh axis — GPipe-style collective
pipelining.

**Beyond reference parity by design.** The reference has no pipeline
parallelism of any kind (SURVEY §2.6: PP "No"); its only distributed
training is a single-node MPI data-parallel ring (reference:
cntk-train/src/main/scala/CommandBuilders.scala:79-93). On TPU pods,
pipelining layers across the ``pp`` axis is one of the standard scale-out
dimensions, so the framework ships a real implementation, not a reserved
axis name.

Design (the collective-pipelining recipe — one SPMD program, no
per-stage programs):

* the L identical blocks' parameters are **stacked on a leading layer
  axis** and sharded over ``pp`` — stage *s* holds layers
  ``[s·L/P, (s+1)·L/P)``,
* inside one ``shard_map``, every stage steps the same loop
  ``M + P - 1`` times (M microbatches, P stages): apply the local layer
  stack to the in-flight activation, then ``ppermute`` it to the next
  stage. Stage 0 injects microbatch *t* at step *t*; the last stage
  collects microbatch *j* at step ``j + P - 1``. The ``P - 1`` bubble
  steps compute on stale activations whose results are never collected,
* outputs are zeroed off the last stage and ``psum``-replicated over
  ``pp``, so the caller sees an ordinary ``[B, ...]`` array,
* everything (``ppermute``, ``psum``, the scan) is differentiable, so
  ``jax.grad`` through :func:`pipeline_apply` yields exact gradients —
  the numerics match the unpipelined layer stack bit-for-bit in f32
  (asserted by the tests on the virtual CPU mesh),
* the batch axis simultaneously shards over ``dp``/``fsdp`` (each dp
  group pipelines its own microbatch slices), composing PP×DP in one
  program.

Scheduling note: this is the GPipe fill-drain schedule — bubble fraction
``(P-1)/(M+P-1)``, driven down by more microbatches. 1F1B-style
schedules reduce activation memory, not bubbles; with ``jax.grad`` the
backward replays the same collective schedule in reverse, which is the
natural fit for XLA's compilation model.
"""

from __future__ import annotations

from typing import Any, Callable


def commit_replicated(tree: Any, mesh) -> Any:
    """Pin traced shard_map operands fully replicated before entry.

    GSPMD full-to-shard sharp edge (jax ≤ 0.4.37): an operand computed
    *inside* an enclosing jit trace (e.g. per-block params re-stacked at
    trace time) can reach the partitioner sharded over mesh axes its
    ``in_spec`` leaves unmentioned; with the replication check off
    (``check_vma=False`` — required by per-shard code) the conversion
    consumes it as an **unreduced partial sum**: every shard sees
    axis-extent × the true value. On a dp×pp mesh this silently scaled
    the pipelined ViT forward by the dp extent (the dp×pp loss-parity
    seed failure). An explicit replicated sharding constraint on traced
    leaves forces the correct (local-slice) conversion; concrete arrays
    committed by ``jax.device_put`` never hit the edge and pass through
    untouched. The SPMD verifier's partial-sum escape check
    (:mod:`mmlspark_tpu.analysis.spmd`) flags shard_map call sites that
    feed trace-computed operands without this pin."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    repl = NamedSharding(mesh, P())

    def one(leaf):
        if isinstance(leaf, jax.core.Tracer):
            return jax.lax.with_sharding_constraint(leaf, repl)
        return leaf

    return jax.tree_util.tree_map(one, tree)


def stack_layer_params(layer_params: list) -> Any:
    """Stack per-layer pytrees (one per block, identical structure) into a
    single pytree with a leading layer axis — the shape
    :func:`pipeline_apply` shards over ``pp``."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *layer_params)


def pipeline_spec(mesh, stacked_params) -> Any:
    """NamedShardings placing stacked layer params on the pipeline: layer
    axis over ``pp``, replicated over every other mesh axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(leaf):
        return NamedSharding(mesh, P("pp"))

    return jax.tree_util.tree_map(one, stacked_params)


def pipeline_apply(block_fn: Callable, stacked_params: Any, x: Any,
                   mesh, num_microbatches: int) -> Any:
    """Run ``x`` through L pipelined blocks: ``block_fn(layer_params, h)``
    applied layer-by-layer, stages sharded over ``pp``.

    ``stacked_params``: pytree with leading layer axis L (from
    :func:`stack_layer_params`), L divisible by the ``pp`` extent.
    ``x``: ``[B, ...]`` with B divisible by
    ``num_microbatches × dp-extent``. Returns ``[B, ...]`` activations
    after all L blocks, identical (up to dtype rounding) to applying the
    blocks sequentially.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    pp = mesh.shape["pp"]
    M = int(num_microbatches)
    leaves = jax.tree_util.tree_leaves(stacked_params)
    L = int(leaves[0].shape[0])
    if L % pp:
        raise ValueError(f"{L} layers not divisible by pp={pp}")
    B = x.shape[0]
    dp_ext = mesh.shape["dp"] * mesh.shape["fsdp"]
    if B % (M * dp_ext):
        raise ValueError(
            f"batch {B} not divisible by microbatches {M} x dp {dp_ext}")
    mb = B // M
    xm = x.reshape((M, mb) + x.shape[1:])

    def stage_fn(stacked, xm_local):
        # stacked: [L/pp, ...] this stage's layers
        # xm_local: [M, mb/dp, ...] this dp-slice's microbatches
        idx = jax.lax.axis_index("pp")

        def apply_stage(h):
            def body(h, layer):
                return block_fn(layer, h), None
            h, _ = jax.lax.scan(body, h, stacked)
            return h

        shape = xm_local.shape[1:]
        state0 = jnp.zeros(shape, xm_local.dtype)
        out0 = jnp.zeros((M,) + shape, xm_local.dtype)

        def step(carry, t):
            state, out = carry
            # stage 0 injects microbatch t (clip keeps the gather legal
            # during the drain steps; the value is unused off stage 0)
            inject = jax.lax.dynamic_index_in_dim(
                xm_local, jnp.clip(t, 0, M - 1), 0, keepdims=False)
            h = jnp.where(idx == 0, inject, state)
            h = apply_stage(h)
            # last stage collects microbatch t-(P-1) while the pipe drains
            wi = jnp.clip(t - (pp - 1), 0, M - 1)
            valid = (idx == pp - 1) & (t >= pp - 1)
            cur = jax.lax.dynamic_index_in_dim(out, wi, 0, keepdims=False)
            out = jax.lax.dynamic_update_index_in_dim(
                out, jnp.where(valid, h.astype(out.dtype), cur), wi, 0)
            # rotate the in-flight activation one stage down the ring
            state = jax.lax.ppermute(
                h, "pp", [(i, (i + 1) % pp) for i in range(pp)])
            return (state, out), None

        (_, out), _ = jax.lax.scan(step, (state0, out0),
                                   jnp.arange(M + pp - 1))
        # outputs live on the last stage only; replicate over pp
        out = jnp.where(idx == pp - 1, out, jnp.zeros_like(out))
        return jax.lax.psum(out, "pp")

    data_axes = ("dp", "fsdp")
    # trace-computed layer stacks (the Trainer re-stacks block{i} params
    # at trace time) must be pinned replicated or the pp-unaware dp axis
    # corrupts them on entry — see commit_replicated
    stacked_params = commit_replicated(stacked_params, mesh)
    out = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(P("pp"), P(None, data_axes)),
        out_specs=P(None, data_axes),
        check_vma=False,
    )(stacked_params, xm)
    return out.reshape((B,) + out.shape[2:])
