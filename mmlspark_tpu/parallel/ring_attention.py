"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

**Beyond reference parity by design.** The reference's only sequence
workload pads sentences host-side to a fixed 613 tokens and feeds them one
at a time (minibatch 1) through a pretrained BiLSTM — no sequence
parallelism of any kind exists there (SURVEY §2.6/§5; reference:
notebooks/samples/304 - Medical Entity Extraction.ipynb). A TPU-native
framework must instead treat long context as a first-class axis: sequences
shard over the ``sp`` mesh axis and attention runs distributed.

Two standard strategies, both expressed as ``shard_map`` collectives so XLA
schedules them on the ICI rings:

* :func:`ring_attention` — K/V blocks rotate around the ``sp`` ring via
  ``ppermute`` while each device keeps its Q shard resident; softmax is
  accumulated online (flash-attention style running max/denominator), so
  memory stays O(L/sp) per device and compute overlaps the ring transfers.
* :func:`ulysses_attention` — ``all_to_all`` re-shards [B, L/sp, H, D] to
  [B, L, H/sp, D] (sequence → head sharding), runs ordinary local attention
  per head group, and all-to-alls back. Cheaper for moderate L when heads
  divide the axis; ring wins at very long L.

Both shard the batch dim over ``dp`` as well (each dp group computes only
its batch slice on a dp×sp mesh), and both match single-device attention
numerics — including all-zero outputs for fully-masked query rows (tests
assert this on the 8-virtual-device CPU mesh).

**Declared sharding contracts** (verified statically by
:mod:`mmlspark_tpu.analysis.spmd`, pinned against the lowered program in
tests/test_spmd.py): q/k/v ``P('dp','sp',None,None)``, mask
``P('dp','sp')``, outputs sharded like q; ring = ``ppermute(sp)`` per
hop per rotating operand, Ulysses = ``all_to_all(sp)`` ×3 in,
``all_gather(sp)`` for the mask, ``all_to_all(sp)`` back. Neither
strategy may communicate over any other axis.
"""

from __future__ import annotations

import numpy as np


def _masked_softmax(scores, jnp):
    """Softmax over the last axis where -inf marks masked entries; rows with
    ALL entries masked yield zero weights (not NaN), matching the ring
    path's guarded accumulator."""
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(scores - m)  # exp(-inf) == 0 for masked entries
    return e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)


def _local_attention(q, k, v, scale, mask=None):
    """Plain softmax attention on local blocks: [B, Lq, H, D] x [B, Lk, H, D].

    Scores accumulate and the softmax runs in float32 whatever the
    operand dtype (bf16 operands are the MXU's native f32-accumulate
    pass); the weights return to ``v``'s dtype for the weighted sum, so a
    bf16 model's output stays bf16. A no-op for float32 operands."""
    import jax.numpy as jnp

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, -jnp.inf)
    w = _masked_softmax(scores, jnp)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def attention_reference(q, k, v, causal: bool = False, kv_mask=None):
    """Single-device reference attention (the numerics oracle).

    ``kv_mask``: [B, Lk] bool, True for real (non-pad) keys.
    """
    import jax.numpy as jnp

    # a python float: weakly typed, so it never widens a bf16 model's
    # activations (a numpy float64 scalar is strongly typed and promoted
    # everything after the first attention layer to float32)
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    mask = None
    if causal:
        L = q.shape[1]
        mask = jnp.tril(jnp.ones((L, L), bool))[None, None, :, :]
    if kv_mask is not None:
        key_mask = kv_mask[:, None, None, :]
        mask = key_mask if mask is None else (mask & key_mask)
    return _local_attention(q, k, v, scale, mask)


def _resolve_batch_axis(mesh, batch_axis):
    """Batch dim shards over ``batch_axis`` when the mesh has it (size-1
    axes are harmless); None disables batch sharding."""
    if batch_axis is not None and batch_axis in mesh.shape:
        return batch_axis
    return None


def _run_sharded(body, mesh, axis, batch_axis, q, k, v, kv_mask):
    """Shared tail of both strategies: build specs, commit inputs, shard_map."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    b_axis = _resolve_batch_axis(mesh, batch_axis)
    spec = P(b_axis, axis, None, None)
    mask_spec = P(b_axis, axis)
    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(spec, spec, spec, mask_spec),
                       out_specs=spec, check_vma=False)
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], bool)
    sharding = NamedSharding(mesh, spec)
    q = jax.device_put(q, sharding)
    k = jax.device_put(k, sharding)
    v = jax.device_put(v, sharding)
    kv_mask = jax.device_put(jnp.asarray(kv_mask, bool),
                             NamedSharding(mesh, mask_spec))
    return fn(q, k, v, kv_mask)


def ring_attention(q, k, v, mesh, axis: str = "sp", causal: bool = False,
                   kv_mask=None, batch_axis: str | None = "dp",
                   impl: str = "auto"):
    """Distributed attention over sequence shards.

    Args are *global* [B, L, H, D] arrays (or already sharded); output is
    sharded like q. L must divide by the ``axis`` size, B by the
    ``batch_axis`` size. ``kv_mask`` ([B, L] bool, True = real key) rotates
    around the ring with its K/V block so pad keys never receive attention
    weight.

    ``impl`` selects the LOCAL block's implementation — the collective
    schedule (one ``ppermute`` per hop per rotating operand) is identical
    either way. Each hop is one flash online-softmax block update
    (:func:`mmlspark_tpu.ops.pallas.attention.attention_block_update`,
    the ONE shared body): ``"xla"`` runs it vmapped under plain XLA,
    ``"pallas"`` as the fused kernel (the per-hop score block never
    leaves VMEM), ``"auto"`` = the kernel on TPU, XLA elsewhere.
    """
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.pallas.attention import (
        attention_block_update, resolve_impl,
    )

    resolved = resolve_impl(impl)
    sp = mesh.shape[axis]
    scale = np.float32(1.0 / np.sqrt(q.shape[-1]))

    def body(ql, kl, vl, maskl):
        # ql/kl/vl: [B, l, H, D] local shards; online-softmax accumulation
        # while K/V blocks rotate around the ring (one hop per step)
        me = jax.lax.axis_index(axis)
        B, l, H, D = ql.shape
        acc = jnp.zeros((B, H, l, D), jnp.float32)
        denom = jnp.zeros((B, H, l, 1), jnp.float32)
        m = jnp.full((B, H, l, 1), -jnp.inf, jnp.float32)
        qf = ql.astype(jnp.float32).transpose(0, 2, 1, 3)  # [B,H,l,D]
        perm = [(i, (i + 1) % sp) for i in range(sp)]

        kv = (kl.astype(jnp.float32), vl.astype(jnp.float32), maskl)
        for step in range(sp):
            kc, vc, mc = kv
            # K block index currently resident on this device
            kv_idx = (me - step) % sp
            keep = jnp.broadcast_to(mc[:, None, :], (B, l, l))
            if causal:
                q_pos = me * l + jnp.arange(l)[:, None]
                k_pos = kv_idx * l + jnp.arange(l)[None, :]
                keep = keep & (k_pos <= q_pos)[None]
            m, denom, acc = attention_block_update(
                qf, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3),
                keep, m, denom, acc, scale, impl=resolved)
            if step + 1 < sp:
                kv = jax.lax.ppermute(kv, axis, perm)
        out = acc / jnp.maximum(denom, 1e-30)
        return jnp.einsum("bhqd->bqhd", out).astype(ql.dtype)

    return _run_sharded(body, mesh, axis, batch_axis, q, k, v, kv_mask)


def ulysses_attention(q, k, v, mesh, axis: str = "sp",
                      causal: bool = False, kv_mask=None,
                      batch_axis: str | None = "dp",
                      impl: str = "auto"):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    Re-shards sequence → heads with one ``all_to_all``, runs full-sequence
    local attention on each head group, and re-shards back. H must divide by
    the ``axis`` size. ``kv_mask``: [B, L] bool, True = real key.

    ``impl`` selects the local attention after the re-shard (the
    collective schedule is identical either way): ``"xla"`` keeps the
    plain full-softmax path, ``"pallas"`` runs the fused flash kernel
    (:func:`mmlspark_tpu.ops.pallas.attention.flash_attention`),
    ``"auto"`` = the kernel on TPU, plain XLA elsewhere.
    """
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.ops.pallas.attention import (
        flash_attention, resolve_impl,
    )

    resolved = resolve_impl(impl)
    sp = mesh.shape[axis]
    if q.shape[2] % sp:
        raise ValueError(
            f"heads ({q.shape[2]}) must divide the {axis!r} axis ({sp})")
    scale = np.float32(1.0 / np.sqrt(q.shape[-1]))

    def body(ql, kl, vl, maskl):
        # [B, l, H, D] → all_to_all → [B, L, H/sp, D]
        def a2a(x, split, concat):
            return jax.lax.all_to_all(x, axis, split_axis=split,
                                      concat_axis=concat, tiled=True)

        qg = a2a(ql, 2, 1)
        kg = a2a(kl, 2, 1)
        vg = a2a(vl, 2, 1)
        # the mask has no head axis: gather the full [B, L] key mask
        mask_g = jax.lax.all_gather(maskl, axis, axis=1, tiled=True)
        if resolved == "pallas":
            out4 = flash_attention(
                qg.astype(jnp.float32).transpose(0, 2, 1, 3),
                kg.astype(jnp.float32).transpose(0, 2, 1, 3),
                vg.astype(jnp.float32).transpose(0, 2, 1, 3),
                kv_mask=mask_g, causal=causal, scale=scale,
                impl="pallas")
            out = out4.transpose(0, 2, 1, 3)
        else:
            mask = mask_g[:, None, None, :]
            if causal:
                L = qg.shape[1]
                mask = mask & jnp.tril(jnp.ones((L, L), bool))[None, None]
            out = _local_attention(qg.astype(jnp.float32),
                                   kg.astype(jnp.float32),
                                   vg.astype(jnp.float32), scale, mask)
        return a2a(out.astype(ql.dtype), 1, 2)

    return _run_sharded(body, mesh, axis, batch_axis, q, k, v, kv_mask)
