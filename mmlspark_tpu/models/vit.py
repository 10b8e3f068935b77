"""ViT-B/16 — distributed fine-tune flagship (BASELINE config 5).

A vision transformer is the natural TPU model: patch embedding and every
block are large dense matmuls that map straight onto the MXU, and the whole
forward is static-shaped. Design:

* 16×16 patch embed as a strided conv (one big matmul per image),
* pre-LN encoder blocks (MHSA + MLP), bfloat16 compute / float32 params,
* global-average-pool head (the standard GAP variant — no class token, so
  featurization and sequence handling stay uniform with the other models),
* ``features`` node = pooled, final-LN embedding (the featurizer cut),
  ``logits`` = classification head.

The B/16 configuration (12 layers, 768 wide, 12 heads, 3072 MLP) matches
the ubiquitous checkpoint family; smaller configs are constructor args so
tests exercise the same class.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn


class BhtdSelfAttention(nn.Module):
    """Self-attention computed in ``[B, H, T, dh]`` layout.

    Parameter tree is identical to flax's
    ``nn.MultiHeadDotProductAttention`` (``query``/``key``/``value``
    DenseGeneral kernels ``[D, H, dh]`` and ``out`` kernel ``[H, dh, D]``),
    so checkpoints are interchangeable — only the compute layout differs:
    the head axis moves next to batch BEFORE the score/weighted-sum
    einsums instead of XLA inserting transposes around each one
    (measured ~4% faster fwd+bwd at ViT-B shapes on v5e in round 4; not
    re-measured on current code).

    ``impl`` selects the attention compute (same params either way):

    * ``"einsum"`` — the historical two-einsum + full softmax path;
    * ``"flash"`` / ``"flash_xla"`` / ``"flash_pallas"`` — the fused
      online-softmax path (:mod:`mmlspark_tpu.ops.pallas.attention`,
      the serving-path attention: the score matrix never materializes
      in HBM), mapping to the kernel's ``auto``/``xla``/``pallas``
      backend selection.
    """

    heads: int
    dtype: Any = jnp.bfloat16
    impl: str = "einsum"

    IMPLS = ("einsum", "flash", "flash_xla", "flash_pallas")

    @nn.compact
    def __call__(self, x):
        if self.impl not in self.IMPLS:
            # validate up front: 'pallas'/'xla' (the kernel's own flag
            # vocabulary) must not silently run the einsum path
            raise ValueError(
                f"unknown attention impl {self.impl!r}; one of "
                f"{list(self.IMPLS)}")
        B, T, D = x.shape
        H = self.heads
        dh = D // H
        q = nn.DenseGeneral((H, dh), dtype=self.dtype, name="query")(x)
        k = nn.DenseGeneral((H, dh), dtype=self.dtype, name="key")(x)
        v = nn.DenseGeneral((H, dh), dtype=self.dtype, name="value")(x)
        k = k.transpose(0, 2, 1, 3)                  # [B,H,T,dh]
        v = v.transpose(0, 2, 1, 3)
        if self.impl.startswith("flash"):
            from mmlspark_tpu.ops.pallas.attention import flash_attention
            kernel_impl = {"flash": "auto", "flash_xla": "xla",
                           "flash_pallas": "pallas"}[self.impl]
            o = flash_attention(q.transpose(0, 2, 1, 3), k, v,
                                impl=kernel_impl)
            o = o.astype(self.dtype)
        else:
            q = q.transpose(0, 2, 1, 3) * (dh ** -0.5)
            scores = jnp.einsum("bhqd,bhkd->bhqk", q, k)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("bhqk,bhkd->bhqd", probs, v)
        o = o.transpose(0, 2, 1, 3)                  # [B,T,H,dh]
        return nn.DenseGeneral(D, axis=(-2, -1), dtype=self.dtype,
                               name="out")(o)


class EncoderBlock(nn.Module):
    dim: int
    heads: int
    mlp_dim: int
    dtype: Any = jnp.bfloat16
    attn_impl: str = "bhtd"   # "bhtd" | "flax" (same params either way)

    @nn.compact
    def __call__(self, x):
        h = nn.LayerNorm(dtype=self.dtype, name="ln1")(x)
        if self.attn_impl == "bhtd" or self.attn_impl.startswith("flash"):
            h = BhtdSelfAttention(
                heads=self.heads, dtype=self.dtype, name="attn",
                impl=("einsum" if self.attn_impl == "bhtd"
                      else self.attn_impl))(h)
        elif self.attn_impl == "flax":
            h = nn.MultiHeadDotProductAttention(
                num_heads=self.heads, dtype=self.dtype, name="attn")(h, h)
        else:
            # 'pallas'/'xla' (the kernel flag vocabulary) must not fall
            # through to the flax reference — its param tree differs, so
            # a checkpoint would fail to restore much later and opaquely
            raise ValueError(
                f"unknown attn_impl {self.attn_impl!r}; one of ['bhtd', "
                "'flax', 'flash', 'flash_xla', 'flash_pallas']")
        x = x + h
        h = nn.LayerNorm(dtype=self.dtype, name="ln2")(x)
        h = nn.Dense(self.mlp_dim, dtype=self.dtype, name="mlp_in")(h)
        h = nn.gelu(h)
        h = nn.Dense(self.dim, dtype=self.dtype, name="mlp_out")(h)
        return x + h


class ViT(nn.Module):
    """Vision transformer with GAP head; defaults are B/16."""

    num_classes: int = 1000
    patch: int = 16
    dim: int = 768
    depth: int = 12
    heads: int = 12
    mlp_dim: int = 3072
    dtype: Any = jnp.bfloat16
    # rematerialize each encoder block on the backward pass: activation HBM
    # drops from O(depth) block outputs to O(1), buying larger fine-tune
    # batches at ~1/3 extra forward FLOPs (jax.checkpoint semantics).
    # Measured on v5e it LOSES throughput at every batch that fits
    # (B=128: 137→178 ms/step) — memory capacity is not the binding
    # constraint there; the flag exists for models/batches that OOM
    remat: bool = False
    attn_impl: str = "bhtd"  # see BhtdSelfAttention; "flax" = reference;
    #                          "flash"/"flash_xla"/"flash_pallas" = the
    #                          fused online-softmax serving path
    #                          (ops/pallas/attention.py)
    # microbatch count when the encoder stack runs pipelined over a pp
    # mesh (bubble fraction (pp-1)/(M+pp-1)); batch must divide by
    # microbatches × dp extent
    pipeline_microbatches: int = 4

    OUTPUT_NAMES = ("features", "logits")

    def mesh_hooks(self, mesh) -> dict:
        """Trainer integration (train/loop.py:resolve_mesh_hooks): on a
        ``pp > 1`` mesh the encoder blocks run as the GPipe collective
        pipeline (parallel/pipeline.py) — same per-block params (and
        checkpoints) as the sequential stack."""
        kwargs: dict = {}
        handled: set = set()
        if mesh.shape.get("pp", 1) > 1:
            if self.depth % mesh.shape["pp"]:
                raise ValueError(
                    f"ViT depth {self.depth} not divisible by "
                    f"pp={mesh.shape['pp']}")
            kwargs["pipeline_mesh"] = mesh
            handled.add("pp")
        return {"apply_kwargs": kwargs, "param_rules": None,
                "handled": handled}

    @nn.compact
    def __call__(self, x, output: str = "logits", train: bool = False,
                 pipeline_mesh: Any = None):
        B, H, W, _ = x.shape
        if H % self.patch or W % self.patch:
            raise ValueError(
                f"input {H}x{W} not divisible by patch {self.patch}")
        x = nn.Conv(self.dim, (self.patch, self.patch),
                    strides=(self.patch, self.patch), dtype=self.dtype,
                    name="patch_embed")(x.astype(self.dtype))
        h, w = x.shape[1], x.shape[2]
        x = x.reshape(B, h * w, self.dim)
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (h * w, self.dim))
        x = x + pos[None].astype(self.dtype)
        if pipeline_mesh is not None and not self.is_initializing():
            x = self._pipelined_blocks(x, pipeline_mesh)
        else:
            block_cls = (nn.remat(EncoderBlock) if self.remat
                         else EncoderBlock)
            for i in range(self.depth):
                x = block_cls(self.dim, self.heads, self.mlp_dim,
                              dtype=self.dtype, attn_impl=self.attn_impl,
                              name=f"block{i}")(x)
        x = nn.LayerNorm(dtype=self.dtype, name="ln_f")(x)
        x = jnp.mean(x, axis=1)  # GAP over patches
        features = x.astype(jnp.float32)
        if output == "features":
            return features
        logits = nn.Dense(self.num_classes, dtype=self.dtype,
                          name="head")(x)
        return logits.astype(jnp.float32)

    def _pipelined_blocks(self, x, mesh):
        """Run the encoder stack through the GPipe collective pipeline.

        Params keep the sequential layout (``block{i}`` subtrees — so
        checkpoints are interchangeable between pipelined and sequential
        runs, and a pp resume of a dp run just works); they are stacked
        on a leading layer axis at trace time and handed to
        :func:`~mmlspark_tpu.parallel.pipeline.pipeline_apply`, which
        pins the traced stack replicated
        (:func:`~mmlspark_tpu.parallel.pipeline.commit_replicated` — the
        GSPMD full-to-shard edge fed unpinned trace-built operands to
        each shard multiplied by the dp extent) and reshards it over
        ``pp`` inside its shard_map. The re-stack costs one device-local
        copy of the block params per step — the price of a single param
        layout across all execution paths. Gradients flow
        through the stack back to the per-block leaves (exact; the
        pipeline is collective-differentiable)."""
        from mmlspark_tpu.parallel.pipeline import (
            pipeline_apply, stack_layer_params,
        )

        template = EncoderBlock(self.dim, self.heads, self.mlp_dim,
                                dtype=self.dtype, attn_impl=self.attn_impl)
        params = self.variables["params"]
        stacked = stack_layer_params(
            [params[f"block{i}"] for i in range(self.depth)])

        def block_fn(p, h):
            return template.apply({"params": p}, h)

        if self.remat:  # honor the flag on this path too (jax.checkpoint
            # around each block application inside the pipeline scan)
            block_fn = jax.checkpoint(block_fn)

        return pipeline_apply(block_fn, stacked, x, mesh,
                              num_microbatches=self.pipeline_microbatches)


def vit_b16(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
            **kw: Any) -> ViT:
    return ViT(num_classes=num_classes, dtype=dtype, **kw)


def vit_tiny(num_classes: int = 10, image_patch: int = 8,
             dtype: Any = jnp.float32, **kw: Any) -> ViT:
    """Small same-class config for tests/CI."""
    return ViT(num_classes=num_classes, patch=image_patch, dim=64, depth=2,
               heads=4, mlp_dim=128, dtype=dtype, **kw)
