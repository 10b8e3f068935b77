"""Sequence model family: BiLSTM tagger and Transformer encoder.

The reference's sequence workload is notebook 304 (Medical Entity
Extraction): a pretrained CNTK BiLSTM run token-tagged sentences padded
host-side to a fixed 613 tokens, minibatch 1 (reference:
notebooks/samples/304 - Medical Entity Extraction.ipynb). The TPU-native
family:

* :class:`BiLSTMTagger` — embeddings → forward+backward LSTM (``nn.RNN``
  over ``lax.scan``, compiler-friendly recurrence) → per-token logits.
  Padded/bucketed *batches* replace minibatch-1 (see
  :func:`bucket_batches`).
* :class:`TransformerTagger` — encoder blocks whose attention is pluggable:
  local (single device) or sequence-parallel ring/Ulysses over the ``sp``
  mesh axis (:mod:`mmlspark_tpu.parallel.ring_attention`) for long
  sequences.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import numpy as np
import flax.linen as nn
import jax.numpy as jnp


class BiLSTMTagger(nn.Module):
    """Per-token classification over embedded sequences."""

    vocab_size: int = 1024
    embed_dim: int = 64
    hidden: int = 128
    num_tags: int = 8
    dtype: Any = jnp.float32
    # lax.scan unroll factor for the recurrence: an RNN step's matmuls are
    # tiny, so per-iteration loop overhead dominates — unrolling 16 steps
    # per scan iteration measured 11.7 → 25.0M tokens/s at B=64/L=613 on
    # v5e in round 5 (knee at 16; 64+ regresses and blows up compile
    # time; not re-measured). Params are unaffected — execution detail only
    unroll: int = 16

    OUTPUT_NAMES = ("features", "logits")

    @nn.compact
    def __call__(self, tokens, output: str = "logits", train: bool = False,
                 mask=None):
        # tokens: [B, L] int32; mask: [B, L] bool (True = real token) — the
        # backward LSTM must start at each row's true end, not at the pad
        x = nn.Embed(self.vocab_size, self.embed_dim, name="embed")(
            tokens.astype(jnp.int32))
        seq_lengths = (jnp.sum(mask.astype(jnp.int32), axis=1)
                       if mask is not None else None)
        fwd = nn.RNN(nn.LSTMCell(self.hidden), unroll=self.unroll,
                     name="lstm_fwd")(
            x, seq_lengths=seq_lengths)
        bwd = nn.RNN(nn.LSTMCell(self.hidden), reverse=True,
                     keep_order=True, unroll=self.unroll, name="lstm_bwd")(
            x, seq_lengths=seq_lengths)
        h = jnp.concatenate([fwd, bwd], axis=-1)
        if output == "features":
            return h
        return nn.Dense(self.num_tags, name="head")(h)


class TransformerTagger(nn.Module):
    """Small encoder for per-token or pooled outputs; attention impl is
    selected by name so the same params run single-device or
    sequence-parallel."""

    vocab_size: int = 1024
    embed_dim: int = 64
    num_heads: int = 4
    num_layers: int = 2
    mlp_dim: int = 128
    num_tags: int = 8
    max_len: int = 2048
    causal: bool = False
    dtype: Any = jnp.float32
    # > 0 swaps each layer's dense MLP for a Switch-style top-1
    # mixture-of-experts FFN (parallel/moe param layout). Single-device
    # it routes densely; pass ``moe_fn`` (e.g. a closure over
    # parallel.moe.moe_apply and an ep mesh) to run the expert-parallel
    # all-to-all dispatch with the SAME params. Per-layer load-balance
    # aux losses are sown under intermediates/"moe_aux"
    moe_experts: int = 0
    # per-expert capacity headroom for the expert-parallel dispatch
    # (parallel/moe.py); tokens over capacity pass through the residual
    moe_capacity_factor: float = 2.0
    # when set and no explicit mask is passed, tokens equal to this id
    # are treated as padding (the bucketing helpers pad with 0) — how
    # padding-awareness reaches callers that can't thread a mask kwarg,
    # e.g. Trainer.fit_arrays feeding plain (tokens, tags) batches
    pad_token_id: int | None = None

    OUTPUT_NAMES = ("features", "logits")

    @nn.compact
    def __call__(self, tokens, output: str = "logits", train: bool = False,
                 attention_fn: Callable | None = None, mask=None,
                 moe_fn: Callable | None = None, cache=None, positions=None,
                 update_mask=None, return_cache: bool = False,
                 decode_attention_fn: Callable | None = None):
        # mask: [B, L] bool (True = real token); pad keys are excluded from
        # attention so logits don't depend on the bucket's padding amount.
        # attention_fn receives (q, k, v, kv_mask, causal) so a
        # causal-configured model stays causal on the sequence-parallel
        # path — ring_attention/ulysses_attention take the same kwargs.
        #
        # Autoregressive decode (serve/generate.py) threads a slot-major
        # KV-cache through the SAME params:
        #
        # * ``return_cache=True`` (prefill): the full causal forward
        #   additionally returns every layer's K/V stacked
        #   ``[B, layers, H, L, head_dim]`` — what the serve prefill
        #   program scatters into assigned cache slots;
        # * ``cache=(ck, cv)`` (decode): ``tokens`` is ``[S, 1]`` (one new
        #   token per slot), ``positions`` ``[S]`` is each slot's write
        #   index (== its current length), and the caches are
        #   ``[S, layers, H, T_max, head_dim]``. The new token's K/V is
        #   written at ``positions`` (rows where ``update_mask`` is False
        #   keep their cache untouched — the inactive-slot guard of the
        #   fixed-shape decode program), attention runs ``q_len=1``
        #   against the cache through ``decode_attention_fn`` (default
        #   :func:`~mmlspark_tpu.ops.pallas.attention.decode_attention`),
        #   and the call returns ``(logits [S, num_tags], (ck', cv'))``.
        if cache is not None:
            return self._decode_step(tokens, cache, positions, update_mask,
                                     moe_fn, decode_attention_fn)
        B, L = tokens.shape
        if mask is None and self.pad_token_id is not None:
            mask = tokens.astype(jnp.int32) != self.pad_token_id
        x = nn.Embed(self.vocab_size, self.embed_dim, name="embed")(
            tokens.astype(jnp.int32))
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.max_len, self.embed_dim))
        x = x + pos[None, :L]
        head_dim = self.embed_dim // self.num_heads
        kv_layers: list = []
        for i in range(self.num_layers):
            h = nn.LayerNorm(name=f"ln_a{i}")(x)
            qkv = nn.Dense(3 * self.embed_dim, name=f"qkv{i}")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(B, L, self.num_heads, head_dim)
            k = k.reshape(B, L, self.num_heads, head_dim)
            v = v.reshape(B, L, self.num_heads, head_dim)
            if return_cache:
                # [B, H, L, head_dim] — the slot-major cache layer slice
                kv_layers.append((k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3)))
            if attention_fn is None:
                from mmlspark_tpu.parallel.ring_attention import (
                    attention_reference,
                )
                attn = attention_reference(q, k, v, causal=self.causal,
                                           kv_mask=mask)
            else:
                attn = attention_fn(q, k, v, mask, self.causal)
            attn = attn.reshape(B, L, self.embed_dim)
            x = x + nn.Dense(self.embed_dim, name=f"proj{i}")(attn)
            h = nn.LayerNorm(name=f"ln_b{i}")(x)
            if self.moe_experts > 0:
                x = x + self._moe_ffn(h, i, moe_fn, mask)
            else:
                h = nn.Dense(self.mlp_dim, name=f"mlp_in{i}")(h)
                h = nn.gelu(h)
                x = x + nn.Dense(self.embed_dim, name=f"mlp_out{i}")(h)
        x = nn.LayerNorm(name="ln_f")(x)
        out = x if output == "features" \
            else nn.Dense(self.num_tags, name="head")(x)
        if return_cache:
            ck = jnp.stack([k for k, _ in kv_layers], axis=1)
            cv = jnp.stack([v for _, v in kv_layers], axis=1)
            return out, (ck, cv)
        return out

    def _decode_step(self, tokens, cache, positions, update_mask, moe_fn,
                     decode_attention_fn):
        """One token step against the slot-major KV-cache — the body of
        the serve plane's ONE fixed-shape decode program. Same submodule
        names (and therefore the same params) as the full forward."""
        if decode_attention_fn is None:
            from mmlspark_tpu.ops.pallas.attention import decode_attention
            decode_attention_fn = decode_attention
        ck, cv = cache
        S = tokens.shape[0]
        T = ck.shape[3]
        head_dim = self.embed_dim // self.num_heads
        positions = positions.astype(jnp.int32)
        x = nn.Embed(self.vocab_size, self.embed_dim, name="embed")(
            tokens.astype(jnp.int32))          # [S, 1, D]
        pos = self.param("pos_embed", nn.initializers.normal(0.02),
                         (self.max_len, self.embed_dim))
        x = x + jnp.take(pos, positions, axis=0)[:, None, :]
        rows = jnp.arange(S)
        # the new position becomes visible to its own query (inclusive)
        keep = jnp.arange(T)[None, :] <= positions[:, None]
        if update_mask is not None:
            keep = keep & update_mask[:, None]
            sel = update_mask[:, None, None, None, None]
        for i in range(self.num_layers):
            h = nn.LayerNorm(name=f"ln_a{i}")(x)
            qkv = nn.Dense(3 * self.embed_dim, name=f"qkv{i}")(h)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(S, self.num_heads, head_dim)
            k = k.reshape(S, self.num_heads, head_dim)
            v = v.reshape(S, self.num_heads, head_dim)
            # functional in-place write at each slot's position; rows
            # outside update_mask keep their old cache bits exactly (an
            # inactive slot's stale position must never clobber a row a
            # concurrent prefill just filled)
            ck_new = ck.at[rows, i, :, positions].set(k)
            cv_new = cv.at[rows, i, :, positions].set(v)
            if update_mask is not None:
                ck = jnp.where(sel, ck_new, ck)
                cv = jnp.where(sel, cv_new, cv)
            else:
                ck, cv = ck_new, cv_new
            attn = decode_attention_fn(q, ck[:, i], cv[:, i], keep)
            attn = attn.astype(x.dtype).reshape(S, 1, self.embed_dim)
            x = x + nn.Dense(self.embed_dim, name=f"proj{i}")(attn)
            h = nn.LayerNorm(name=f"ln_b{i}")(x)
            if self.moe_experts > 0:
                x = x + self._moe_ffn(h, i, moe_fn, None)
            else:
                h = nn.Dense(self.mlp_dim, name=f"mlp_in{i}")(h)
                h = nn.gelu(h)
                x = x + nn.Dense(self.embed_dim, name=f"mlp_out{i}")(h)
        x = nn.LayerNorm(name="ln_f")(x)
        logits = nn.Dense(self.num_tags, name="head")(x)[:, 0]
        return logits, (ck, cv)

    def mesh_hooks(self, mesh) -> dict:
        """Trainer integration (train/loop.py:resolve_mesh_hooks): on an
        ``sp > 1`` mesh attention runs as the ring collective; on an
        ``ep > 1`` mesh (with ``moe_experts > 0``) the MoE FFNs dispatch
        expert-parallel via all-to-all, expert params sharded over ``ep``.
        Same params as the single-device paths — parallelism is an
        execution detail, not a model change."""
        from jax.sharding import PartitionSpec as P

        kwargs: dict = {}
        handled: set = set()
        rules = None
        if mesh.shape.get("sp", 1) > 1:
            from mmlspark_tpu.parallel.ring_attention import ring_attention

            def attention_fn(q, k, v, kv_mask, causal, _mesh=mesh):
                return ring_attention(q, k, v, _mesh, causal=causal,
                                      kv_mask=kv_mask)

            kwargs["attention_fn"] = attention_fn
            handled.add("sp")
        if mesh.shape.get("ep", 1) > 1 and self.moe_experts > 0:
            from mmlspark_tpu.parallel.moe import moe_apply

            def moe_fn(params, x, token_mask, _mesh=mesh):
                return moe_apply(params, x, _mesh,
                                 capacity_factor=self.moe_capacity_factor,
                                 token_mask=token_mask)

            kwargs["moe_fn"] = moe_fn
            handled.add("ep")

            def rules(path: str, leaf):
                # stacked expert FFNs shard over ep on the expert axis;
                # the gate stays under the generic rules (replicated)
                name = path.rsplit("/", 1)[-1]
                if name.startswith("moe") and name.endswith(
                        ("_w_in", "_b_in", "_w_out", "_b_out")):
                    return P("ep")
                return None
        return {"apply_kwargs": kwargs, "param_rules": rules,
                "handled": handled}

    def _moe_ffn(self, h, i: int, moe_fn: Callable | None, mask):
        """Switch MoE FFN for layer ``i`` — params in the
        ``parallel/moe`` layout (gate + expert-stacked FFN), routed
        densely by default or through ``moe_fn`` for expert parallelism.
        The padding mask rides along so pad tokens never claim capacity
        slots (the padding invariant: a sentence's logits must not depend
        on its bucket's pad amount)."""
        from mmlspark_tpu.parallel.moe import moe_dense

        B, L, D = h.shape
        E = self.moe_experts
        dh = self.mlp_dim
        init = nn.initializers.lecun_normal()
        params = {
            "gate": self.param(f"moe{i}_gate", init, (D, E)),
            "w_in": self.param(f"moe{i}_w_in", init, (E, D, dh)),
            "b_in": self.param(f"moe{i}_b_in", nn.initializers.zeros,
                               (E, dh)),
            "w_out": self.param(f"moe{i}_w_out", init, (E, dh, D)),
            "b_out": self.param(f"moe{i}_b_out", nn.initializers.zeros,
                                (E, D)),
        }
        flat = h.reshape(B * L, D)
        flat_mask = None if mask is None else mask.reshape(B * L)
        y, aux = (moe_fn or moe_dense)(params, flat, flat_mask)
        self.sow("intermediates", "moe_aux", aux)
        return y.reshape(B, L, D)


# ---- padded/bucketed batching (the 613-token fixed pad, generalized) ----

def _check_sequence(i: int, s) -> np.ndarray:
    """Validate one token sequence; returns it as an int32 array.

    Typed errors instead of silent misshape: an empty sequence would
    produce an all-pad row whose logits are pure padding noise, and
    non-integer tokens would be silently cast by ``np.int32`` (floats
    floor, strings crash deep inside the embed lookup)."""
    arr = np.asarray(s)
    if arr.ndim != 1:
        raise ValueError(
            f"sequence {i} has shape {arr.shape}; expected a flat 1-D "
            "token sequence")
    if arr.size == 0:
        raise ValueError(
            f"sequence {i} is empty; an empty sequence has no tokens to "
            "tag (drop it before batching)")
    if not np.issubdtype(arr.dtype, np.integer):
        if arr.dtype == bool or not np.issubdtype(arr.dtype, np.number) \
                or not np.array_equal(arr, arr.astype(np.int64)):
            raise TypeError(
                f"sequence {i} has non-integer tokens (dtype "
                f"{arr.dtype}); token ids must be integers")
    return arr.astype(np.int32)


def pad_sequences(seqs: Sequence[Sequence[int]], length: int,
                  pad_value: int = 0) -> tuple[np.ndarray, np.ndarray]:
    """Pad token sequences to ``length``; returns (tokens, mask).

    Raises ``ValueError`` for empty or overlong sequences and
    ``TypeError`` for non-integer tokens — a sequence longer than
    ``length`` used to be silently truncated, which dropped tokens with
    no signal at all (use :func:`bucket_batches` to pick covering pads).
    """
    out = np.full((len(seqs), length), pad_value, dtype=np.int32)
    mask = np.zeros((len(seqs), length), dtype=bool)
    for i, s in enumerate(seqs):
        arr = _check_sequence(i, s)
        n = arr.shape[0]
        if n > length:
            raise ValueError(
                f"sequence {i} has {n} tokens > pad length {length}; "
                "truncation would silently drop tokens")
        out[i, :n] = arr
        mask[i, :n] = True
    return out, mask


def bucket_batches(seqs: Sequence[Sequence[int]], batch_size: int,
                   bucket_sizes: Sequence[int] = (64, 128, 256, 512, 1024),
                   pad_value: int = 0):
    """Group sequences into fixed-shape padded batches.

    Sequences are bucketed by length to the smallest covering bucket, so XLA
    compiles at most ``len(bucket_sizes)`` programs instead of one per
    unique length — the compilation-model-aware version of the reference's
    single fixed 613-token pad. Yields (tokens [b, bucket], mask, indices)
    with original row indices for order restoration.

    Raises ``ValueError`` when a sequence is empty or exceeds the
    largest bucket (it used to be silently truncated into the top
    bucket) and ``TypeError`` for non-integer tokens.
    """
    # ascending order makes the first covering bucket below the smallest
    bucket_sizes = sorted(bucket_sizes)
    buckets: dict[int, list[int]] = {b: [] for b in bucket_sizes}
    overflow = max(bucket_sizes)
    for i, s in enumerate(seqs):
        n = _check_sequence(i, s).shape[0]
        if n > overflow:
            raise ValueError(
                f"sequence {i} has {n} tokens > largest bucket "
                f"{overflow}; truncation would silently drop tokens")
        for b in bucket_sizes:
            if n <= b:
                buckets[b].append(i)
                break
    for b, idxs in buckets.items():
        for start in range(0, len(idxs), batch_size):
            chunk = idxs[start:start + batch_size]
            toks, mask = pad_sequences([seqs[i] for i in chunk], b,
                                       pad_value)
            yield toks, mask, np.asarray(chunk)
