"""ConvMoELM — a causal language model whose layers differ in kind: gated
short convolutions among grouped-query attention layers, dense feed-forward
layers first and dropless top-k expert layers after, scored as a table
column.

The family of ``model_type: lfm2_moe`` configurations (LFM2-8B-A1B), built
from the configuration's own key names (``docs/lm.md`` has the equations):

* pre-norm blocks ``h = x + Op(rms(x))``, ``y = h + FF(rms(h))``; ``Op`` is
  chosen per layer by ``layer_types[i]``, ``FF`` is a dense gated-SiLU MLP
  for ``i < num_dense_layers`` and the expert layer after; a last RMSNorm
  and the head, which is the embedding (tied);
* **gated short convolution** (``conv``): ``[B | C | u] = x W_in``, ``z = B
  * u``, a depthwise causal convolution of ``conv_L_cache`` taps over the
  sequence (zero before the row's start), ``Op(x) = (C * conv(z)) W_out``;
* **grouped-query attention** (``full_attention``): ``num_attention_heads``
  query heads share ``num_key_value_heads`` key/value heads; an RMSNorm
  over each query and key head (one learned scale each) BEFORE half-split
  RoPE (pairs ``(i, i + head_dim / 2)``); the core is
  :func:`~mmlspark_tpu.ops.pallas.attention.flash_attention`, which reads
  the shared K/V heads where they lie (no repeated copy exists);
* **experts**: a sigmoid router in float32; the picks are the
  ``num_experts_per_tok`` largest of ``score + bias`` (``use_expert_bias``),
  their weights the unbiased scores over ``(their sum + 1e-6)``; every
  expert is held, so :func:`~mmlspark_tpu.parallel.moe.moe_dropless` drops
  no token at any load and, at four row tiles of pairs an expert or more
  (its buffer packed), has one rung and no conditional.

**The parameter tree is by kind, not by position**: ``conv/*`` stacks the
conv layers in layer order, ``attn/*`` the attention layers, ``dense/*``
the dense feed-forward layers, ``router/*`` and ``routed/*`` the expert
layers, ``norms/operator_norm`` and ``norms/ffn_norm`` every layer. It
depends on the configuration alone. **How the stack is run** is decided apart, when the
module is traced (:func:`segments`): the layer list is cut into runs of a
repeated period (the 13-layer stage: one leading layer, then ``(attention,
conv, conv, conv)`` three times; the published 24: two, a period of four
four times, a period of three twice) and each run is ONE ``lax.scan`` over
its repeats whose body indexes the stacks, so compile time follows the
number of distinct periods, not the depth. The expert stacks are read in
place through ``moe_dropless(layer=)``.

Input, output nodes and dtypes as :class:`~mmlspark_tpu.models.lm.
LatentMoELM`: ``features``, ``expert_load`` (``[B, expert layers *
experts]``), ``moe_bucket``, ``token_logprob``, ``logits``.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.models.lm import (
    ExpertStacks, _fan_in_normal, _near_one, rms_norm, token_logprob,
)
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import flash_attention
# ``causal_taps`` is the convolution's array-code reference, kept under the
# name both families' tests know it by
from mmlspark_tpu.ops.pallas.causal_conv import (  # noqa: F401
    causal_conv, causal_taps,
)
from mmlspark_tpu.parallel.moe import moe_dropless

LAYER_KINDS = ("conv", "full_attention")
FF_KINDS = ("dense", "moe")
# the guard in the normalisation of the picked weights (the family's public
# implementation; no key of the configuration states it)
NORM_TOPK_EPS = 1e-6
# the longest period :func:`segments` looks for
MAX_PERIOD = 8


@dataclasses.dataclass(frozen=True)
class ConvLMConfig:
    """The sizes of one :class:`ConvMoELM`, under the configuration's own
    key names."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_dense_layers: int
    layer_types: tuple
    num_attention_heads: int
    num_key_value_heads: int
    num_experts: int
    num_experts_per_tok: int
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    rope_theta: float = 1e6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    logprob_chunk: int = 1024       # positions a head product at a time

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(f"{len(self.layer_types)} layer_types for "
                             f"{self.num_hidden_layers} layers")
        unknown = set(self.layer_types) - set(LAYER_KINDS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}; one "
                             f"of {LAYER_KINDS}")
        if self.conv_bias:
            raise ValueError("conv_bias is not supported (the family's "
                             "published configurations have none)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def n_routed_experts(self) -> int:
        """The experts held here: all of them (the name the load readers
        use for a share's count)."""
        return self.num_experts

    @property
    def kinds(self) -> tuple:
        """``(operator, feed-forward)`` of every layer, in order."""
        return tuple((op, "dense" if i < self.num_dense_layers else "moe")
                     for i, op in enumerate(self.layer_types))


def segments(kinds: tuple) -> list:
    """Cut a list of layer kinds into runs ``(start, period, repeats)``:
    at each position the period (up to ``MAX_PERIOD`` layers) whose
    immediate repeats cover the most layers, the shorter on a tie; a layer
    that starts no repeat is a run of its own."""
    out, i, n = [], 0, len(kinds)
    while i < n:
        best = (1, 1)
        for p in range(1, min(MAX_PERIOD, n - i) + 1):
            r = 1
            while kinds[i + r * p:i + (r + 1) * p] == kinds[i:i + p]:
                r += 1
            if r > 1 and p * r > best[0] * best[1]:
                best = (p, r)
        out.append((i, *best))
        i += best[0] * best[1]
    return out


def rope_tables(positions, dim: int, theta: float) -> tuple:
    """``(cos, sin)`` ``[L, dim / 2]`` float32: angle ``p * theta^(-i /
    (dim / 2))``, no scaling."""
    inv = theta ** (-np.arange(dim // 2, dtype=np.float64) / (dim // 2))
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        inv, jnp.float32)[None, :]
    return jnp.cos(angle), jnp.sin(angle)


def apply_rope_half(x, cos, sin):
    """Rotate the pairs ``(x[i], x[i + dim / 2])`` of the last axis; ``x``
    ``[B, L, heads, dim]`` float32, tables ``[L, dim / 2]``."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _dot(x, w, dtype):
    """A bias-free product on ``dtype`` operands, float32 out."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def short_conv(p: dict, x, c: ConvLMConfig):
    """The gated short convolution on normed ``x`` ``[B, L, d]``."""
    d = c.hidden_size
    with jax.named_scope("lm/conv/in"):
        bcu = _dot(x, p["in_proj"], c.dtype)
    with jax.named_scope("lm/conv/mix"):
        # [B | C | u] read where it lies: C * conv(B * u), rounded once
        y = causal_conv(bcu, p["taps"], channels=d, at=2 * d, pre_at=0,
                        post_at=d, dtype=c.dtype)
    with jax.named_scope("lm/conv/out"):
        return _dot(y, p["out_proj"], c.dtype)


def grouped_attention(p: dict, x, positions, c):
    """Causal attention of ``c.num_attention_heads`` query heads on
    ``c.num_key_value_heads`` key/value heads, on normed ``x`` ``[B, L,
    d]``. What it does beyond the projections and the core follows what it
    is handed: per-head q/k RMSNorms where the tree ``p`` holds their
    scales (``q_norm`` / ``k_norm``; eps ``c.norm_eps``), half-split RoPE
    (``c.rope_theta``) where ``positions`` are given and no positional term
    where they are ``None``. The core's scope is ``lm/mqa/attention`` on one
    key/value head, ``lm/gqa/attention`` on more."""
    b, n, _ = x.shape
    h, hkv, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
    q = _dot(x, p["q"], c.dtype).reshape(b, n, h, hd)
    k = _dot(x, p["k"], c.dtype).reshape(b, n, hkv, hd)
    v = _dot(x, p["v"], c.dtype).reshape(b, n, hkv, hd)
    if "q_norm" in p:
        q = rms_norm(q, p["q_norm"], c.norm_eps)
        k = rms_norm(k, p["k_norm"], c.norm_eps)
    if positions is not None:
        cos, sin = rope_tables(positions, hd, c.rope_theta)
        q, k = apply_rope_half(q, cos, sin), apply_rope_half(k, cos, sin)

    def bhtd(a):
        return a.astype(c.dtype).transpose(0, 2, 1, 3)

    with jax.named_scope(f"lm/{'mqa' if hkv == 1 else 'gqa'}/attention"):
        o = flash_attention(bhtd(q), bhtd(k), bhtd(v), causal=True,
                            scale=hd ** -0.5)
    return _dot(o.transpose(0, 2, 1, 3).reshape(b, n, h * hd), p["o"],
                c.dtype)


def gated_mlp(p: dict, x, dtype):
    """``(silu(x W_gate) * x W_up) W_down``, float32 out."""
    return _dot(jax.nn.silu(_dot(x, p["gate"], dtype))
                * _dot(x, p["up"], dtype), p["down"], dtype)


def expert_layer(router: dict, routed: dict, layer, x, c: ConvLMConfig):
    """Expert layer ``layer`` (an index into the ``routed`` stacks, which
    may be traced) on normed ``x`` ``[B, L, d]``: ``(y, load [B, experts],
    bucket)``."""
    b, n, d = x.shape
    flat = x.reshape(b * n, d).astype(c.dtype)
    with jax.named_scope("lm/moe/experts"):
        y, picks, bucket = moe_dropless(
            flat, router["kernel"], routed, top_k=c.num_experts_per_tok,
            norm_topk=c.norm_topk_prob, scaling=c.routed_scaling_factor,
            layer=layer, score="sigmoid",
            bias=router["bias"] if c.use_expert_bias else None,
            norm_eps=NORM_TOPK_EPS)
    with jax.named_scope("lm/moe/route"):
        load = jnp.sum(jax.nn.one_hot(picks.reshape(b, -1), c.num_experts,
                                      dtype=jnp.int32), axis=1)
    return y.reshape(b, n, d), load, bucket


def _at(stack, index):
    """Layer ``index`` (static or traced) of every leaf of a kind's stack."""
    return jax.tree.map(lambda v: jax.lax.dynamic_index_in_dim(
        v, index, keepdims=False), stack)


class KindStack(nn.Module):
    """The weights of all ``layers`` layers of one kind, each leaf a
    ``[layers, ...]`` stack; ``leaves``: ``(name, shape of one layer's,
    initializer, dtype)``."""

    layers: int
    leaves: tuple

    @nn.compact
    def __call__(self) -> dict:
        return {name: self.param(name, init, (self.layers,) + shape, dtype)
                for name, shape, init, dtype in self.leaves}


class ConvMoELM(nn.Module):
    """See the module docstring; build one with
    :func:`mmlspark_tpu.models.lm.from_config`."""

    cfg: ConvLMConfig

    OUTPUT_NAMES = ("features", "expert_load", "moe_bucket", "token_logprob",
                    "logits")

    def _stacks(self) -> dict:
        """Declare the by-kind stacks, ``kind -> {leaf: [layers, ...]}`` (a
        kind no layer has is left empty), and say what was built."""
        c = self.cfg
        d, hd = c.hidden_size, c.head_dim
        count = {kind: sum(kind in pair for pair in c.kinds)
                 for kind in LAYER_KINDS + FF_KINDS}
        reg = _obs_registry()
        for kind in LAYER_KINDS:
            reg.gauge("lm.layers", kind=kind).set(count[kind])
        for kind in FF_KINDS:
            reg.gauge("lm.ff", kind=kind).set(count[kind])

        def kernel(name, *shape):
            return (name, shape, _fan_in_normal, c.param_dtype)

        def scale(name, *shape):
            return (name, shape, _near_one, jnp.float32)

        def stack(name, layers, *leaves):
            return KindStack(layers, leaves, name=name)() if layers else {}

        q_width = c.num_attention_heads * hd
        kv_width = c.num_key_value_heads * hd
        stacks = {
            "conv": stack(
                "conv", count["conv"], kernel("in_proj", d, 3 * d),
                kernel("taps", c.conv_L_cache, d), kernel("out_proj", d, d)),
            "attn": stack(
                "attn", count["full_attention"], kernel("q", d, q_width),
                kernel("k", d, kv_width), kernel("v", d, kv_width),
                kernel("o", q_width, d), scale("q_norm", hd),
                scale("k_norm", hd)),
            "dense": stack(
                "dense", count["dense"], kernel("gate", d, c.intermediate_size),
                kernel("up", d, c.intermediate_size),
                kernel("down", c.intermediate_size, d)),
            # ``bias`` is a buffer of the checkpoint (the load balancer's
            # running correction), not a trained weight: it moves the picks
            # only; the stand-in is drawn so that it moves some
            "router": stack(
                "router", count["moe"],
                ("kernel", (d, c.num_experts), _fan_in_normal, jnp.float32),
                ("bias", (c.num_experts,), jax.nn.initializers.normal(0.05),
                 jnp.float32)),
            "routed": ExpertStacks(
                count["moe"], c.num_experts, d, c.moe_intermediate_size,
                c.param_dtype, name="routed")() if count["moe"] else {},
        }
        norms = stack("norms", c.num_hidden_layers, scale("operator_norm", d),
                      scale("ffn_norm", d))
        return {**stacks, **norms}

    def _block(self, stacks: dict, h, positions, kind: tuple, layer,
               index: dict):
        """One block; ``layer`` indexes the per-layer norms and ``index``
        the stack of each kind (any of them may be traced). Returns the
        new hidden state and ``(load, bucket)`` (``None`` for a dense
        feed-forward layer)."""
        c = self.cfg
        op, ff = kind
        x = rms_norm(h, _at(stacks["operator_norm"], layer), c.norm_eps)
        if op == "conv":
            with jax.named_scope("lm/conv"):
                mixed = short_conv(_at(stacks["conv"], index["conv"]), x, c)
        else:
            with jax.named_scope("lm/gqa"):
                mixed = grouped_attention(
                    _at(stacks["attn"], index["full_attention"]), x,
                    positions, c)
        h = (h.astype(jnp.float32) + mixed).astype(c.dtype)
        x = rms_norm(h, _at(stacks["ffn_norm"], layer), c.norm_eps)
        if ff == "dense":
            with jax.named_scope("lm/dense"):
                y = gated_mlp(_at(stacks["dense"], index["dense"]), x,
                              c.dtype)
            stats = None
        else:
            y, load, bucket = expert_layer(
                _at(stacks["router"], index["moe"]), stacks["routed"],
                index["moe"], x, c)
            stats = (load, bucket)
        return (h.astype(jnp.float32) + y).astype(c.dtype), stats

    def _run(self, stacks: dict, h, positions):
        """Every layer, a run of :func:`segments` at a time; returns the
        hidden state and the expert layers' ``load [n, B, E]`` and
        ``bucket [n]`` in layer order."""
        c = self.cfg
        kinds = c.kinds
        # each layer's place among the layers of its own kinds
        seen, ordinal = collections.Counter(), []
        for pair in kinds:
            ordinal.append({k: seen[k] for k in pair})
            seen.update(pair)
        loads = [jnp.zeros((0, h.shape[0], c.num_experts), jnp.int32)]
        buckets = [jnp.zeros((0,), jnp.int32)]
        for start, period, repeats in segments(kinds):
            per = collections.Counter(
                k for pair in kinds[start:start + period] for k in pair)

            def one_period(h, rep, start=start, period=period, per=per):
                stats = []
                for j in range(start, start + period):
                    index = {k: at + rep * per[k]
                             for k, at in ordinal[j].items()}
                    h, s = self._block(stacks, h, positions, kinds[j],
                                       j + rep * period, index)
                    stats += [] if s is None else [s]
                return h, stats

            if repeats == 1:
                h, stats = one_period(h, 0)
                stats = jax.tree.map(lambda a: a[None], stats)
            else:
                h, stats = jax.lax.scan(one_period, h, jnp.arange(repeats))
            if stats:
                # a period's expert layers, each [repeats, ...] -> layer order
                load, bucket = (jnp.stack(a, axis=1) for a in zip(*stats))
                loads.append(load.reshape((-1,) + load.shape[2:]))
                buckets.append(bucket.reshape(-1))
        return h, jnp.concatenate(loads), jnp.concatenate(buckets)

    @nn.compact
    def __call__(self, x, output: str = "logits"):
        if output not in self.OUTPUT_NAMES:
            raise ValueError(f"unknown output node {output!r}; available: "
                             f"{self.OUTPUT_NAMES}")
        c = self.cfg
        tokens = x.astype(jnp.int32)
        b, n = tokens.shape
        # as the head the table is a fan-in-normal [d, V] matrix (logits of
        # a unit-rms state have unit variance); the first operator_norm
        # rescales the rows it hands out as embeddings
        table = nn.Embed(
            c.vocab_size, c.hidden_size, param_dtype=c.param_dtype,
            embedding_init=jax.nn.initializers.normal(c.hidden_size ** -0.5),
            name="embed").embedding
        h = jnp.take(table, tokens, axis=0).astype(c.dtype)
        h, load, bucket = self._run(self._stacks(), h, jnp.arange(n))
        if output == "expert_load":
            # [expert layers, B, experts] -> a row's picks on each expert
            return load.transpose(1, 0, 2).reshape(b, -1).astype(jnp.float32)
        if output == "moe_bucket":
            return jnp.broadcast_to(bucket[None, :], (b, bucket.shape[0]))
        h = rms_norm(h, self.param("embedding_norm", _near_one,
                                   (c.hidden_size,), jnp.float32), c.norm_eps)
        if output == "features":
            return jnp.mean(h, axis=1)
        head = table.astype(c.dtype).T
        h = h.astype(c.dtype)
        with jax.named_scope("lm/head"):
            if output == "logits":
                return jnp.dot(h, head, preferred_element_type=jnp.float32)
            return token_logprob(h, head, tokens, c.logprob_chunk)
