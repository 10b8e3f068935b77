"""Qwen3NextLM — a causal language model whose layers mix by Gated DeltaNet
(a gated delta-rule linear attention) three times in four and by gated
softmax attention the fourth, with a dropless top-k expert layer after
every mixer, scored as a table column.

The family of ``model_type: qwen3_next`` configurations
(Qwen3-Next-80B-A3B), built from the configuration's own key names
(``docs/lm.md`` has the equations). ``rms0(x) = x / rms(x) * (1 + w)`` is
the family's zero-centred norm:

* layer ``i``: ``h = h + Mixer_i(rms0(h))``, ``h = h + MoE_i(rms0(h))``; the
  mixer is attention where ``(i + 1) % full_attention_interval == 0`` and
  Gated DeltaNet elsewhere; then ``rms0`` and an UNTIED head. Every layer
  has the expert layer (``decoder_sparse_step`` 1, ``mlp_only_layers``
  empty: a dense MLP layer is refused, not approximated);
* **Gated DeltaNet** (``gdn``): ``in_proj_qkvz`` and ``in_proj_ba`` lay
  their columns out by key head, ``[q_j | k_j | v_j | z_j]`` and ``[b_j |
  a_j]`` (``v_j``, ``z_j``, ``b_j``, ``a_j``: key head ``j``'s value heads),
  as the family's public implementation does, and the program takes the
  ``[q | k | v]`` columns and the ``z`` columns as two products
  (:func:`split_qkvz`); a depthwise causal convolution of
  ``linear_conv_kernel_dim`` taps (no bias) and SiLU over ``[q | k | v]``
  (:func:`~mmlspark_tpu.ops.pallas.causal_conv.causal_conv`), its output
  left grouped by key head; ``beta = sigmoid(b)``, ``g
  = -exp(A_log) softplus(a + dt_bias)``; the recurrence with the l2 norms of
  ``q`` and ``k`` fused in (:func:`~mmlspark_tpu.ops.pallas.gated_delta.
  gated_delta`: chunked matrix products, the states in VMEM); then each value
  head's output ``rms(o) * w`` and THEN ``* silu(z)`` (the norm first, one
  plain ``[value_dim]`` scale for all heads); the out-projection;
* **gated attention** (``attention``): the query projection gives every head
  ``[q_h | gate_h]``; ``q`` and ``k`` get ``rms0`` over the head; rotary on
  the first ``partial_rotary_factor x head_dim`` dims (half-split pairs,
  ``rope_theta``); the causal core
  (:func:`~mmlspark_tpu.ops.pallas.attention.flash_attention`, K and V read
  once a key/value head); ``o_h * sigmoid(gate_h)``; ``W_o``;
* **experts**: a softmax router in float32 over ``router_width``; the picks
  are the ``num_experts_per_tok`` largest, their weights renormalised over
  the picks (``norm_topk_prob``); gated-SiLU experts
  (:func:`~mmlspark_tpu.parallel.moe.moe_dropless`, three grouped products);
  one shared expert of the same form at ``shared_expert_intermediate_size``
  times ``sigmoid(x w_sg)``. The module holds the experts ``[first_expert,
  first_expert + num_experts)`` — ``num_experts`` counts those HELD, one
  chip's share of an expert-parallel deployment — and a pick of an absent
  expert adds nothing here.

**The parameter tree is by kind**: ``gdn/*`` stacks the Gated DeltaNet
layers in layer order, ``attn/*`` the attention layers, ``router/*``,
``routed/*`` (``[layers, held, ...]``, read in place), ``shared/*`` every
layer's experts, ``norms/*`` every layer's two norms; ``embed``, ``head``,
``norm_f``. **The stack runs as ONE ``lax.scan`` over all layers** whose body
picks its mixer by ``lax.switch`` and then runs the expert layer (as
:mod:`~mmlspark_tpu.models.lm_mamba2` does): each kernel is ONE instruction
of the program whose device time sums all its layers.

Input, output nodes and dtypes as :class:`~mmlspark_tpu.models.lm.
LatentMoELM`: ``features``, ``expert_load`` (``[B, layers * held]``),
``moe_bucket``, ``token_logprob``, ``logits``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.models.lm import (
    ExpertStacks, Kernel, _fan_in_normal, _near_one, rms_norm, token_logprob,
)
from mmlspark_tpu.models.lm_conv import (
    KindStack, _at, _dot, apply_rope_half, gated_mlp, rope_tables,
)
from mmlspark_tpu.models.lm_mamba2 import _a_log, _dt_bias
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import flash_attention
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv
from mmlspark_tpu.ops.pallas.gated_delta import gated_delta
from mmlspark_tpu.parallel.moe import moe_dropless

LAYER_KINDS = ("gdn", "attention")
# the step sizes a fresh Gated DeltaNet head starts from, as the Mamba
# layers' (a head forgets over 1 to 1,000 positions)
DT_RANGE = (1e-3, 1e-1, 1e-4)


def _zero_centred(key, shape, dtype):
    """A zero-centred norm's ``w`` (applied as ``1 + w``): normal(0,
    0.1^2)."""
    return (0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """The sizes of one :class:`Qwen3NextLM`, under the configuration's own
    key names. ``num_experts`` counts the experts HELD here
    (``first_expert`` on) and ``router_width`` the experts the router scores
    (the published count; ``None``: every routed expert is held);
    ``vocab_size`` is the held slice of the vocabulary."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    num_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    shared_expert_intermediate_size: int
    router_width: int | None = None
    first_expert: int = 0
    linear_conv_kernel_dim: int = 4
    full_attention_interval: int = 4
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    logprob_chunk: int = 1024       # positions a head product at a time

    def __post_init__(self):
        for name, want in (("tie_word_embeddings", False),
                           ("hidden_act", "silu"), ("decoder_sparse_step", 1),
                           ("mlp_only_layers", ())):
            if getattr(self, name) != want:
                raise ValueError(f"{name} = {getattr(self, name)!r} is not "
                                 f"supported (the published value is "
                                 f"{want!r}: every layer has its experts)")
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(f"{self.linear_num_value_heads} value heads on "
                             f"{self.linear_num_key_heads} key heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor "
                             f"{self.partial_rotary_factor} of head_dim "
                             f"{self.head_dim}: no even rotary width")

    @property
    def n_routed_experts(self) -> int:
        """The experts held here (the name the load readers use)."""
        return self.num_experts

    @property
    def routed_width(self) -> int:
        return (self.num_experts if self.router_width is None
                else self.router_width)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def per(self) -> int:
        """Value heads a key head serves."""
        return self.linear_num_value_heads // self.linear_num_key_heads

    @property
    def key_group(self) -> int:
        """A key head's columns of ``in_proj_qkvz``: ``q``, ``k``, ``v``,
        ``z``."""
        return 2 * self.linear_key_head_dim \
            + 2 * self.per * self.linear_value_head_dim

    @property
    def conv_group(self) -> int:
        """A key head's convolved channels: ``q``, ``k``, ``v``."""
        return 2 * self.linear_key_head_dim \
            + self.per * self.linear_value_head_dim

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer, in order."""
        return tuple("attention" if (i + 1) % self.full_attention_interval
                     == 0 else "gdn" for i in range(self.num_hidden_layers))


def rms0(x, w, eps: float):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``, float32."""
    return rms_norm(x, 1.0 + w, eps)


def grouped_taps(taps, c: Qwen3NextConfig):
    """The convolution's taps ``[K, q | k | v]`` (every head's ``q``, then
    ``k``, then ``v``: the public layout) in the key-head order the
    convolution reads its channels in."""
    hk, dk = c.linear_num_key_heads, c.linear_key_head_dim
    k = taps.shape[0]
    q, kk, v = (taps[:, :hk * dk], taps[:, hk * dk:2 * hk * dk],
                taps[:, 2 * hk * dk:])
    return jnp.concatenate([a.reshape(k, hk, -1) for a in (q, kk, v)],
                           axis=-1).reshape(k, -1)


def split_qkvz(w, c: Qwen3NextConfig):
    """``in_proj_qkvz`` ``[d, key heads x (q | k | v | z)]`` as two
    matrices: ``[q_j | k_j | v_j]`` of every key head ``j`` side by side
    (the columns the convolution takes, still grouped by key head) and every
    ``z`` (the value heads' gates, in value-head order)."""
    by_head = w.reshape(w.shape[0], c.linear_num_key_heads, c.key_group)
    return (by_head[..., :c.conv_group].reshape(w.shape[0], -1),
            by_head[..., c.conv_group:].reshape(w.shape[0], -1))


def gdn_mixer(p: dict, x, c: Qwen3NextConfig):
    """The Gated DeltaNet mixer on normed ``x`` ``[B, L, d]``; float32
    out."""
    b, n, _ = x.shape
    hk, hv, dv = (c.linear_num_key_heads, c.linear_num_value_heads,
                  c.linear_value_head_dim)
    with jax.named_scope("lm/gdn/in"):
        # two products, not one sliced: the gates' columns of a fused
        # product are a strided third of it that the chip would copy out
        # whole
        w_qkv, w_z = split_qkvz(p["in_proj_qkvz"], c)
        qkv, z = _dot(x, w_qkv, c.dtype), _dot(x, w_z, c.dtype)
        ba = _dot(x, p["in_proj_ba"], c.dtype).reshape(b, n, hk, 2, c.per)
    with jax.named_scope("lm/gdn/conv"):
        qkv = causal_conv(qkv, grouped_taps(p["conv_taps"], c),
                          channels=hk * c.conv_group, silu=True,
                          dtype=c.dtype)
    with jax.named_scope("lm/gdn/recurrence"):
        beta = jax.nn.sigmoid(ba[..., 0, :]).reshape(b, n, hv)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            ba[..., 1, :].reshape(b, n, hv) + p["dt_bias"])
        o = gated_delta(qkv, g, beta, key_heads=hk,
                        key_dim=c.linear_key_head_dim, value_heads=hv,
                        value_dim=dv)
    with jax.named_scope("lm/gdn/norm"):
        # a value head a row of [B L hv, dv]: the head's channels on lanes
        u = rms_norm(o.reshape(-1, dv), p["norm"], c.rms_norm_eps) \
            * jax.nn.silu(z.reshape(-1, dv))
    with jax.named_scope("lm/gdn/out"):
        return _dot(u.reshape(b, n, hv * dv), p["out_proj"], c.dtype)


def gated_attention(p: dict, x, c: Qwen3NextConfig):
    """Causal attention of ``num_attention_heads`` query heads on
    ``num_key_value_heads`` key/value heads on normed ``x`` ``[B, L, d]``,
    each head's output gated by ``sigmoid`` of its second half of the query
    projection; zero-centred q/k norms; rotary on the first ``rotary_dim``
    dims. Float32 out."""
    b, n, _ = x.shape
    h, hkv, hd, rot = (c.num_attention_heads, c.num_key_value_heads,
                       c.head_dim, c.rotary_dim)
    with jax.named_scope("lm/gqa/proj"):
        qg = _dot(x, p["q"], c.dtype).reshape(b, n, h, 2 * hd)
        q = rms0(qg[..., :hd], p["q_norm"], c.rms_norm_eps)
        k = rms0(_dot(x, p["k"], c.dtype).reshape(b, n, hkv, hd),
                 p["k_norm"], c.rms_norm_eps)
        v = _dot(x, p["v"], c.dtype).reshape(b, n, hkv, hd)
    with jax.named_scope("lm/gqa/rope"):
        cos, sin = rope_tables(jnp.arange(n), rot, c.rope_theta)

        def rotary(a):
            return jnp.concatenate([apply_rope_half(a[..., :rot], cos, sin),
                                    a[..., rot:]], axis=-1)
        q, k = rotary(q), rotary(k)

    def bhtd(a):
        return a.astype(c.dtype).transpose(0, 2, 1, 3)

    with jax.named_scope("lm/gqa/core"):
        o = flash_attention(bhtd(q), bhtd(k), bhtd(v), causal=True,
                            scale=hd ** -0.5)
    with jax.named_scope("lm/gqa/gate"):
        o = o.transpose(0, 2, 1, 3) * jax.nn.sigmoid(qg[..., hd:])
    return _dot(o.reshape(b, n, h * hd), p["o"], c.dtype)


def expert_layer(router: dict, routed: dict, shared: dict, layer, x,
                 c: Qwen3NextConfig):
    """Expert layer ``layer`` (an index into the ``routed`` stacks, which
    may be traced) on normed ``x`` ``[B, L, d]``: ``(y, load [B, held],
    bucket)``; ``shared`` empty leaves the gated shared expert out."""
    b, n, d = x.shape
    flat = x.reshape(b * n, d).astype(c.dtype)
    with jax.named_scope("lm/moe/experts"):
        y, picks, bucket = moe_dropless(
            flat, router["kernel"], routed, top_k=c.num_experts_per_tok,
            first_expert=c.first_expert, norm_topk=c.norm_topk_prob,
            layer=layer)
    with jax.named_scope("lm/moe/route"):
        local = picks.reshape(b, -1) - c.first_expert
        load = jnp.sum(jax.nn.one_hot(local, c.num_experts,
                                      dtype=jnp.int32), axis=1)
    if shared:
        with jax.named_scope("lm/moe/shared"):
            gate = jax.nn.sigmoid(_dot(flat, shared["expert_gate"], c.dtype))
            y = y + gate * gated_mlp(shared, flat, c.dtype)
    return y.reshape(b, n, d), load, bucket


class Qwen3NextLM(nn.Module):
    """See the module docstring; build one with
    :func:`mmlspark_tpu.models.lm.from_config`."""

    cfg: Qwen3NextConfig

    OUTPUT_NAMES = ("features", "expert_load", "moe_bucket", "token_logprob",
                    "logits")

    def _stacks(self) -> dict:
        """Declare the by-kind stacks, ``kind -> {leaf: [layers, ...]}`` (a
        kind no layer has is left empty), and say what was built."""
        c = self.cfg
        d, hd, layers = c.hidden_size, c.head_dim, c.num_hidden_layers
        count = {kind: c.kinds.count(kind) for kind in LAYER_KINDS}
        reg = _obs_registry()
        for kind in LAYER_KINDS:
            reg.gauge("lm.layers", kind=kind).set(count[kind])
        reg.gauge("lm.layers", kind="moe").set(layers)

        def kernel(name, *shape):
            return (name, shape, _fan_in_normal, c.param_dtype)

        def f32(name, init, *shape):
            return (name, shape, init, jnp.float32)

        def stack(name, n, *leaves):
            return KindStack(n, leaves, name=name)() if n else {}

        hk, hv = c.linear_num_key_heads, c.linear_num_value_heads
        dv = c.linear_value_head_dim
        fs = c.shared_expert_intermediate_size
        return {
            "gdn": stack(
                "gdn", count["gdn"],
                kernel("in_proj_qkvz", d, hk * c.key_group),
                kernel("in_proj_ba", d, 2 * hv),
                kernel("conv_taps", c.linear_conv_kernel_dim,
                       hk * c.conv_group),
                f32("dt_bias", _dt_bias(*DT_RANGE), hv),
                f32("A_log", _a_log, hv), f32("norm", _near_one, dv),
                kernel("out_proj", hv * dv, d)),
            "attn": stack(
                "attn", count["attention"],
                kernel("q", d, 2 * c.num_attention_heads * hd),
                kernel("k", d, c.num_key_value_heads * hd),
                kernel("v", d, c.num_key_value_heads * hd),
                kernel("o", c.num_attention_heads * hd, d),
                f32("q_norm", _zero_centred, hd),
                f32("k_norm", _zero_centred, hd)),
            "router": stack(
                "router", layers,
                ("kernel", (d, c.routed_width), _fan_in_normal,
                 jnp.float32)),
            "routed": ExpertStacks(layers, c.num_experts, d,
                                   c.moe_intermediate_size, c.param_dtype,
                                   name="routed")(),
            "shared": stack(
                "shared", layers, kernel("gate", d, fs), kernel("up", d, fs),
                kernel("down", fs, d), kernel("expert_gate", d, 1)),
            **stack("norms", layers, f32("input_norm", _zero_centred, d),
                    f32("post_norm", _zero_centred, d)),
        }

    def _run(self, stacks: dict, h):
        """Every layer, as one scan whose body picks its mixer and then runs
        the expert layer; returns the hidden state and ``load [layers, B,
        held]`` and ``bucket [layers]``."""
        c = self.cfg
        kinds = c.kinds
        present = [k for k in LAYER_KINDS if k in kinds]
        # each layer's place among the layers of its own kind
        index = np.asarray([kinds[:i].count(k) for i, k in enumerate(kinds)],
                           np.int32)
        branch = np.asarray([present.index(k) for k in kinds], np.int32)
        eps = c.rms_norm_eps

        def block(h, xs):
            layer, which, at = xs
            x = rms0(h, _at(stacks["input_norm"], layer), eps)

            def gdn():
                with jax.named_scope("lm/gdn"):
                    return gdn_mixer(_at(stacks["gdn"], at), x, c)

            def attention():
                with jax.named_scope("lm/gqa"):
                    return gated_attention(_at(stacks["attn"], at), x, c)

            mixers = {"gdn": gdn, "attention": attention}
            branches = [mixers[k] for k in present]
            mixed = (branches[0]() if len(branches) == 1
                     else jax.lax.switch(which, branches))
            h = (h.astype(jnp.float32) + mixed).astype(c.dtype)
            y, load, bucket = expert_layer(
                _at(stacks["router"], layer), stacks["routed"],
                _at(stacks["shared"], layer), layer,
                rms0(h, _at(stacks["post_norm"], layer), eps), c)
            return (h.astype(jnp.float32) + y).astype(c.dtype), (load, bucket)

        return jax.lax.scan(block, h, (jnp.arange(len(kinds)),
                                       jnp.asarray(branch),
                                       jnp.asarray(index)))

    @nn.compact
    def __call__(self, x, output: str = "logits"):
        if output not in self.OUTPUT_NAMES:
            raise ValueError(f"unknown output node {output!r}; available: "
                             f"{self.OUTPUT_NAMES}")
        c = self.cfg
        tokens = x.astype(jnp.int32)
        b = tokens.shape[0]
        table = nn.Embed(c.vocab_size, c.hidden_size,
                         param_dtype=c.param_dtype,
                         embedding_init=jax.nn.initializers.normal(1.0),
                         name="embed").embedding
        h = jnp.take(table, tokens, axis=0).astype(c.dtype)
        h, (load, bucket) = self._run(self._stacks(), h)
        if output == "expert_load":
            # [layers, B, held] -> a row's picks on each held expert
            return load.transpose(1, 0, 2).reshape(b, -1).astype(jnp.float32)
        if output == "moe_bucket":
            return jnp.broadcast_to(bucket[None, :], (b, bucket.shape[0]))
        h = rms0(h, self.param("norm_f", _zero_centred, (c.hidden_size,),
                               jnp.float32), c.rms_norm_eps)
        if output == "features":
            return jnp.mean(h, axis=1)
        head = Kernel((c.hidden_size, c.vocab_size), c.param_dtype,
                      name="head")().astype(c.dtype)
        h = h.astype(c.dtype)
        with jax.named_scope("lm/head"):
            if output == "logits":
                return jnp.dot(h, head, preferred_element_type=jnp.float32)
            return token_logprob(h, head, tokens, c.logprob_chunk)
