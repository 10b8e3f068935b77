"""NemotronHLM — a causal language model whose every layer is ONE mixer:
a Mamba-2 (state-space dual) layer, a dropless top-k layer of UNGATED
squared-ReLU experts, or grouped-query attention, scored as a table column.

The family of ``model_type: nemotron_h`` configurations
(NVIDIA-Nemotron-3-Nano-30B-A3B), built from the configuration's own key
names (``docs/lm.md`` has the equations):

* block ``i``: ``h = x + Mixer_i(rms(x) * w_i)`` and nothing else (no
  feed-forward half); the mixer by character ``i`` of
  ``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` experts, ``*``
  attention. ``-`` (a dense squared-ReLU MLP) occurs nowhere in the
  published pattern and is refused, not approximated. Then ``norm_f`` and an
  UNTIED head;
* **Mamba-2** (``M``): ``[z | xBC | dt] = x W_in`` (``d_i`` | ``d_i + 2 G
  N`` | ``H``, with ``d_i = mamba_num_heads x mamba_head_dim``, NOT ``expand
  x hidden_size``); a depthwise causal convolution of ``conv_kernel`` taps
  with a bias, then SiLU, over all of ``xBC``
  (:func:`~mmlspark_tpu.ops.pallas.causal_conv.causal_conv`, read where it
  lies in the wide product); ``dt = softplus(dt + dt_bias)``, one step size
  a head; the recurrence with one scalar decay a head a position and ``B``
  / ``C`` shared by the heads of a group
  (:func:`~mmlspark_tpu.ops.pallas.ssd_scan.ssd_scan`: chunked matrix
  products, the state in VMEM, the skip ``D`` inside); the gate ``y *
  silu(z)`` and THEN an RMSNorm within each of ``n_groups`` groups of
  channels with a learned scale; the out-projection;
* **experts** (``E``): a sigmoid router in float32 over ``router_width``;
  the picks are the ``num_experts_per_tok`` largest of ``score + bias`` (one
  group: ``n_group`` = ``topk_group`` = 1), their weights the unbiased
  scores over ``(their sum + 1e-20)`` times ``routed_scaling_factor``;
  expert ``e(x) = relu(x U_e)^2 D_e``, no gate, no bias
  (:func:`~mmlspark_tpu.parallel.moe.moe_dropless` handed stacks without a
  ``gate``: two grouped products); one shared expert of the same form at
  ``moe_shared_expert_intermediate_size``. The module holds the experts
  ``[first_expert, first_expert + n_routed_experts)`` — one chip's share of
  an expert-parallel deployment — and a pick of an absent expert adds
  nothing here;
* **attention** (``*``): ``num_attention_heads`` query heads of
  ``head_dim`` (a key of its own: 128, not ``hidden_size / heads``) on
  ``num_key_value_heads`` key/value heads, NO positional term
  (:func:`~mmlspark_tpu.models.lm_layers.grouped_attention` handed no
  positions and a tree without norm leaves; the Mamba layers carry
  position).

**The parameter tree is by kind**: ``mamba2/*`` stacks the Mamba-2 layers in
layer order, ``attn/*`` the attention layers, ``router/*``, ``routed/*``
(``[expert layers, held, ...]``, read in place) and ``shared/*`` the expert
layers, ``norms/norm`` every layer; ``embed``, ``head``, ``norm_f``. **The
stack runs as ONE ``lax.scan`` over all layers**
(:func:`~mmlspark_tpu.models.lm_layers.run_layers`: the mixer by the
layer's kind): any layer order, one body to trace, and each kernel ONE
instruction of the program whose device time sums all its layers.

Input, output nodes and dtypes as :class:`~mmlspark_tpu.models.lm.
LatentMoELM`: ``features``, ``expert_load`` (``[B, expert layers *
held]``), ``moe_bucket``, ``token_logprob``, ``logits``.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.models.lm import (
    ExpertStacks, _fan_in_normal, _near_one, lm_input, readout, rms_norm,
)
from mmlspark_tpu.models.lm_layers import (
    _a_log, _at, _dot, _dt_bias, attention_leaves, grouped_attention,
    layer_counts, leaf_makers, run_layers,
)
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv
from mmlspark_tpu.ops.pallas.ssd_scan import ssd_scan
from mmlspark_tpu.parallel.moe import _activation, moe_dropless

# the pattern's characters and the kinds they stand for (``-``, a dense
# MLP, is refused: no published pattern holds one)
PATTERN = {"M": "mamba2", "E": "moe", "*": "attention"}
LAYER_KINDS = tuple(PATTERN.values())
# the guard in the normalisation of the picked weights (the family's public
# implementation; no key of the configuration states it)
NORM_TOPK_EPS = 1e-20


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """The sizes of one :class:`NemotronHLM`, under the configuration's own
    key names. ``n_routed_experts`` counts the experts HELD here
    (``first_expert`` on) and ``router_width`` the experts the router scores
    (the published count; ``None``: every routed expert is held);
    ``vocab_size`` is the held slice of the vocabulary."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    hybrid_override_pattern: str
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_shared_expert_intermediate_size: int
    router_width: int | None = None
    first_expert: int = 0
    n_shared_experts: int = 1
    conv_kernel: int = 4
    use_conv_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    mlp_hidden_act: str = "relu2"
    mamba_hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    use_bias: bool = False
    time_step_min: float = 1e-3
    time_step_max: float = 1e-1
    time_step_floor: float = 1e-4
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    logprob_chunk: int = 1024       # positions a head product at a time

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers:
            raise ValueError(f"{len(pattern)} characters in "
                             f"hybrid_override_pattern for "
                             f"{self.num_hidden_layers} layers")
        unknown = sorted(set(pattern) - set(PATTERN))
        if unknown:
            raise ValueError(
                f"hybrid_override_pattern holds {unknown}; built are "
                f"{sorted(PATTERN)} ('-', a dense MLP layer, occurs in no "
                "published pattern and is not approximated)")
        for name, want in (
                ("tie_word_embeddings", False), ("mlp_hidden_act", "relu2"),
                ("mamba_hidden_act", "silu"), ("mamba_proj_bias", False),
                ("attention_bias", False), ("mlp_bias", False),
                ("use_bias", False), ("n_group", 1), ("topk_group", 1)):
            if getattr(self, name) != want:
                raise ValueError(f"{name} = {getattr(self, name)!r} is not "
                                 f"supported (the published value is "
                                 f"{want!r})")
        if self.n_shared_experts not in (0, 1):
            raise ValueError(f"n_shared_experts = {self.n_shared_experts}: "
                             "one shared expert or none")
        if self.mamba_num_heads % self.n_groups:
            raise ValueError(f"{self.mamba_num_heads} Mamba heads in "
                             f"{self.n_groups} groups")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The convolved channels: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def routed_width(self) -> int:
        return (self.n_routed_experts if self.router_width is None
                else self.router_width)

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer, in order."""
        return tuple(PATTERN[ch] for ch in self.hybrid_override_pattern)


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``rms(y * silu(z))`` within each of ``groups`` groups of the last
    axis, times ``scale``: the gate first, then the norm; float32."""
    u = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = u.reshape(u.shape[:-1] + (groups, -1))
    var = jnp.mean(grouped * grouped, axis=-1, keepdims=True)
    return (grouped * jax.lax.rsqrt(var + eps)).reshape(u.shape) * scale


def mamba2_mixer(p: dict, x, c: NemotronHConfig):
    """The Mamba-2 mixer on normed ``x`` ``[B, L, d]``; float32 out."""
    d_i, heads = c.d_inner, c.mamba_num_heads
    with jax.named_scope("lm/mamba2/in"):
        zxd = _dot(x, p["in_proj"], c.dtype)        # [z | xBC | dt]
    with jax.named_scope("lm/mamba2/conv"):
        xbc = causal_conv(
            zxd, p["conv_taps"], channels=c.conv_dim, at=d_i, silu=True,
            bias=p["conv_bias"] if c.use_conv_bias else None, dtype=c.dtype)
    with jax.named_scope("lm/mamba2/ssd"):
        dt = jax.nn.softplus(zxd[..., d_i + c.conv_dim:] + p["dt_bias"])
        y = ssd_scan(xbc, dt, -jnp.exp(p["A_log"]), p["D"], heads=heads,
                     head_dim=c.mamba_head_dim, groups=c.n_groups,
                     state=c.ssm_state_size)
    with jax.named_scope("lm/mamba2/norm"):
        u = gated_group_norm(y, zxd[..., :d_i], p["norm"], c.n_groups,
                             c.layer_norm_epsilon)
    with jax.named_scope("lm/mamba2/out"):
        return _dot(u, p["out_proj"], c.dtype)


def ungated_mlp(p: dict, x, act: str, dtype):
    """``act(x W_up) W_down`` (``relu2``: ``relu(.)^2``), float32 out."""
    return _dot(_activation(act)(_dot(x, p["up"], dtype)), p["down"], dtype)


def expert_layer(router: dict, routed: dict, shared: dict, layer, x,
                 c: NemotronHConfig):
    """Expert layer ``layer`` (an index into the ``routed`` stacks, which
    may be traced) on normed ``x`` ``[B, L, d]``: ``(y, load [B, held],
    bucket)``."""
    b, n, d = x.shape
    flat = x.reshape(b * n, d).astype(c.dtype)
    with jax.named_scope("lm/moe/experts"):
        y, picks, bucket = moe_dropless(
            flat, router["kernel"], routed, top_k=c.num_experts_per_tok,
            first_expert=c.first_expert, norm_topk=c.norm_topk_prob,
            scaling=c.routed_scaling_factor, layer=layer, score="sigmoid",
            bias=router["bias"], norm_eps=NORM_TOPK_EPS,
            activation=c.mlp_hidden_act)
    with jax.named_scope("lm/moe/route"):
        local = picks.reshape(b, -1) - c.first_expert
        load = jnp.sum(jax.nn.one_hot(local, c.n_routed_experts,
                                      dtype=jnp.int32), axis=1)
    if shared:
        with jax.named_scope("lm/moe/shared"):
            y = y + ungated_mlp(shared, flat, c.mlp_hidden_act, c.dtype)
    return y.reshape(b, n, d), load, bucket


class NemotronHLM(nn.Module):
    """See the module docstring; build one with
    :func:`mmlspark_tpu.models.lm.from_config`."""

    cfg: NemotronHConfig

    OUTPUT_NAMES = ("features", "expert_load", "moe_bucket", "token_logprob",
                    "logits")

    def _stacks(self) -> dict:
        """Declare the by-kind stacks, ``kind -> {leaf: [layers, ...]}`` (a
        kind no layer has is left empty), and say what was built."""
        c = self.cfg
        d, d_i = c.hidden_size, c.d_inner
        count = layer_counts(c.kinds, LAYER_KINDS)
        kernel, f32, stack = leaf_makers(c.param_dtype)
        shared = c.n_shared_experts * c.moe_shared_expert_intermediate_size
        return {
            "mamba2": stack(
                "mamba2", count["mamba2"],
                kernel("in_proj", d, d_i + c.conv_dim + c.mamba_num_heads),
                kernel("conv_taps", c.conv_kernel, c.conv_dim),
                f32("conv_bias", jax.nn.initializers.normal(0.1), c.conv_dim),
                f32("dt_bias", _dt_bias(c.time_step_min, c.time_step_max,
                                        c.time_step_floor),
                    c.mamba_num_heads),
                f32("A_log", _a_log, c.mamba_num_heads),
                f32("D", _near_one, c.mamba_num_heads),
                f32("norm", _near_one, d_i), kernel("out_proj", d_i, d)),
            "attn": stack("attn", count["attention"],
                          *attention_leaves(c, kernel)),
            # ``bias`` is a buffer of the checkpoint (the load balancer's
            # running correction), not a trained weight: it moves the picks
            # only; the stand-in is drawn so that it moves some
            "router": stack(
                "router", count["moe"],
                f32("kernel", _fan_in_normal, d, c.routed_width),
                f32("bias", jax.nn.initializers.normal(0.05), c.routed_width)),
            "routed": ExpertStacks(
                count["moe"], c.n_routed_experts, d, c.moe_intermediate_size,
                c.param_dtype, gated=False, name="routed")()
            if count["moe"] else {},
            "shared": stack(
                "shared", count["moe"] if shared else 0,
                kernel("up", d, shared), kernel("down", shared, d)),
            **stack("norms", c.num_hidden_layers, f32("norm", _near_one, d)),
        }

    @nn.compact
    def __call__(self, x, output: str = "logits"):
        c = self.cfg
        tokens, _, h = lm_input(self, x, output, 1.0)
        stacks = self._stacks()

        def mamba2(at, x):
            with jax.named_scope("lm/mamba2"):
                return mamba2_mixer(_at(stacks["mamba2"], at), x, c)

        def moe(at, x):
            return expert_layer(_at(stacks["router"], at), stacks["routed"],
                                _at(stacks["shared"], at), at, x, c)

        def attention(at, x):
            with jax.named_scope("lm/gqa"):
                return grouped_attention(_at(stacks["attn"], at), x, None, c)

        h, (load, bucket) = run_layers(
            h, c.kinds, lambda h, i: rms_norm(h, _at(stacks["norm"], i),
                                              c.layer_norm_epsilon),
            {"mamba2": mamba2, "moe": moe, "attention": attention}, c.dtype,
            no_stats=(jnp.zeros((h.shape[0], c.n_routed_experts), jnp.int32),
                      jnp.zeros((), jnp.int32)))
        experts = np.flatnonzero(np.asarray(c.kinds) == "moe")
        return readout(
            c, output, tokens, h, (load[experts], bucket[experts]),
            lambda h: rms_norm(h, self.param(
                "norm_f", _near_one, (c.hidden_size,), jnp.float32),
                c.layer_norm_epsilon))
