"""LatentMoELM — a causal language model with latent attention and a
dropless top-k expert layer, scored as a table column.

The family of ``model_type: mistral4`` / DeepSeek-V3-style configurations,
built from the configuration's own key names (``docs/lm.md``):

* pre-norm blocks ``h = x + MLA(rms(x))``, ``y = h + MoE(rms(h))`` with
  RMSNorm (statistics in float32), a final RMSNorm and an untied head;
* **latent attention**: low-rank query (``q_lora_rank``) and key/value
  (``kv_lora_rank``) projections with an RMSNorm on each latent, heads of
  ``[nope | rope]`` query/key parts and ``v_head_dim`` values, one rope key
  shared by all heads, interleaved RoPE with YaRN frequencies, and the
  position scale ``1 + beta ln(1 + floor(p / original_max))`` on queries.
  The attention core is :func:`~mmlspark_tpu.ops.pallas.attention.
  flash_attention` (tiled over queries at long windows, only the causal
  triangle's tile pairs run; ``[B, H, L, L]`` never exists);
* **experts**: softmax router over ``router_width`` in float32, top-k
  renormalised, gated-SiLU experts plus shared experts. The module holds
  the experts ``[first_expert, first_expert + n_routed_experts)`` — one
  chip's share of an expert-parallel deployment — and
  :func:`~mmlspark_tpu.parallel.moe.moe_dropless` computes their part of
  the result for the tokens routed to them, dropping none;
* the layers run as ONE ``lax.scan`` over stacked layer weights
  (``nn.scan``): compile time does not grow with depth, and each kernel is
  one named operation in a device trace.

Input: a ``[B, L]`` batch of token ids (float32 as a table column ships
them — exact below 2^24 — or any integer type). Output nodes:

* ``features``: the final-norm hidden state averaged over the row;
* ``expert_load``: ``[B, layers * held]``, the row's picks that landed on
  each held expert of each layer (what :func:`publish_expert_load` sums);
* ``moe_bucket``: ``[B, layers]`` int32, the rung of the expert layer's
  row-count ladder that the row's step took in each layer (0 = the
  smallest; what :func:`publish_bucket_steps` counts);
* ``token_logprob``: ``[B, L]`` float32, ``out[0] = 0``, ``out[t] = log
  softmax(logits[t-1])[token[t]]`` over the held vocabulary, computed in
  sequence chunks so that ``[L, V]`` never exists whole;
* ``logits``: ``[B, L, V]`` float32 (small windows only).

Weights are stored in ``param_dtype`` and matrix products run on
``dtype`` operands with float32 accumulation; router, norms, softmax and
the log-likelihood are float32.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.attention import flash_attention
from mmlspark_tpu.parallel.moe import moe_dropless


def _fan_in_normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            / math.sqrt(shape[-2])).astype(dtype)


def _near_one(key, shape, dtype):
    return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def rms_norm(x, scale, eps: float):
    """``x / rms(x) * scale`` over the last axis, statistics in float32."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", _near_one, (x.shape[-1],), jnp.float32)
        return rms_norm(x, scale, self.eps)


class Linear(nn.Module):
    """A bias-free product on ``dtype`` operands, float32 out."""

    features: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        w = self.param("kernel", _fan_in_normal,
                       (x.shape[-1], self.features), self.param_dtype)
        return jnp.dot(x.astype(self.dtype), w.astype(self.dtype),
                       preferred_element_type=jnp.float32)


class Kernel(nn.Module):
    shape: tuple
    param_dtype: Any

    @nn.compact
    def __call__(self):
        return self.param("kernel", _fan_in_normal, self.shape,
                          self.param_dtype)


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """The sizes of one :class:`LatentMoELM`, under the configuration's
    own key names. ``n_routed_experts`` counts the experts HELD here
    (``first_expert`` on) and ``router_width`` the experts the router
    scores (the published count; ``None``: every routed expert is held);
    ``vocab_size`` is the held slice of the vocabulary."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    moe_intermediate_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    rope_parameters: tuple          # the configuration's dict, as items
    router_width: int | None = None
    first_expert: int = 0
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    logprob_chunk: int = 1024       # positions a head product at a time

    @property
    def rope(self) -> dict:
        return dict(self.rope_parameters)

    @property
    def routed_width(self) -> int:
        return (self.n_routed_experts if self.router_width is None
                else self.router_width)


def yarn_inv_freq(dim: int, rope: dict) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN: below ``low`` the
    plain ones, above ``high`` those divided by ``factor``, a linear ramp
    between (``low``/``high`` from ``beta_fast``/``beta_slow`` rotations
    over ``original_max_position_embeddings``)."""
    base, orig = rope["rope_theta"], rope["original_max_position_embeddings"]
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def correction(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(rope["beta_fast"])), 0)
    high = min(math.ceil(correction(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return freq / rope["factor"] * ramp + freq * (1 - ramp)


def _yarn_mscale(m: float, factor: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if m else 1.0


def rope_tables(positions, dim: int, rope: dict) -> tuple:
    """``(cos, sin)`` ``[L, dim / 2]`` float32 for interleaved pairs."""
    angle = positions.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_inv_freq(dim, rope), jnp.float32)[None, :]
    factor = (_yarn_mscale(rope["mscale"], rope["factor"])
              / _yarn_mscale(rope["mscale_all_dim"], rope["factor"]))
    return jnp.cos(angle) * factor, jnp.sin(angle) * factor


def apply_rope_interleaved(x, cos, sin):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis; ``x``
    ``[B, L, ..., dim]`` float32, tables ``[L, dim / 2]``."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    shape = (1, cos.shape[0]) + (1,) * (x.ndim - 3) + (cos.shape[1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) over whole causal windows."""

    cfg: LMConfig

    def softmax_scale(self) -> float:
        c, rope = self.cfg, self.cfg.rope
        m = _yarn_mscale(rope["mscale_all_dim"], rope["factor"])
        return (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * m * m

    @nn.compact
    def __call__(self, x, positions):
        c, rope_cfg = self.cfg, self.cfg.rope
        b, n, d = x.shape
        h, nope, rope = c.num_attention_heads, c.qk_nope_head_dim, \
            c.qk_rope_head_dim

        def lin(features, name):
            return Linear(features, c.dtype, c.param_dtype, name=name)

        c_q = RMSNorm(c.rms_norm_eps, name="q_a_norm")(
            lin(c.q_lora_rank, "q_a")(x))
        q = lin(h * (nope + rope), "q_b")(c_q).reshape(b, n, h, nope + rope)
        kv_a = lin(c.kv_lora_rank + rope, "kv_a")(x)
        c_kv = RMSNorm(c.rms_norm_eps, name="kv_a_norm")(
            kv_a[..., :c.kv_lora_rank])
        kv = lin(h * (nope + c.v_head_dim), "kv_b")(c_kv).reshape(
            b, n, h, nope + c.v_head_dim)
        cos, sin = rope_tables(positions, rope, rope_cfg)
        k_rope = apply_rope_interleaved(kv_a[..., c.kv_lora_rank:],
                                        cos, sin)              # [B, L, rope]
        q_rope = apply_rope_interleaved(q[..., nope:], cos, sin)
        steps = jnp.floor(positions.astype(jnp.float32)
                          / rope_cfg["original_max_position_embeddings"])
        q_scale = 1.0 + rope_cfg["llama_4_scaling_beta"] * jnp.log1p(steps)
        q = jnp.concatenate([q[..., :nope], q_rope], -1) \
            * q_scale[None, :, None, None]
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (b, n, h, rope))], -1)
        v = kv[..., nope:]

        def bhtd(a):
            return a.astype(c.dtype).transpose(0, 2, 1, 3)

        with jax.named_scope("lm/mla/attention"):
            o = flash_attention(bhtd(q), bhtd(k), bhtd(v), causal=True,
                                scale=self.softmax_scale())
        o = o.transpose(0, 2, 1, 3).reshape(b, n, h * c.v_head_dim)
        return lin(d, "o")(o)


class ExpertStacks(nn.Module):
    """The held routed experts' weights of ALL layers, ``[layers, held, ...]``
    stacks that ``moe_dropless`` reads in place (a scan over them would
    copy a layer's 1.6 GB out of the stack every step: a kernel's operand
    cannot be a slice). ``gated``: whether the experts have a ``gate``
    stack beside ``up`` and ``down`` (``moe_dropless`` takes the expert's
    form from the stacks it is handed)."""

    layers: int
    held: int
    hidden: int
    width: int
    param_dtype: Any
    gated: bool = True

    @nn.compact
    def __call__(self) -> dict:
        def stack(name, rows, cols):
            return self.param(name, _fan_in_normal,
                              (self.layers, self.held, rows, cols),
                              self.param_dtype)
        shapes = {"gate": (self.hidden, self.width),
                  "up": (self.hidden, self.width),
                  "down": (self.width, self.hidden)}
        return {name: stack(name, *shape) for name, shape in shapes.items()
                if self.gated or name != "gate"}


class SharedExpert(nn.Module):
    width: int
    dtype: Any
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        def w(name, shape):
            return self.param(name, _fan_in_normal, shape,
                              self.param_dtype).astype(self.dtype)
        d = x.shape[-1]
        x = x.astype(self.dtype)
        gate = jnp.dot(x, w("gate", (d, self.width)),
                       preferred_element_type=jnp.float32)
        up = jnp.dot(x, w("up", (d, self.width)),
                     preferred_element_type=jnp.float32)
        return jnp.dot((jax.nn.silu(gate) * up).astype(self.dtype),
                       w("down", (self.width, d)),
                       preferred_element_type=jnp.float32)


class MoE(nn.Module):
    """The block's expert layer: its router (``router/kernel``) and shared
    expert (``shared/*``), and layer ``layer`` of the model's
    :class:`ExpertStacks`."""

    cfg: LMConfig

    @nn.compact
    def __call__(self, x, layer, experts):
        c = self.cfg
        b, n, d = x.shape
        flat = x.reshape(b * n, d).astype(c.dtype)
        router = Kernel((d, c.routed_width), jnp.float32, name="router")()
        with jax.named_scope("lm/moe/experts"):
            routed, picks, bucket = moe_dropless(
                flat, router, experts, top_k=c.num_experts_per_tok,
                first_expert=c.first_expert, norm_topk=c.norm_topk_prob,
                scaling=c.routed_scaling_factor, layer=layer)
        with jax.named_scope("lm/moe/route"):
            local = picks.reshape(b, -1) - c.first_expert
            load = jnp.sum(jax.nn.one_hot(local, c.n_routed_experts,
                                          dtype=jnp.int32), axis=1)
        # the shared expert on the flat tokens too: a reshape between the
        # routed part and this sum is moved into the expert layer's branches
        # by the compiler, where it keeps the combine's float32 converts
        # out of its fusion (four [N, d] float32 buffers a step)
        y = routed
        if c.n_shared_experts:
            with jax.named_scope("lm/moe/shared"):
                y = y + SharedExpert(
                    c.n_shared_experts * c.moe_intermediate_size, c.dtype,
                    c.param_dtype, name="shared")(flat)
        return y.reshape(b, n, d), load, bucket


class Block(nn.Module):
    cfg: LMConfig

    @nn.compact
    def __call__(self, x, positions, layer, experts):
        c = self.cfg
        with jax.named_scope("lm/mla"):
            h = x.astype(jnp.float32) + LatentAttention(c, name="mla")(
                RMSNorm(c.rms_norm_eps, name="input_norm")(x), positions)
        h = h.astype(c.dtype)
        y, load, bucket = MoE(c, name="moe")(
            RMSNorm(c.rms_norm_eps, name="post_norm")(h), layer, experts)
        self.sow("intermediates", "moe_load", load)
        return (h.astype(jnp.float32) + y).astype(c.dtype), (load, bucket)


class LatentMoELM(nn.Module):
    """See the module docstring; build one with :func:`from_config`."""

    cfg: LMConfig

    OUTPUT_NAMES = ("features", "expert_load", "moe_bucket", "token_logprob",
                    "logits")

    @nn.compact
    def __call__(self, x, output: str = "logits"):
        c = self.cfg
        tokens, _, h = lm_input(self, x, output, 1.0)
        n = tokens.shape[1]
        experts = ExpertStacks(
            c.num_hidden_layers, c.n_routed_experts, c.hidden_size,
            c.moe_intermediate_size, c.param_dtype, name="experts")()
        layers = nn.scan(
            Block, variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            in_axes=(nn.broadcast, 0, nn.broadcast),
            length=c.num_hidden_layers)
        h, stats = layers(c, name="layers")(
            h, jnp.arange(n), jnp.arange(c.num_hidden_layers), experts)
        return readout(c, output, tokens, h, stats,
                       RMSNorm(c.rms_norm_eps, name="final_norm"))


def lm_input(module, x, output: str, std: float) -> tuple:
    """The head every family's ``__call__`` starts with: refuse an
    ``output`` node ``module`` does not have, before anything runs; then the
    ids ``x`` as int32 ``tokens``, the table ``embed/embedding`` ``[V, d]``
    (drawn normal with ``std``) and the tokens' rows in it on ``dtype``."""
    if output not in module.OUTPUT_NAMES:
        raise ValueError(f"unknown output node {output!r}; available: "
                         f"{module.OUTPUT_NAMES}")
    c = module.cfg
    tokens = x.astype(jnp.int32)
    table = nn.Embed(c.vocab_size, c.hidden_size, param_dtype=c.param_dtype,
                     embedding_init=jax.nn.initializers.normal(std),
                     name="embed").embedding
    return tokens, table, jnp.take(table, tokens, axis=0).astype(c.dtype)


def readout(c, output: str, tokens, h, stats, norm, table=None):
    """The tail every family's ``__call__`` ends in, from the stack's hidden
    state ``h`` ``[B, L, d]``: ``expert_load`` / ``moe_bucket`` from the
    expert layers' ``stats = (load [layers, B, held], bucket [layers])``;
    else ``norm(h)`` (the family's final norm) and ``features``; else the
    head ``[d, V]`` (the embedding ``table`` where it is tied, a
    ``head/kernel`` of its own where ``table`` is ``None``) and ``logits`` /
    ``token_logprob`` under ``lm/head``."""
    b = tokens.shape[0]
    if output == "expert_load":
        # [layers, B, held] -> a row's picks on each held expert
        return stats[0].transpose(1, 0, 2).reshape(b, -1).astype(jnp.float32)
    if output == "moe_bucket":
        # [layers] -> the step's rung on each of its rows
        return jnp.broadcast_to(stats[1][None, :], (b, stats[1].shape[0]))
    h = norm(h)
    if output == "features":
        return jnp.mean(h, axis=1)
    head = (table.astype(c.dtype).T if table is not None
            else Kernel((c.hidden_size, c.vocab_size), c.param_dtype,
                        name="head")().astype(c.dtype))
    h = h.astype(c.dtype)
    with jax.named_scope("lm/head"):
        if output == "logits":
            return jnp.dot(h, head, preferred_element_type=jnp.float32)
        return token_logprob(h, head, tokens, c.logprob_chunk)


def token_logprob(h, head, tokens, chunk: int):
    """``[B, L]`` float32: 0 at position 0, then the log-probability the
    model at ``t - 1`` gave token ``t``. The head product, its
    log-sum-exp and the pick run ``chunk`` positions at a time (the whole
    window at once where ``chunk`` does not divide it)."""
    b, n, d = h.shape
    if n % chunk:
        chunk = n
    nxt = jnp.roll(tokens, -1, axis=1)      # the last target is not used

    def one(args):
        hc, tc = args                       # [B, chunk, d], [B, chunk]
        logits = jnp.dot(hc, head, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        return picked - jax.nn.logsumexp(logits, axis=-1)

    def chunks(a):
        return jnp.moveaxis(a.reshape((b, n // chunk, chunk) + a.shape[2:]),
                            1, 0)

    lp = jnp.moveaxis(jax.lax.map(one, (chunks(h), chunks(nxt))), 0, 1)
    lp = lp.reshape(b, n)
    return jnp.concatenate([jnp.zeros((b, 1), jnp.float32), lp[:, :-1]], 1)


def config_fields(cls, cfg: dict, **overrides) -> dict:
    """The fields of the config dataclass ``cls`` that the configuration
    dict ``cfg`` gives under their own names, ``compute_dtype`` /
    ``param_dtype`` as dtypes, lists as tuples; ``overrides`` last."""
    names = {f.name for f in dataclasses.fields(cls)}
    kw = {k: tuple(v) if isinstance(v, list) else v for k, v in cfg.items()
          if k in names and k not in ("dtype", "param_dtype")
          and not isinstance(v, dict)}
    kw["dtype"] = jnp.dtype(cfg.get("compute_dtype", "bfloat16"))
    kw["param_dtype"] = jnp.dtype(cfg.get("param_dtype", "bfloat16"))
    kw.update(overrides)
    return kw


# ``model_type`` -> the family's module, its module class and its config
# class; a type that is not here is built as a LatentMoELM (``mistral4`` /
# DeepSeek-V3-style keys)
FAMILIES = {"lfm2_moe": ("lm_conv", "ConvMoELM", "ConvLMConfig"),
            "jamba": ("lm_ssm", "JambaLM", "JambaConfig"),
            "nemotron_h": ("lm_mamba2", "NemotronHLM", "NemotronHConfig"),
            "qwen3_next": ("lm_gdn", "Qwen3NextLM", "Qwen3NextConfig")}


def from_config(cfg: dict, **overrides) -> nn.Module:
    """The module of a configuration dict under the published key names
    (plus ``compute_dtype`` / ``param_dtype``), by its ``model_type``
    (:data:`FAMILIES`): ``lfm2_moe`` gives a :class:`~mmlspark_tpu.models.
    lm_conv.ConvMoELM`, ``jamba`` a :class:`~mmlspark_tpu.models.lm_ssm.
    JambaLM`, ``nemotron_h`` a :class:`~mmlspark_tpu.models.lm_mamba2.
    NemotronHLM` (``router_width`` / ``first_expert`` for a share),
    ``qwen3_next`` a :class:`~mmlspark_tpu.models.lm_gdn.Qwen3NextLM` (the
    same two keys, ``num_experts`` the held count), anything else a
    :class:`LatentMoELM` (the same two keys for a share);
    ``overrides`` are further fields of the module's config."""
    if cfg.get("model_type") not in FAMILIES:
        rope = tuple(sorted(cfg["rope_parameters"].items()))
        return LatentMoELM(LMConfig(**config_fields(
            LMConfig, cfg, **{"rope_parameters": rope, **overrides})))
    name, module, config = FAMILIES[cfg["model_type"]]
    family = importlib.import_module(f"mmlspark_tpu.models.{name}")
    config = getattr(family, config)
    return getattr(family, module)(config(**config_fields(config, cfg,
                                                          **overrides)))


def publish_expert_load(load, tokens: int) -> dict:
    """Publish routed-load counts the host has learned — ``load``
    ``[layers, held]``, the picks on each held expert of each layer (the
    ``expert_load`` node's column summed over rows), of ``tokens`` token x
    layer routings — as the counters ``moe.tokens`` and ``moe.held_pairs``
    (picks that landed on a held expert) and the gauge
    ``moe.expert_load_max`` (the busiest held expert's picks in one layer)
    of ``obs.registry()``; returns the same three numbers."""
    load = np.asarray(load, np.float64)
    out = {"moe.tokens": int(tokens), "moe.held_pairs": int(load.sum()),
           "moe.expert_load_max": int(load.max())}
    reg = _obs_registry()
    reg.counter("moe.tokens").add(out["moe.tokens"])
    reg.counter("moe.held_pairs").add(out["moe.held_pairs"])
    reg.gauge("moe.expert_load_max").set(out["moe.expert_load_max"])
    return out


def publish_bucket_steps(bucket, rows_per_step: int) -> dict:
    """Publish which rungs the expert layer's steps took — ``bucket``
    ``[rows, layers]``, the ``moe_bucket`` node's column over a table
    scored ``rows_per_step`` rows a step (every row of a step carries the
    step's rung) — as the counters ``moe.bucket_steps`` (layer-steps run)
    and ``moe.bucket_steps_first`` (those that took the smallest rung) of
    ``obs.registry()``; returns the same two numbers."""
    steps = np.asarray(bucket)[::rows_per_step]
    out = {"moe.bucket_steps": int(steps.size),
           "moe.bucket_steps_first": int((steps == 0).sum())}
    reg = _obs_registry()
    for name, value in out.items():
        reg.counter(name).add(value)
    return out
