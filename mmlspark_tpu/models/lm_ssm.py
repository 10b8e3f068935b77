"""JambaLM — a causal language model whose mixers are selective state-space
(Mamba) layers with a few multi-query attention layers among them, a dense
gated-SiLU MLP in every layer, scored as a table column.

The family of ``model_type: jamba`` configurations (AI21-Jamba2-3B), built
from the configuration's own key names (``docs/lm.md`` has the equations):

* pre-norm blocks ``h = x + Mixer(rms(x))`` (``input_layernorm``), ``y = h +
  MLP(rms(h))`` (``pre_ff_layernorm``), a last RMSNorm and the head, which
  is the embedding (``tie_word_embeddings``); layer ``i`` is attention when
  ``i % attn_layer_period == attn_layer_offset``, a Mamba layer otherwise;
* **Mamba mixer**: ``[u | z] = x W_in``; a depthwise causal convolution of
  ``mamba_d_conv`` taps WITH a bias, then SiLU; ``[dt | B | C] = c W_x``
  with an RMSNorm on each of the three (the family's departure from
  Mamba-1); ``delta = softplus(dt W_dt + b_dt)``; the selective scan
  (:func:`~mmlspark_tpu.ops.pallas.selective_scan.selective_scan`: state
  ``[d_inner, mamba_d_state]`` a row, carried in VMEM over chunks of the
  sequence) with the skip ``D`` and the gate ``silu(z)`` fused in; the
  out-projection;
* **attention**: ``num_attention_heads`` query heads on
  ``num_key_value_heads`` key/value heads (20 on ONE in the published
  model) and NO positional term of any kind, no q/k norm:
  :func:`~mmlspark_tpu.models.lm_conv.grouped_attention` handed no
  positions and a tree without norm leaves (the Mamba layers carry
  position);
* ``num_experts`` must be 1 (every feed-forward layer the dense MLP): a
  configuration with routed experts is refused, not approximated.

**The parameter tree is by kind** (as :mod:`~mmlspark_tpu.models.lm_conv`):
``mamba/*`` stacks the Mamba layers in layer order, ``attn/*`` the
attention layers, ``dense/*`` and ``norms/*`` every layer. **The stack runs
as ONE ``lax.scan`` over all layers** whose body picks the mixer by
``lax.cond`` on the layer's kind (static per-layer arrays of kind and
index-within-kind ride along as the scan's inputs; the by-kind stacks are
indexed inside the branch): any layer order, one body to compile, and each
kernel ONE instruction of the program whose device time sums all its
layers.

Input and dtypes as :class:`~mmlspark_tpu.models.lm.LatentMoELM`; output
nodes ``features``, ``token_logprob``, ``logits`` (there is no router, so no
``expert_load`` / ``moe_bucket``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mmlspark_tpu.models.lm import (
    _fan_in_normal, _near_one, rms_norm, token_logprob,
)
from mmlspark_tpu.models.lm_conv import (
    KindStack, _at, _dot, gated_mlp, grouped_attention,
)
from mmlspark_tpu.obs.metrics import registry as _obs_registry
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv
from mmlspark_tpu.ops.pallas.selective_scan import selective_scan

LAYER_KINDS = ("mamba", "attention")
# the step sizes a fresh Mamba layer starts from (Gu & Dao, arXiv:2312.00752,
# section 3.6): log-uniform, so that a channel forgets over 10 to 1,000+
# positions
DT_INIT = (1e-3, 1e-1)


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    """The sizes of one :class:`JambaLM`, under the configuration's own key
    names."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    attn_layer_period: int
    attn_layer_offset: int
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_experts: int = 1
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    logprob_chunk: int = 1024       # positions a head product at a time

    def __post_init__(self):
        if self.num_experts > 1:
            raise ValueError(
                f"num_experts = {self.num_experts}: this family is built "
                "with a dense MLP in every layer (num_experts 1); routed "
                "experts are not approximated")
        for name, want in (("mamba_proj_bias", False),
                           ("tie_word_embeddings", True),
                           ("hidden_act", "silu")):
            if getattr(self, name) != want:
                raise ValueError(f"{name} = {getattr(self, name)!r} is not "
                                 f"supported (the published value is "
                                 f"{want!r})")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def kinds(self) -> tuple:
        """The mixer of every layer, in order."""
        return tuple(
            "attention"
            if i % self.attn_layer_period == self.attn_layer_offset
            else "mamba" for i in range(self.num_hidden_layers))


def _a_log(key, shape, dtype):
    """``log(1..N)`` along the state axis (the published initialisation)
    and a little noise, so that no two channels decay alike."""
    base = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
    return (base + 0.1 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def _dt_bias(key, shape, dtype):
    """``softplus^-1`` of step sizes log-uniform over ``DT_INIT``."""
    lo, hi = (math.log(v) for v in DT_INIT)
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def mamba_mixer(p: dict, x, c: JambaConfig):
    """The Mamba mixer on normed ``x`` ``[B, L, d]``; float32 out."""
    d_i, n, r = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    eps = c.rms_norm_eps
    with jax.named_scope("lm/mamba/in"):
        uz = _dot(x, p["in_proj"], c.dtype)
    with jax.named_scope("lm/mamba/conv"):
        # both halves of [u | z] read where they lie, in one pass
        conv, gate = causal_conv(
            uz, p["conv_taps"], channels=d_i, cast_at=d_i, silu=True,
            bias=p["conv_bias"] if c.mamba_conv_bias else None, dtype=c.dtype)
    with jax.named_scope("lm/mamba/params"):
        dbc = _dot(conv, p["x_proj"], c.dtype)
        dt = rms_norm(dbc[..., :r], p["dt_norm"], eps)
        b = rms_norm(dbc[..., r:r + n], p["b_norm"], eps)
        cc = rms_norm(dbc[..., r + n:], p["c_norm"], eps)
        delta = jax.nn.softplus(_dot(dt, p["dt_proj"], c.dtype)
                                + p["dt_bias"])
    with jax.named_scope("lm/mamba/scan"):
        y = selective_scan(conv, delta, -jnp.exp(p["A_log"]), b, cc, p["D"],
                           gate)
    with jax.named_scope("lm/mamba/out"):
        return _dot(y, p["out_proj"], c.dtype)


class JambaLM(nn.Module):
    """See the module docstring; build one with
    :func:`mmlspark_tpu.models.lm.from_config`."""

    cfg: JambaConfig

    OUTPUT_NAMES = ("features", "token_logprob", "logits")

    def _stacks(self) -> dict:
        """Declare the by-kind stacks, ``kind -> {leaf: [layers, ...]}`` (a
        kind no layer has is left empty), and say what was built."""
        c = self.cfg
        d, d_i, hd = c.hidden_size, c.d_inner, c.head_dim
        n, r = c.mamba_d_state, c.mamba_dt_rank
        count = {kind: c.kinds.count(kind) for kind in LAYER_KINDS}
        for kind in LAYER_KINDS:
            _obs_registry().gauge("lm.layers", kind=kind).set(count[kind])

        def kernel(name, *shape):
            return (name, shape, _fan_in_normal, c.param_dtype)

        def f32(name, init, *shape):
            return (name, shape, init, jnp.float32)

        def stack(name, layers, *leaves):
            return KindStack(layers, leaves, name=name)() if layers else {}

        q_width = c.num_attention_heads * hd
        kv_width = c.num_key_value_heads * hd
        stacks = {
            "mamba": stack(
                "mamba", count["mamba"], kernel("in_proj", d, 2 * d_i),
                kernel("conv_taps", c.mamba_d_conv, d_i),
                f32("conv_bias", jax.nn.initializers.normal(0.1), d_i),
                kernel("x_proj", d_i, r + 2 * n),
                f32("dt_norm", _near_one, r), f32("b_norm", _near_one, n),
                f32("c_norm", _near_one, n), kernel("dt_proj", r, d_i),
                f32("dt_bias", _dt_bias, d_i), f32("A_log", _a_log, d_i, n),
                f32("D", _near_one, d_i), kernel("out_proj", d_i, d)),
            "attn": stack(
                "attn", count["attention"], kernel("q", d, q_width),
                kernel("k", d, kv_width), kernel("v", d, kv_width),
                kernel("o", q_width, d)),
            "dense": stack(
                "dense", c.num_hidden_layers,
                kernel("gate", d, c.intermediate_size),
                kernel("up", d, c.intermediate_size),
                kernel("down", c.intermediate_size, d)),
        }
        norms = stack("norms", c.num_hidden_layers,
                      f32("input_layernorm", _near_one, d),
                      f32("pre_ff_layernorm", _near_one, d))
        return {**stacks, **norms}

    def _run(self, stacks: dict, h):
        """Every layer, as one scan whose body picks its mixer."""
        c = self.cfg
        kinds = c.kinds
        # each layer's place among the layers of its own kind
        index = np.asarray([kinds[:i].count(k) for i, k in enumerate(kinds)],
                           np.int32)
        attention = np.asarray([k == "attention" for k in kinds])

        def block(h, xs):
            layer, is_attention, at = xs
            x = rms_norm(h, _at(stacks["input_layernorm"], layer),
                         c.rms_norm_eps)

            def mamba():
                with jax.named_scope("lm/mamba"):
                    return mamba_mixer(_at(stacks["mamba"], at), x, c)

            def mqa():
                with jax.named_scope("lm/mqa"):
                    return grouped_attention(_at(stacks["attn"], at), x,
                                             None, c)

            if attention.all():
                mixed = mqa()
            elif not attention.any():
                mixed = mamba()
            else:
                mixed = jax.lax.cond(is_attention, mqa, mamba)
            h = (h.astype(jnp.float32) + mixed).astype(c.dtype)
            x = rms_norm(h, _at(stacks["pre_ff_layernorm"], layer),
                         c.rms_norm_eps)
            with jax.named_scope("lm/dense"):
                y = gated_mlp(_at(stacks["dense"], layer), x, c.dtype)
            return (h.astype(jnp.float32) + y).astype(c.dtype), None

        h, _ = jax.lax.scan(
            block, h, (jnp.arange(len(kinds)), jnp.asarray(attention),
                       jnp.asarray(index)))
        return h

    @nn.compact
    def __call__(self, x, output: str = "logits"):
        if output not in self.OUTPUT_NAMES:
            raise ValueError(f"unknown output node {output!r}; available: "
                             f"{self.OUTPUT_NAMES}")
        c = self.cfg
        tokens = x.astype(jnp.int32)
        # as the head the table is a fan-in-normal [d, V] matrix; the first
        # input_layernorm rescales the rows it hands out as embeddings
        table = nn.Embed(
            c.vocab_size, c.hidden_size, param_dtype=c.param_dtype,
            embedding_init=jax.nn.initializers.normal(c.hidden_size ** -0.5),
            name="embed").embedding
        h = jnp.take(table, tokens, axis=0).astype(c.dtype)
        h = self._run(self._stacks(), h)
        h = rms_norm(h, self.param("final_layernorm", _near_one,
                                   (c.hidden_size,), jnp.float32),
                     c.rms_norm_eps)
        if output == "features":
            return jnp.mean(h, axis=1)
        head = table.astype(c.dtype).T
        h = h.astype(c.dtype)
        with jax.named_scope("lm/head"):
            if output == "logits":
                return jnp.dot(h, head, preferred_element_type=jnp.float32)
            return token_logprob(h, head, tokens, c.logprob_chunk)
