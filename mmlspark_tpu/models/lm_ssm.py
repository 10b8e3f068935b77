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
  :func:`~mmlspark_tpu.models.lm_layers.grouped_attention` handed no
  positions and a tree without norm leaves (the Mamba layers carry
  position);
* ``num_experts`` must be 1 (every feed-forward layer the dense MLP): a
  configuration with routed experts is refused, not approximated.

**The parameter tree is by kind** (as :mod:`~mmlspark_tpu.models.lm_conv`):
``mamba/*`` stacks the Mamba layers in layer order, ``attn/*`` the
attention layers, ``dense/*`` and ``norms/*`` every layer. **The stack runs
as ONE ``lax.scan`` over all layers**
(:func:`~mmlspark_tpu.models.lm_layers.run_layers`: the mixer by the
layer's kind, then the MLP): any layer order, one body to compile, and each
kernel ONE instruction of the program whose device time sums all its
layers.

Input and dtypes as :class:`~mmlspark_tpu.models.lm.LatentMoELM`; output
nodes ``features``, ``token_logprob``, ``logits`` (there is no router, so no
``expert_load`` / ``moe_bucket``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from mmlspark_tpu.models.lm import _near_one, lm_input, readout, rms_norm
from mmlspark_tpu.models.lm_layers import (
    _at, _dot, _dt_bias, attention_leaves, gated_mlp, grouped_attention,
    layer_counts, leaf_makers, run_layers,
)
from mmlspark_tpu.ops.pallas.causal_conv import causal_conv
from mmlspark_tpu.ops.pallas.selective_scan import selective_scan

LAYER_KINDS = ("mamba", "attention")


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
        d, d_i = c.hidden_size, c.d_inner
        n, r = c.mamba_d_state, c.mamba_dt_rank
        count = layer_counts(c.kinds, LAYER_KINDS)
        kernel, f32, stack = leaf_makers(c.param_dtype)
        return {
            "mamba": stack(
                "mamba", count["mamba"], kernel("in_proj", d, 2 * d_i),
                kernel("conv_taps", c.mamba_d_conv, d_i),
                f32("conv_bias", jax.nn.initializers.normal(0.1), d_i),
                kernel("x_proj", d_i, r + 2 * n),
                f32("dt_norm", _near_one, r), f32("b_norm", _near_one, n),
                f32("c_norm", _near_one, n), kernel("dt_proj", r, d_i),
                f32("dt_bias", _dt_bias(), d_i),
                f32("A_log", _a_log, d_i, n),
                f32("D", _near_one, d_i), kernel("out_proj", d_i, d)),
            "attn": stack("attn", count["attention"],
                          *attention_leaves(c, kernel)),
            "dense": stack(
                "dense", c.num_hidden_layers,
                kernel("gate", d, c.intermediate_size),
                kernel("up", d, c.intermediate_size),
                kernel("down", c.intermediate_size, d)),
            **stack("norms", c.num_hidden_layers,
                    f32("input_layernorm", _near_one, d),
                    f32("pre_ff_layernorm", _near_one, d)),
        }

    @nn.compact
    def __call__(self, x, output: str = "logits"):
        c = self.cfg
        eps = c.rms_norm_eps
        # as the head the table is a fan-in-normal [d, V] matrix; the first
        # input_layernorm rescales the rows it hands out as embeddings
        tokens, table, h = lm_input(self, x, output, c.hidden_size ** -0.5)
        stacks = self._stacks()

        def mamba(at, x):
            with jax.named_scope("lm/mamba"):
                return mamba_mixer(_at(stacks["mamba"], at), x, c)

        def mqa(at, x):
            with jax.named_scope("lm/mqa"):
                return grouped_attention(_at(stacks["attn"], at), x, None, c)

        def mlp(h, layer):
            x = rms_norm(h, _at(stacks["pre_ff_layernorm"], layer), eps)
            with jax.named_scope("lm/dense"):
                return gated_mlp(_at(stacks["dense"], layer), x, c.dtype)

        h, _ = run_layers(
            h, c.kinds,
            lambda h, i: rms_norm(h, _at(stacks["input_layernorm"], i), eps),
            {"mamba": mamba, "attention": mqa}, c.dtype, after=mlp)
        return readout(
            c, output, tokens, h, None,
            lambda h: rms_norm(h, self.param(
                "final_layernorm", _near_one, (c.hidden_size,), jnp.float32),
                eps), table)
