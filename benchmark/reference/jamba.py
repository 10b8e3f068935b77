"""Plain reference for ``model_type: jamba`` (AI21-Jamba2-3B): selective
state-space (Mamba) layers with a few multi-query attention layers among
them and a dense gated-SiLU MLP in every layer, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No kernels, no
scan over layers, no cache; the recurrence is a ``lax.scan`` over positions
(no chunk, so no carry between chunks to get wrong); the one key/value head
is met by every query head, a head at a time.

The equations, from the configuration's own keys (``x`` a token's state,
``t`` its position; ``d = hidden_size``, ``d_i = mamba_expand * d``, ``N =
mamba_d_state``, ``R = mamba_dt_rank``, ``K = mamba_d_conv``):

* layer ``i`` is attention when ``i % attn_layer_period ==
  attn_layer_offset``, a Mamba layer otherwise; ``num_experts`` is 1, so
  every feed-forward part is the dense MLP;
* block ``i``: ``h = x + Mixer_i(rms(x))`` (``input_layernorm``), ``y = h +
  MLP(rms(h))`` (``pre_ff_layernorm``), ``MLP(u) = (silu(u W_gate) * (u
  W_up)) W_down``; RMSNorm with ``rms_norm_eps`` and a learned scale; after
  the last block an RMSNorm (``final_layernorm``) and logits ``h E^T`` with
  the embedding ``E`` (``tie_word_embeddings``); no biases but the two below;
* Mamba mixer: ``[u | z] = x W_in``; ``c_t = silu(b_conv + sum_j w_j * u_{t
  - (K - 1) + j})`` (depthwise, ``u`` zero before the row's start);
  ``[dt | B | C] = c W_x`` (widths ``R``, ``N``, ``N``); ``dt <- rms(dt)``,
  ``B <- rms(B)``, ``C <- rms(C)``, each with its own learned scale (the
  family's departure from Mamba-1, which has none of the three); ``delta =
  softplus(dt W_dt + b_dt)``; ``A = -exp(A_log)``; ``s_t = exp(delta_t (x)
  A) * s_{t-1} + (delta_t * c_t) (x) B_t`` with ``s_{-1} = 0``; ``y_t = s_t
  C_t + D * c_t``; ``Mixer(x) = (y * silu(z)) W_out``;
* attention: ``q = x W_q`` as ``num_attention_heads`` heads of ``d /
  num_attention_heads``, ``k = x W_k``, ``v = x W_v`` as
  ``num_key_value_heads`` heads; query head ``h`` meets key/value head ``h
  // (heads / kv heads)``; causal softmax of ``q.k / sqrt(head)``; ``o
  W_o``. **No rotary or other positional term, no q/k norm.**

``assumed`` (the configuration file lists them): the head width, the layer
order rule, the precision, and the seeded stand-ins for the checkpoint.

Weights come from :func:`layer_leaf` (one layer's) and :func:`outer_leaf`
(the embedding, the last norm, and every stack of the program's tree, which
holds its layers by kind: ``mamba/*``, ``attn/*``, ``dense/*``,
``norms/*``), one leaf at a time from the seed's key. The forward runs a
layer at a time so that neither side ever holds the tree in float32.
``quant`` rounds both operands of every matrix product to that type (the
control; ``"bfloat16"`` rounds the residual stream too: the witness of
``reference/lfm2.py``); ``fault`` plants ``state_dropped``: the state is
zeroed every ``DROP_EVERY`` positions, which is what a chunked kernel that
loses its carry computes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.lfm2 import (
    _dot, _wide, gated, rms_norm, rounder, token_logprob,
)
from benchmark.reference.rounding import round_to

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("state_dropped",)
DROP_EVERY = 256
# the step sizes the stand-in bias starts the channels from: log-uniform
DT_RANGE = (1e-3, 1e-1)


# ---- the layers' kinds ----

def layer_kind(cfg: dict, i: int) -> str:
    return ("attention"
            if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "mamba")


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


# ---- the seed's weights ----

def kind_shapes(cfg: dict) -> dict:
    """``kind -> {leaf: shape}`` of one layer's weights of each kind; the
    names are the program's stacks'."""
    d, d_i, hd = cfg["hidden_size"], d_inner(cfg), head_dim(cfg)
    n, r, f = (cfg["mamba_d_state"], cfg["mamba_dt_rank"],
               cfg["intermediate_size"])
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "every": {"norms/input_layernorm": (d,),
                  "norms/pre_ff_layernorm": (d,),
                  "dense/gate": (d, f), "dense/up": (d, f),
                  "dense/down": (f, d)},
        "mamba": {"mamba/in_proj": (d, 2 * d_i),
                  "mamba/conv_taps": (cfg["mamba_d_conv"], d_i),
                  "mamba/conv_bias": (d_i,),
                  "mamba/x_proj": (d_i, r + 2 * n),
                  "mamba/dt_norm": (r,), "mamba/b_norm": (n,),
                  "mamba/c_norm": (n,), "mamba/dt_proj": (r, d_i),
                  "mamba/dt_bias": (d_i,), "mamba/A_log": (d_i, n),
                  "mamba/D": (d_i,), "mamba/out_proj": (d_i, d)},
        "attention": {"attn/q": (d, hq * hd), "attn/k": (d, hkv * hd),
                      "attn/v": (d, hkv * hd), "attn/o": (hq * hd, d)},
    }


def layer_paths(cfg: dict) -> dict:
    """``leaf -> (kind, shape)`` over every kind."""
    return {path: (kind, shape) for kind, leaves in kind_shapes(cfg).items()
            for path, shape in leaves.items()}


def outer_shapes(cfg: dict) -> dict:
    return {"embed/embedding": (cfg["vocab_size"], cfg["hidden_size"]),
            "final_layernorm": (cfg["hidden_size"],)}


def _leaf(cfg: dict, key, path: str, shape: tuple):
    """One leaf from its own key: matrices (the conv's taps among them)
    normal(0, 1 / fan_in); the tied embedding normal(0, 1 / hidden_size);
    norm scales and ``D`` 1 + normal(0, 0.1^2); ``conv_bias`` normal(0,
    0.1^2); ``A_log`` log(1..N) along the state axis + normal(0, 0.1^2);
    ``dt_bias`` the inverse softplus of step sizes log-uniform over
    ``DT_RANGE``, so that a channel forgets over 10 to 1,000+ positions;
    all values are what ``param_dtype`` holds exactly."""
    name = path.rsplit("/", 1)[-1]
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return round_to(dt + jnp.log(-jnp.expm1(-dt)), cfg["param_dtype"])
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm") or name == "D":
        v = 1.0 + 0.1 * z
    elif name == "conv_bias":
        v = 0.1 * z
    elif name == "A_log":
        v = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)) + 0.1 * z
    elif name == "embedding":
        v = z * (1.0 / math.sqrt(shape[-1]))
    else:
        v = z * (1.0 / math.sqrt(shape[-2]))
    return round_to(v, cfg["param_dtype"])


def layer_leaf(cfg: dict, key, layer, path: str):
    """Leaf ``path`` of layer ``layer`` (which may be traced)."""
    paths = layer_paths(cfg)
    k = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    k = jax.random.fold_in(k, list(paths).index(path))
    return _leaf(cfg, k, path, paths[path][1])


def layers_of(cfg: dict, kind: str) -> list:
    """The layers that hold weights of ``kind``, in order."""
    return [i for i in range(cfg["num_hidden_layers"])
            if kind in ("every", layer_kind(cfg, i))]


def outer_leaf(cfg: dict, key, path: str):
    """A leaf of the program's tree that is no single layer's: the
    embedding, the last norm, or the stack ``path`` of all layers of its
    kind, in layer order, held in ``param_dtype`` (which holds every value
    exactly)."""
    if path in outer_shapes(cfg):
        shapes = outer_shapes(cfg)
        k = jax.random.fold_in(jax.random.fold_in(key, 2),
                               list(shapes).index(path))
        return _leaf(cfg, k, path, shapes[path])
    kind = layer_paths(cfg)[path][0]
    store = jnp.dtype(cfg["param_dtype"])
    return jax.lax.map(
        lambda i: layer_leaf(cfg, key, i, path).astype(store),
        jnp.asarray(layers_of(cfg, kind), jnp.int32))


def make_layer_params(cfg: dict, key, layer, kind: str) -> dict:
    """The float32 weights of layer ``layer`` (which may be traced), whose
    mixer is ``kind``."""
    shapes = kind_shapes(cfg)
    return {p: layer_leaf(cfg, key, layer, p)
            for k in ("every", kind) for p in shapes[k]}


def make_params(cfg: dict, key) -> dict:
    """The whole tree, float32: ``{"outer": {...}, "layers": [{...}]}``.
    For small sizes; at the cell's size take a layer at a time."""
    return {"outer": {p: outer_leaf(cfg, key, p) for p in outer_shapes(cfg)},
            "layers": [make_layer_params(cfg, key, i, layer_kind(cfg, i))
                       for i in range(cfg["num_hidden_layers"])]}


# ---- the forward ----

def conv_silu(p: dict, u, cfg: dict):
    """``silu(b + sum_j w_j * u_{t - (K - 1) + j})`` of one row ``[L,
    d_i]``, an explicit sum over shifted copies."""
    taps = p["mamba/conv_taps"]
    last = taps.shape[0] - 1
    mixed = jnp.zeros_like(u) + p["mamba/conv_bias"]
    for j in range(last + 1):
        back = last - j                 # tap j meets the value `back` before
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[:u.shape[0] - back]],
            axis=0)
        mixed = mixed + taps[j] * shifted
    return jax.nn.silu(mixed)


def recurrence(delta, c, a, b_t, c_t, fault: str | None = None):
    """``y [L, d_i]`` of one row: the state ``[d_i, N]`` a position at a
    time; ``state_dropped`` zeroes it before every ``DROP_EVERY``-th
    position."""
    def step(s, at):
        t, d, x, bb, cc = at
        if fault == "state_dropped":
            s = jnp.where(t % DROP_EVERY == 0, 0.0, s)
        s = jnp.exp(d[:, None] * a) * s + (d * x)[:, None] * bb[None, :]
        return s, jnp.sum(s * cc[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                        (jnp.arange(delta.shape[0]), delta, c, b_t, c_t))
    return y


def mamba_mixer(p: dict, x, cfg: dict, quant: str | None = None,
                fault: str | None = None):
    """The Mamba mixer of one row: ``x`` ``[L, d]`` (already normed)."""
    q = rounder(quant)
    d_i, n, r = d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    eps = cfg["rms_norm_eps"]
    uz = _dot(x, p["mamba/in_proj"], q)
    c = conv_silu(p, uz[:, :d_i], cfg)
    dbc = _dot(c, p["mamba/x_proj"], q)
    dt = rms_norm(dbc[:, :r], p["mamba/dt_norm"], eps)
    b_t = rms_norm(dbc[:, r:r + n], p["mamba/b_norm"], eps)
    c_t = rms_norm(dbc[:, r + n:], p["mamba/c_norm"], eps)
    delta = jax.nn.softplus(_dot(dt, p["mamba/dt_proj"], q)
                            + p["mamba/dt_bias"])
    y = recurrence(delta, c, -jnp.exp(p["mamba/A_log"]), b_t, c_t, fault)
    y = (y + p["mamba/D"] * c) * jax.nn.silu(uz[:, d_i:])
    return _dot(y, p["mamba/out_proj"], q)


def attention(p: dict, x, cfg: dict, quant: str | None = None):
    """Multi-query (grouped) attention of one row, no positional term: ``x``
    ``[L, d]`` (already normed); a query head at a time (its ``[L, L]``
    scores are 1 GB at the cell's window)."""
    q = rounder(quant)
    hd = head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = x.shape[0]
    qh = q(_dot(x, p["attn/q"], q).reshape(n, h, hd))
    kh = q(_dot(x, p["attn/k"], q).reshape(n, hkv, hd))
    vh = q(_dot(x, p["attn/v"], q).reshape(n, hkv, hd))
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]

    def one_head(i):
        kv = i // (h // hkv)
        scores = jnp.dot(qh[:, i], kh[:, kv].T, precision=HIGHEST) \
            * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.dot(q(w), vh[:, kv], precision=HIGHEST)

    out = jax.lax.map(one_head, jnp.arange(h))           # [h, L, hd]
    return _dot(out.transpose(1, 0, 2).reshape(n, h * hd), p["attn/o"], q)


def layer(p: dict, x, cfg: dict, kind: str, quant: str | None = None,
          fault: str | None = None):
    """One block whose mixer is ``kind`` on one row ``[L, d]``."""
    eps = cfg["rms_norm_eps"]
    # a program in a wide ``quant`` holds the residual stream in it too
    stream = rounder(quant) if _wide(quant) else (lambda a: a)
    normed = rms_norm(x, p["norms/input_layernorm"], eps)
    if kind == "mamba":
        h = stream(x + mamba_mixer(p, normed, cfg, quant, fault))
    else:
        h = stream(x + attention(p, normed, cfg, quant))
    normed = rms_norm(h, p["norms/pre_ff_layernorm"], eps)
    return stream(h + gated(normed, p["dense/gate"], p["dense/up"],
                            p["dense/down"], rounder(quant)))


def head_logits(outer: dict, x, cfg: dict, quant: str | None = None):
    x = rms_norm(x, outer["final_layernorm"], cfg["rms_norm_eps"])
    return _dot(x, outer["embed/embedding"].T, rounder(quant))


def forward(params: dict, tokens, cfg: dict, quant: str | None = None,
            fault: str | None = None) -> dict:
    """One row of token ids ``[L]`` through a whole tree of
    :func:`make_params`: ``features``, ``logits``, ``token_logprob``."""
    x = params["outer"]["embed/embedding"][tokens]
    for i, p in enumerate(params["layers"]):
        x = layer(p, x, cfg, layer_kind(cfg, i), quant, fault)
    logits = head_logits(params["outer"], x, cfg, quant)
    feats = jnp.mean(rms_norm(x, params["outer"]["final_layernorm"],
                              cfg["rms_norm_eps"]), axis=0)
    return {"features": feats, "logits": logits,
            "token_logprob": token_logprob(logits, tokens)}


def score_rows(cfg: dict, key, rows, quant: str | None = None,
               fault: str | None = None) -> np.ndarray:
    """``token_logprob [N, L]`` of the token rows ``[N, L]``, a layer at a
    time: one layer's float32 weights are made, every row goes through it,
    and they are dropped before the next is made."""
    rows = np.asarray(rows).astype(np.int32)
    n = rows.shape[0]
    with jax.default_matmul_precision("highest"):
        outer = {k: jax.jit(lambda kk, k=k: outer_leaf(cfg, kk, k))(key)
                 for k in outer_shapes(cfg)}
        xs = [outer["embed/embedding"][row] for row in rows]
        kinds = {layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])}
        make = {kind: jax.jit(lambda k, i, kind=kind: make_layer_params(
            cfg, k, i, kind)) for kind in kinds}
        step = {kind: jax.jit(lambda p, x, kind=kind: layer(
            p, x, cfg, kind, quant, fault)) for kind in kinds}
        for i in range(cfg["num_hidden_layers"]):
            kind = layer_kind(cfg, i)
            p = make[kind](key, i)
            for r in range(n):
                xs[r] = step[kind](p, xs[r])
            del p
        tail = jax.jit(lambda o, x, t: token_logprob(
            head_logits(o, x, cfg, quant), t))
        return np.stack([np.asarray(tail(outer, xs[r], rows[r]))
                         for r in range(n)])
