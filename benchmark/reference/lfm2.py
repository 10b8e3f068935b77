"""Plain reference for ``model_type: lfm2_moe`` (LFM2-8B-A1B): gated short
convolutions among grouped-query attention layers, dense feed-forward
layers first and top-k expert layers after, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No kernels, no
scan over layers, no cache; keys and values repeated to the query heads'
count; the convolution an explicit sum over shifted copies; experts by a
plain loop, every token through each.

The equations, from the configuration's own keys (``x`` a token's state,
``t`` its position; ``d = hidden_size``):

* block ``i``: ``h = x + Op_i(rms(x))`` (``operator_norm``), ``y = h +
  FF_i(rms(h))`` (``ffn_norm``); RMSNorm with ``norm_eps`` and a learned
  scale; after the last block an RMSNorm (``embedding_norm``) and logits
  ``h E^T`` with the embedding ``E`` (tied);
* ``Op_i`` for ``layer_types[i] == "conv"``: ``[B | C | u] = x W_in`` (three
  ``d``-wide parts in that order); ``z = B * u``; ``c_t = sum_j w_j *
  z_{t - (K - 1) + j}`` over the ``K = conv_L_cache`` taps, ``z`` zero
  before the row's start (depthwise, causal, no bias, no activation);
  ``Op(x)_t = (C_t * c_t) W_out``;
* ``Op_i`` for ``"full_attention"``: ``q = x W_q`` as ``num_attention_heads``
  heads of ``d / num_attention_heads``, ``k = x W_k`` and ``v = x W_v`` as
  ``num_key_value_heads`` heads; ``q <- rms(q)``, ``k <- rms(k)`` over the
  head (one learned scale each, shared by the heads) BEFORE RoPE; RoPE over
  the whole head on the pairs ``(i, i + half)`` with ``theta_i =
  rope_theta^(-i / half)``, no scaling; query head ``h`` meets key/value
  head ``h // (heads / kv heads)``; causal softmax of ``q.k / sqrt(head)``;
  ``Op(x) = concat(o_h) W_o``; no biases;
* ``FF_i`` for ``i < num_dense_layers``: ``(silu(h W_1) * h W_3) W_2`` at
  width ``intermediate_size``; after: ``s = sigmoid(h W_g)`` over the
  ``num_experts``; the picks are the ``num_experts_per_tok`` largest of ``s
  + b`` (``use_expert_bias``); ``w_k = s_k / (sum over the picks of s +
  1e-6)`` (``norm_topk_prob``) times ``routed_scaling_factor``; ``FF(h) =
  sum_k w_k E_k(h)`` with ``E`` the same gated form at width
  ``moe_intermediate_size``; no shared expert.

``assumed`` (the configuration file lists them): the tied head; half-split
RoPE pairs and the q/k norms before RoPE; the ``1e-6``; ``b`` a seeded
stand-in for the checkpoint's buffer.

Weights come from :func:`layer_leaf` (one layer's) and :func:`outer_leaf`
(the embedding, the last norm, and every stack of the program's tree, which
holds its layers by kind: ``conv/*``, ``attn/*``, ``dense/*``, ``router/*``,
``routed/*``, ``norms/*``), one leaf at a time from the seed's key, so that
neither side ever holds the whole tree in float32. The forward runs a layer
at a time for the same reason. ``quant`` rounds both operands of every
matrix product to that type (the control); ``fault`` plants
``expert_swapped``: the first two experts of every layer trade places.
``quant="bfloat16"`` is no control but a witness: operands AND the
residual stream rounded to the configuration's own ``compute_dtype``, which
is what any program in that type holds; its gap to the float32 reference
is the nearest such a program can come (:func:`rounder`).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.rounding import operand_rounder, round_to

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("expert_swapped",)
NORM_TOPK_EPS = 1e-6
# heads whose [L, L] scores exist at once (memory, not mathematics)
HEAD_GROUP = 4


# ---- the layers' kinds ----

def layer_kind(cfg: dict, i: int) -> tuple:
    """``(operator, feed-forward)`` of layer ``i``."""
    return (cfg["layer_types"][i],
            "dense" if i < cfg["num_dense_layers"] else "moe")


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


# ---- the seed's weights ----

def kind_shapes(cfg: dict) -> dict:
    """``kind -> {leaf: shape}`` of one layer's weights of each kind; the
    names are the program's stacks'."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    f, m, e = (cfg["moe_intermediate_size"], cfg["intermediate_size"],
               cfg["num_experts"])
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "norms": {"norms/operator_norm": (d,), "norms/ffn_norm": (d,)},
        "conv": {"conv/in_proj": (d, 3 * d),
                 "conv/taps": (cfg["conv_L_cache"], d),
                 "conv/out_proj": (d, d)},
        "full_attention": {"attn/q": (d, hq * hd), "attn/k": (d, hkv * hd),
                           "attn/v": (d, hkv * hd), "attn/o": (hq * hd, d),
                           "attn/q_norm": (hd,), "attn/k_norm": (hd,)},
        "dense": {"dense/gate": (d, m), "dense/up": (d, m),
                  "dense/down": (m, d)},
        "moe": {"router/kernel": (d, e), "router/bias": (e,),
                "routed/gate": (e, d, f), "routed/up": (e, d, f),
                "routed/down": (e, f, d)},
    }


def layer_paths(cfg: dict) -> dict:
    """``leaf -> (kind, shape)`` over every kind."""
    return {path: (kind, shape) for kind, leaves in kind_shapes(cfg).items()
            for path, shape in leaves.items()}


def outer_shapes(cfg: dict) -> dict:
    return {"embed/embedding": (cfg["vocab_size"], cfg["hidden_size"]),
            "embedding_norm": (cfg["hidden_size"],)}


def _leaf(cfg: dict, key, path: str, shape: tuple):
    """One leaf from its own key: matrices (the conv's taps among them)
    normal(0, 1 / fan_in); the tied embedding normal(0, 1 / hidden_size),
    so that as the head it gives a unit-rms state logits of unit variance;
    norm scales 1 + normal(0, 0.1^2); the selection bias normal(0, 0.05^2);
    all values are what ``param_dtype`` holds exactly."""
    z = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("_norm"):
        v = 1.0 + 0.1 * z
    elif path == "router/bias":
        v = 0.05 * z
    elif path == "embed/embedding":
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
            if kind == "norms" or kind in layer_kind(cfg, i)]


def outer_leaf(cfg: dict, key, path: str):
    """A leaf of the program's tree that is no single layer's: the
    embedding, the last norm, or the stack ``path`` of all layers of its
    kind, in layer order, held in ``param_dtype`` (which holds every value
    exactly; a float32 stack of the routed experts would be 5.6 GB)."""
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


def make_layer_params(cfg: dict, key, layer, kind: tuple) -> dict:
    """The float32 weights of layer ``layer`` (which may be traced), whose
    kind is ``kind``."""
    shapes = kind_shapes(cfg)
    return {p: layer_leaf(cfg, key, layer, p)
            for k in ("norms",) + tuple(kind) for p in shapes[k]}


def make_params(cfg: dict, key) -> dict:
    """The whole tree, float32: ``{"outer": {...}, "layers": [{...}]}``.
    For small sizes; at the cell's size take a layer at a time."""
    return {"outer": {p: outer_leaf(cfg, key, p) for p in outer_shapes(cfg)},
            "layers": [make_layer_params(cfg, key, i, layer_kind(cfg, i))
                       for i in range(cfg["num_hidden_layers"])]}


# ---- the forward ----

def _wide(quant: str | None) -> bool:
    """Whether ``quant`` has float32's exponent range (bfloat16)."""
    return quant is not None and jnp.finfo(jnp.dtype(quant)).nexp >= 8


def rounder(quant: str | None):
    """What rounds a product's operand to ``quant``: per-tensor scaled
    (``operand_rounder``) for the narrow types; plain for a type with
    float32's exponent range, which needs no scale (and whose largest value
    ``operand_rounder``'s scale cannot be divided by in float32)."""
    if _wide(quant):
        return lambda a: round_to(a, quant)
    return operand_rounder(quant)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _dot(a, b, q):
    return jnp.dot(q(a), q(b), precision=HIGHEST)


def short_conv(p: dict, x, cfg: dict, quant: str | None = None):
    """The gated short convolution of one row: ``x`` ``[L, d]`` (already
    normed); returns ``[L, d]``."""
    q = rounder(quant)
    d = cfg["hidden_size"]
    bcu = _dot(x, p["conv/in_proj"], q)
    gate_b, gate_c, u = bcu[:, :d], bcu[:, d:2 * d], bcu[:, 2 * d:]
    z = gate_b * u
    taps = p["conv/taps"]
    last = taps.shape[0] - 1
    mixed = jnp.zeros_like(z)
    for j in range(last + 1):
        back = last - j                 # tap j meets the value `back` before
        shifted = jnp.concatenate(
            [jnp.zeros((back, d), z.dtype), z[:z.shape[0] - back]], axis=0)
        mixed = mixed + taps[j] * shifted
    return _dot(gate_c * mixed, p["conv/out_proj"], q)


def rope_half(x, positions, cfg: dict):
    """Rotate the pairs ``(x[i], x[i + half])`` of the last axis by ``p x
    rope_theta^(-i / half)``; ``x`` ``[L, heads, dim]``."""
    half = x.shape[-1] // 2
    inv = cfg["rope_theta"] ** (-np.arange(half, dtype=np.float64) / half)
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p: dict, x, positions, cfg: dict, quant: str | None = None):
    """Grouped-query attention of one row: ``x`` ``[L, d]`` (already
    normed), ``positions`` ``[L]``; returns ``[L, d]``."""
    q = rounder(quant)
    eps, hd = cfg["norm_eps"], head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = x.shape[0]
    qh = _dot(x, p["attn/q"], q).reshape(n, h, hd)
    kh = _dot(x, p["attn/k"], q).reshape(n, hkv, hd)
    vh = _dot(x, p["attn/v"], q).reshape(n, hkv, hd)
    qh = rope_half(rms_norm(qh, p["attn/q_norm"], eps), positions, cfg)
    kh = rope_half(rms_norm(kh, p["attn/k_norm"], eps), positions, cfg)
    # every query head gets its own copy of the key/value head it shares
    kh = jnp.repeat(kh, h // hkv, axis=1)
    vh = jnp.repeat(vh, h // hkv, axis=1)
    causal = positions[:, None] >= positions[None, :]
    qh, kh, vh = q(qh), q(kh), q(vh)
    out = []
    for g in range(0, h, HEAD_GROUP):       # [heads, L, L] a group at a time
        heads = slice(g, g + HEAD_GROUP)
        scores = jnp.einsum("qhd,khd->hqk", qh[:, heads], kh[:, heads],
                            precision=HIGHEST) * hd ** -0.5
        scores = jnp.where(causal[None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", q(w), vh[:, heads],
                              precision=HIGHEST))
    out = jnp.concatenate(out, axis=1)
    return _dot(out.reshape(n, h * hd), p["attn/o"], q)


def gated(x, gate, up, down, q):
    return _dot(jax.nn.silu(_dot(x, gate, q)) * _dot(x, up, q), down, q)


def route(p: dict, x, cfg: dict, q):
    """``(picks [L, k], weights [L, k], margin [L])``: the top-k of the
    sigmoid scores plus the selection bias, the weights the unbiased
    scores normalised over the picks, and the gap between the last pick's
    biased score and the next's."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_dot(x, p["router/kernel"], q))
    biased = scores + p["router/bias"] if cfg["use_expert_bias"] else scores
    top, picks = jax.lax.top_k(biased, k + 1)
    picks = picks[:, :k]
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + NORM_TOPK_EPS)
    return (picks, weights * cfg["routed_scaling_factor"],
            top[:, k - 1] - top[:, k])


def moe(p: dict, x, cfg: dict, quant: str | None = None,
        fault: str | None = None):
    """The expert layer on ``x`` ``[L, d]`` (already normed): ``(y,
    margin)``."""
    q = rounder(quant)
    picks, weights, margin = route(p, x, cfg, q)

    def one_expert(e, routed):
        # every token through expert e, weighted by its pick of e (0 for
        # most); a loop the compiler sees once, not num_experts copies
        src = jnp.where(e < 2, e ^ 1, e) if fault == "expert_swapped" else e
        w_e = jnp.sum(jnp.where(picks == e, weights, 0.0), axis=-1)
        return routed + w_e[:, None] * gated(
            x, p["routed/gate"][src], p["routed/up"][src],
            p["routed/down"][src], q)

    return jax.lax.fori_loop(0, cfg["num_experts"], one_expert,
                             jnp.zeros_like(x)), margin


def layer(p: dict, x, positions, cfg: dict, kind: tuple,
          quant: str | None = None, fault: str | None = None):
    """One block of kind ``kind`` on one row: ``(y [L, d], routing margin
    [L])``, the margin infinite for a dense feed-forward layer."""
    eps = cfg["norm_eps"]
    op, ff = kind
    # a program in a wide ``quant`` holds the residual stream in it too
    stream = rounder(quant) if _wide(quant) else (lambda a: a)
    normed = rms_norm(x, p["norms/operator_norm"], eps)
    if op == "conv":
        h = stream(x + short_conv(p, normed, cfg, quant))
    else:
        h = stream(x + attention(p, normed, positions, cfg, quant))
    normed = rms_norm(h, p["norms/ffn_norm"], eps)
    if ff == "dense":
        y = gated(normed, p["dense/gate"], p["dense/up"], p["dense/down"],
                  rounder(quant))
        margin = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    else:
        y, margin = moe(p, normed, cfg, quant, fault)
    return stream(h + y), margin


def head_logits(outer: dict, x, cfg: dict, quant: str | None = None):
    q = rounder(quant)
    x = rms_norm(x, outer["embedding_norm"], cfg["norm_eps"])
    return _dot(x, outer["embed/embedding"].T, q)


def token_logprob(logits, tokens):
    """``out[0] = 0``, ``out[t] = log softmax(logits[t-1])[tokens[t]]``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    got = jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=1)[:, 0]
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), got])


def forward(params: dict, tokens, cfg: dict, quant: str | None = None,
            fault: str | None = None, positions=None) -> dict:
    """One row of token ids ``[L]`` through a whole tree of
    :func:`make_params`: ``features``, ``logits``, ``token_logprob`` and
    the routing ``margin`` (the least over expert layers, per token)."""
    n = tokens.shape[0]
    positions = jnp.arange(n) if positions is None else positions
    x = params["outer"]["embed/embedding"][tokens]
    margin = jnp.full((n,), jnp.inf, jnp.float32)
    for i, p in enumerate(params["layers"]):
        x, m = layer(p, x, positions, cfg, layer_kind(cfg, i), quant, fault)
        margin = jnp.minimum(margin, m)
    logits = head_logits(params["outer"], x, cfg, quant)
    feats = jnp.mean(rms_norm(x, params["outer"]["embedding_norm"],
                              cfg["norm_eps"]), axis=0)
    return {"features": feats, "logits": logits, "margin": margin,
            "token_logprob": token_logprob(logits, tokens)}


def score_rows(cfg: dict, key, rows, quant: str | None = None,
               fault: str | None = None) -> tuple:
    """``(token_logprob [N, L], margin [N, L])`` of the token rows ``[N,
    L]``, a layer at a time: one layer's float32 weights are made, every
    row goes through it, and they are dropped before the next is made."""
    rows = np.asarray(rows).astype(np.int32)
    n, length = rows.shape
    with jax.default_matmul_precision("highest"):
        outer = {k: jax.jit(lambda kk, k=k: outer_leaf(cfg, kk, k))(key)
                 for k in outer_shapes(cfg)}
        xs = [outer["embed/embedding"][row] for row in rows]
        margins = [jnp.full((length,), jnp.inf, jnp.float32)] * n
        positions = jnp.arange(length)
        kinds = {layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])}
        make = {kind: jax.jit(lambda k, i, kind=kind: make_layer_params(
            cfg, k, i, kind)) for kind in kinds}
        step = {kind: jax.jit(lambda p, x, kind=kind: layer(
            p, x, positions, cfg, kind, quant, fault)) for kind in kinds}
        for i in range(cfg["num_hidden_layers"]):
            kind = layer_kind(cfg, i)
            p = make[kind](key, i)
            for r in range(n):
                xs[r], m = step[kind](p, xs[r])
                margins[r] = jnp.minimum(margins[r], m)
            del p
        tail = jax.jit(lambda o, x, t: token_logprob(
            head_logits(o, x, cfg, quant), t))
        logprob = [np.asarray(tail(outer, xs[r], rows[r])) for r in range(n)]
    return np.stack(logprob), np.stack([np.asarray(m) for m in margins])
