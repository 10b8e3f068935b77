"""Plain reference for ``model_type: mistral4`` (Mistral-Small-4-119B-2603):
latent attention and a top-k expert layer with a shared expert, in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.
No kernels, no cache; experts by a plain loop over the held ones, every
token through each.

The equations, from the configuration's own keys (DeepSeek-V3 convention):

* block: ``h = x + MLA(rms(x))``, ``y = h + MoE(rms(h))``; after the last
  block a final RMSNorm and the head ``W_out``;
* MLA: ``c_q = rms(x W_qa)``; ``q = c_q W_qb`` as heads of ``[q_nope |
  q_rope]``; ``[c_kv | k_rope] = x W_kva``; ``c_kv = rms(c_kv)``; ``[k_nope
  | v]`` per head ``= c_kv W_kvb``; RoPE over interleaved pairs with YaRN
  frequencies on ``q_rope`` and on the one ``k_rope`` all heads share; ``q``
  times ``1 + beta ln(1 + floor(p / original_max_position_embeddings))``;
  causal softmax of ``s q.k``; no biases;
* MoE: ``P = softmax(h W_g)`` over the router's whole width, the
  ``num_experts_per_tok`` largest, ``w_k = P_k / sum of the picks``; a
  share holds experts ``[first_expert, first_expert + n_routed_experts)``
  and sums only the held picks' terms (what the absent experts would add is
  left out, and that partial result goes on); plus one shared expert.

``assumed`` (the configuration file lists both): ``s = qk_head_dim^-0.5 x
(0.1 mscale_all_dim ln factor + 1)^2``; softmax scoring over the whole
router with no correction bias.

Weights come from :func:`layer_leaf` / :func:`outer_leaf`, one leaf at a
time from the seed's key, so that neither side ever holds the whole tree
in float32; the driver hands the same values to the program. The forward
runs a layer at a time for the same reason. ``quant`` rounds both operands
of every matrix product to that type (the control); ``fault`` plants
``expert_swapped``: the first two held experts of every layer trade places.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.rounding import operand_rounder, round_to

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("expert_swapped",)
# heads whose [L, L] scores exist at once (memory, not mathematics)
HEAD_GROUP = 8


# ---- the seed's weights ----

def layer_shapes(cfg: dict) -> dict:
    """Flat ``path -> shape`` of one layer's weights, the share's experts
    only; the paths are the program's, joined by '/'."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    h, held = cfg["num_attention_heads"], cfg["n_routed_experts"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v, rq, rkv = cfg["v_head_dim"], cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {
        "input_norm/scale": (d,),
        "mla/q_a/kernel": (d, rq),
        "mla/q_a_norm/scale": (rq,),
        "mla/q_b/kernel": (rq, h * (nope + rope)),
        "mla/kv_a/kernel": (d, rkv + rope),
        "mla/kv_a_norm/scale": (rkv,),
        "mla/kv_b/kernel": (rkv, h * (nope + v)),
        "mla/o/kernel": (h * v, d),
        "post_norm/scale": (d,),
        "moe/router/kernel": (d, cfg["router_width"]),
        "moe/experts/gate": (held, d, f),
        "moe/experts/up": (held, d, f),
        "moe/experts/down": (held, f, d),
        "moe/shared/gate": (d, f),
        "moe/shared/up": (d, f),
        "moe/shared/down": (f, d),
    }


def outer_shapes(cfg: dict) -> dict:
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed/embedding": (vocab, d), "final_norm/scale": (d,),
            "head/kernel": (d, vocab)}


def _leaf(cfg: dict, key, path: str, shape: tuple):
    """One leaf from its own key: matrices normal(0, 1 / fan_in) (the
    embedding normal(0, 1)), norm scales 1 + normal(0, 0.1^2); all values
    are what ``param_dtype`` holds exactly."""
    z = jax.random.normal(key, shape, jnp.float32)
    if path.endswith("/scale"):
        v = 1.0 + 0.1 * z
    elif path.endswith("/embedding"):
        v = z
    else:
        v = z * (1.0 / math.sqrt(shape[-2]))
    return round_to(v, cfg["param_dtype"])


def layer_leaf(cfg: dict, key, layer, path: str):
    """Leaf ``path`` of layer ``layer`` (which may be traced)."""
    shapes = layer_shapes(cfg)
    k = jax.random.fold_in(jax.random.fold_in(key, 1), layer)
    k = jax.random.fold_in(k, list(shapes).index(path))
    return _leaf(cfg, k, path, shapes[path])


def outer_leaf(cfg: dict, key, path: str):
    shapes = outer_shapes(cfg)
    k = jax.random.fold_in(jax.random.fold_in(key, 2),
                           list(shapes).index(path))
    return _leaf(cfg, k, path, shapes[path])


def make_layer_params(cfg: dict, key, layer) -> dict:
    return {p: layer_leaf(cfg, key, layer, p) for p in layer_shapes(cfg)}


def make_params(cfg: dict, key) -> dict:
    """The whole tree, float32: ``{"outer": {...}, "layers": [{...}]}``.
    For small sizes; at the cell's size take a layer at a time."""
    return {"outer": {p: outer_leaf(cfg, key, p) for p in outer_shapes(cfg)},
            "layers": [make_layer_params(cfg, key, i)
                       for i in range(cfg["num_hidden_layers"])]}


# ---- the forward ----

def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """The ``qk_rope_head_dim / 2`` rotary frequencies under YaRN."""
    rp = cfg["rope_parameters"]
    d = cfg["qk_rope_head_dim"]
    base, factor = rp["rope_theta"], rp["factor"]
    orig = rp["original_max_position_embeddings"]
    i = np.arange(d // 2, dtype=np.float64)
    f = base ** (-2.0 * i / d)

    def correction(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(rp["beta_fast"])), 0)
    high = min(math.ceil(correction(rp["beta_slow"])), d - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return (f / factor) * ramp + f * (1.0 - ramp)


def yarn_attention_factor(cfg: dict) -> float:
    """What cos and sin are multiplied by: ``mscale`` over ``mscale_all_dim``
    in YaRN's ``0.1 m ln factor + 1`` form (1 where the two are equal)."""
    rp = cfg["rope_parameters"]

    def ms(m):
        return 0.1 * m * math.log(rp["factor"]) + 1.0 if m else 1.0
    return ms(rp["mscale"]) / ms(rp["mscale_all_dim"])


def softmax_scale(cfg: dict) -> float:
    """``assumed`` (a): the DeepSeek-V3 use of ``mscale_all_dim``."""
    rp = cfg["rope_parameters"]
    m = 0.1 * rp["mscale_all_dim"] * math.log(rp["factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_interleaved(x, positions, cfg: dict):
    """Rotate the pairs ``(x[2i], x[2i+1])`` of the last axis by ``p x
    inv_freq_i``; ``x`` ``[L, ..., d]``, ``positions`` ``[L]``."""
    angle = (positions.astype(jnp.float32)[:, None]
             * jnp.asarray(yarn_inv_freq(cfg), jnp.float32)[None, :])
    af = yarn_attention_factor(cfg)
    cos, sin = jnp.cos(angle) * af, jnp.sin(angle) * af
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, even * sin + odd * cos], -1)
    return out.reshape(x.shape)


def query_position_scale(positions, cfg: dict):
    rp = cfg["rope_parameters"]
    steps = jnp.floor(positions.astype(jnp.float32)
                      / rp["original_max_position_embeddings"])
    return 1.0 + rp["llama_4_scaling_beta"] * jnp.log1p(steps)


def _dot(a, b, q):
    return jnp.dot(q(a), q(b), precision=HIGHEST)


def mla(p: dict, x, positions, cfg: dict, quant: str | None = None):
    """Latent attention of one row: ``x`` ``[L, d]`` (already normed),
    ``positions`` ``[L]``; returns ``[L, d]``."""
    q = operand_rounder(quant)
    eps = cfg["rms_norm_eps"]
    h = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v_dim, rkv = cfg["v_head_dim"], cfg["kv_lora_rank"]
    n = x.shape[0]
    c_q = rms_norm(_dot(x, p["mla/q_a/kernel"], q),
                   p["mla/q_a_norm/scale"], eps)
    qh = _dot(c_q, p["mla/q_b/kernel"], q).reshape(n, h, nope + rope)
    kv_a = _dot(x, p["mla/kv_a/kernel"], q)
    c_kv = rms_norm(kv_a[:, :rkv], p["mla/kv_a_norm/scale"], eps)
    k_rope = rope_interleaved(kv_a[:, rkv:], positions, cfg)     # [L, rope]
    kv = _dot(c_kv, p["mla/kv_b/kernel"], q).reshape(n, h, nope + v_dim)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_rope = rope_interleaved(qh[..., nope:], positions, cfg)
    qh = jnp.concatenate([qh[..., :nope], q_rope], -1)
    qh = qh * query_position_scale(positions, cfg)[:, None, None]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None, :], (n, h, rope))], -1)
    causal = positions[:, None] >= positions[None, :]
    qh, k, v = q(qh), q(k), q(v)
    out = []
    for g in range(0, h, HEAD_GROUP):       # [heads, L, L] a group at a time
        heads = slice(g, g + HEAD_GROUP)
        scores = jnp.einsum("qhd,khd->hqk", qh[:, heads], k[:, heads],
                            precision=HIGHEST) * softmax_scale(cfg)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", q(w), v[:, heads],
                              precision=HIGHEST))
    out = jnp.concatenate(out, axis=1)
    return _dot(out.reshape(n, h * v_dim), p["mla/o/kernel"], q)


def gated(x, gate, up, down, q):
    return _dot(jax.nn.silu(_dot(x, gate, q)) * _dot(x, up, q), down, q)


def route(p: dict, x, cfg: dict, q):
    """``(picks [L, k], weights [L, k], margin [L])``: the top-k of the
    softmax over the router's whole width, the weights normalised over the
    picks, and the gap between the last pick's probability and the next."""
    k = cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_dot(x, p["moe/router/kernel"], q), axis=-1)
    top, picks = jax.lax.top_k(probs, k + 1)
    weights = top[:, :k]
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return (picks[:, :k], weights * cfg["routed_scaling_factor"],
            top[:, k - 1] - top[:, k])


def moe(p: dict, x, cfg: dict, quant: str | None = None,
        fault: str | None = None, parts: bool = False):
    """The share's expert layer on ``x`` ``[L, d]`` (already normed):
    ``(y, margin)``, or ``(routed, shared, margin)`` with ``parts``."""
    q = operand_rounder(quant)
    picks, weights, margin = route(p, x, cfg, q)
    first, held = cfg["first_expert"], cfg["n_routed_experts"]
    def one_expert(e, routed):
        # every token through expert e, weighted by its pick of e (0 for
        # most); a loop the compiler sees once, not ``held`` copies of it
        src = jnp.where(e < 2, e ^ 1, e) if fault == "expert_swapped" else e
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        return routed + w_e[:, None] * gated(
            x, p["moe/experts/gate"][src], p["moe/experts/up"][src],
            p["moe/experts/down"][src], q)

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(x))
    shared = gated(x, p["moe/shared/gate"], p["moe/shared/up"],
                   p["moe/shared/down"], q)
    if parts:
        return routed, shared, margin
    return routed + shared, margin


def layer(p: dict, x, positions, cfg: dict, quant: str | None = None,
          fault: str | None = None):
    """One block on one row: ``(y [L, d], routing margin [L])``."""
    eps = cfg["rms_norm_eps"]
    h = x + mla(p, rms_norm(x, p["input_norm/scale"], eps), positions, cfg,
                quant)
    y, margin = moe(p, rms_norm(h, p["post_norm/scale"], eps), cfg, quant,
                    fault)
    return h + y, margin


def head_logits(outer: dict, x, cfg: dict, quant: str | None = None):
    q = operand_rounder(quant)
    x = rms_norm(x, outer["final_norm/scale"], cfg["rms_norm_eps"])
    return _dot(x, outer["head/kernel"], q)


def token_logprob(logits, tokens):
    """``out[0] = 0``, ``out[t] = log softmax(logits[t-1])[tokens[t]]``."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    got = jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=1)[:, 0]
    return jnp.concatenate([jnp.zeros((1,), jnp.float32), got])


def forward(params: dict, tokens, cfg: dict, quant: str | None = None,
            fault: str | None = None, positions=None) -> dict:
    """One row of token ids ``[L]`` through a whole tree of
    :func:`make_params`: ``features``, ``logits``, ``token_logprob`` and
    the routing ``margin`` (the least over layers, per token)."""
    n = tokens.shape[0]
    positions = jnp.arange(n) if positions is None else positions
    x = params["outer"]["embed/embedding"][tokens]
    margin = jnp.full((n,), jnp.inf, jnp.float32)
    for p in params["layers"]:
        x, m = layer(p, x, positions, cfg, quant, fault)
        margin = jnp.minimum(margin, m)
    logits = head_logits(params["outer"], x, cfg, quant)
    feats = jnp.mean(rms_norm(x, params["outer"]["final_norm/scale"],
                              cfg["rms_norm_eps"]), axis=0)
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
        embed = jax.jit(lambda k: outer_leaf(cfg, k, "embed/embedding"))(key)
        xs = [embed[row] for row in rows]
        del embed
        margins = [jnp.full((length,), jnp.inf, jnp.float32)] * n
        positions = jnp.arange(length)
        make = jax.jit(lambda k, i: make_layer_params(cfg, k, i))
        step = jax.jit(lambda p, x: layer(p, x, positions, cfg, quant,
                                          fault))
        for i in range(cfg["num_hidden_layers"]):
            p = make(key, i)
            for r in range(n):
                xs[r], m = step(p, xs[r])
                margins[r] = jnp.minimum(margins[r], m)
            del p
        outer = {k: jax.jit(lambda kk, k=k: outer_leaf(cfg, kk, k))(key)
                 for k in ("final_norm/scale", "head/kernel")}
        tail = jax.jit(lambda o, x, t: token_logprob(
            head_logits(o, x, cfg, quant), t))
        logprob = [np.asarray(tail(outer, xs[r], rows[r])) for r in range(n)]
    return np.stack(logprob), np.stack([np.asarray(m) for m in margins])
