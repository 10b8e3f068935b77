"""Plain reference for ``model_type: nemotron_h``
(NVIDIA-Nemotron-3-Nano-30B-A3B): layers of ONE mixer each, a Mamba-2
layer, a layer of ungated squared-ReLU experts or grouped-query attention,
in straightforward ``jax.numpy`` and float32 at ``highest`` matmul
precision. No kernels, no scan over layers, no cache; the recurrence is the
literal ``lax.scan`` over positions (no chunked form, so no carry between
chunks to get wrong); the experts are a loop over the held ones; a query
head at a time meets the key/value head it shares.

The equations, from the configuration's own keys (``x`` a token's state,
``t`` its position; ``d = hidden_size``, ``H = mamba_num_heads``, ``P =
mamba_head_dim``, ``d_i = H P``, ``G = n_groups``, ``N = ssm_state_size``,
``K = conv_kernel``, eps ``layer_norm_epsilon``):

* block ``i``: ``h = x + Mixer_i(rms(x) * w_i)``, the mixer by character
  ``i`` of ``hybrid_override_pattern`` (``M``, ``E``, ``*``); after the last
  block an RMSNorm (``norm_f``) and logits ``h W_head`` (untied); no biases
  but the convolution's and the router's selection bias;
* ``M``: ``[z | xBC | dt] = x W_in`` (widths ``d_i``, ``d_i + 2 G N``,
  ``H``); ``xBC_t <- silu(b + sum_j w_j * xBC_{t - (K - 1) + j})``
  (depthwise, zero before the row's start); ``[x | B | C]`` = ``d_i`` | ``G
  N`` | ``G N``; ``delta_t = softplus(dt_t + dt_bias)`` ``[H]``; ``A =
  -exp(A_log)`` ``[H]``; for head ``h`` in group ``g = h // (H / G)``, with
  ``S_{-1} = 0``: ``S_t = exp(delta_t A) S_{t-1} + delta_t x_t B_{g,t}^T``,
  ``y_t = S_t C_{g,t} + D_h x_t``; ``u = y * silu(z)``; an RMSNorm of ``u``
  within each of ``G`` groups of ``d_i / G`` channels, times a learned
  ``[d_i]`` scale; ``Mixer = u W_out``;
* ``E``: ``s = sigmoid(x W_r)`` over the router's whole width; the picks are
  the ``num_experts_per_tok`` largest of ``s + b``; ``w_k = s_k / (sum of
  the picks' s + 1e-20) * routed_scaling_factor``; expert ``e(x) = relu(x
  U_e)^2 D_e``; a share holds experts ``[first_expert, first_expert +
  n_routed_experts)`` and sums only the held picks' terms; plus one shared
  expert of the same form, for every token;
* ``*``: ``q = x W_q`` as ``num_attention_heads`` heads of ``head_dim``,
  ``k``, ``v`` as ``num_key_value_heads`` heads; NO positional term;
  causal softmax of ``head_dim^-0.5 q.k``; ``W_o``.

Weights come from :func:`layer_leaf` / :func:`outer_leaf` (the program
holds its layers by kind: ``mamba2/*``, ``attn/*``, ``router/*``,
``routed/*``, ``shared/*``, ``norms/*``), one leaf at a time from the seed's
key. The forward runs a layer at a time so that neither side ever holds the
tree in float32. ``quant`` rounds both operands of every matrix product to
that type (the control; ``"bfloat16"`` rounds the residual stream too);
``fault`` plants ``expert_swapped`` (the first two held experts of every
layer trade places) or ``state_dropped`` (the state is zeroed every
``DROP_EVERY`` positions, which is what a chunked kernel that loses its
carry computes).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.lfm2 import (
    _dot, _wide, rms_norm, rounder, token_logprob,
)
from benchmark.reference.rounding import round_to

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("expert_swapped", "state_dropped")
DROP_EVERY = 128
NORM_TOPK_EPS = 1e-20
# the decay rates the stand-in ``A_log`` starts the heads from
A_RANGE = (1.0, 16.0)
KINDS = {"M": "mamba2", "E": "moe", "*": "attention"}


# ---- the layers' kinds ----

def layer_kind(cfg: dict, i: int) -> str:
    return KINDS[cfg["hybrid_override_pattern"][i]]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def conv_dim(cfg: dict) -> int:
    return d_inner(cfg) + 2 * cfg["n_groups"] * cfg["ssm_state_size"]


def router_width(cfg: dict) -> int:
    return cfg.get("router_width") or cfg["n_routed_experts"]


# ---- the seed's weights ----

def kind_shapes(cfg: dict) -> dict:
    """``kind -> {leaf: shape}`` of one layer's weights of each kind; the
    names are the program's stacks'."""
    d, d_i, hd = cfg["hidden_size"], d_inner(cfg), cfg["head_dim"]
    h, cd = cfg["mamba_num_heads"], conv_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, held = router_width(cfg), cfg["n_routed_experts"]
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    return {
        "every": {"norms/norm": (d,)},
        "mamba2": {"mamba2/in_proj": (d, d_i + cd + h),
                   "mamba2/conv_taps": (cfg["conv_kernel"], cd),
                   "mamba2/conv_bias": (cd,), "mamba2/dt_bias": (h,),
                   "mamba2/A_log": (h,), "mamba2/D": (h,),
                   "mamba2/norm": (d_i,), "mamba2/out_proj": (d_i, d)},
        "moe": {"router/kernel": (d, e), "router/bias": (e,),
                "routed/up": (held, d, f), "routed/down": (held, f, d),
                "shared/up": (d, fs), "shared/down": (fs, d)},
        "attention": {"attn/q": (d, hq * hd), "attn/k": (d, hkv * hd),
                      "attn/v": (d, hkv * hd), "attn/o": (hq * hd, d)},
    }


def layer_paths(cfg: dict) -> dict:
    """``leaf -> (kind, shape)`` over every kind."""
    return {path: (kind, shape) for kind, leaves in kind_shapes(cfg).items()
            for path, shape in leaves.items()}


def outer_shapes(cfg: dict) -> dict:
    return {"embed/embedding": (cfg["vocab_size"], cfg["hidden_size"]),
            "head/kernel": (cfg["hidden_size"], cfg["vocab_size"]),
            "norm_f": (cfg["hidden_size"],)}


def _leaf(cfg: dict, key, path: str, shape: tuple):
    """One leaf from its own key: matrices (the conv's taps among them)
    normal(0, 1 / fan_in); the embedding normal(0, 1); norm scales and ``D``
    1 + normal(0, 0.1^2); ``conv_bias`` normal(0, 0.1^2); the selection bias
    normal(0, 0.05^2); ``A_log`` the log of rates uniform over ``A_RANGE``,
    one a head; ``dt_bias`` the inverse softplus of step sizes log-uniform
    over ``[time_step_min, time_step_max]`` floored at ``time_step_floor``;
    all values are what ``param_dtype`` holds exactly."""
    name = path.rsplit("/", 1)[-1]
    if name == "dt_bias":
        lo, hi = (math.log(cfg[k]) for k in ("time_step_min",
                                             "time_step_max"))
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, lo, hi)), cfg["time_step_floor"])
        return round_to(dt + jnp.log(-jnp.expm1(-dt)), cfg["param_dtype"])
    if name == "A_log":
        return round_to(jnp.log(jax.random.uniform(
            key, shape, jnp.float32, *A_RANGE)), cfg["param_dtype"])
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ("norm", "norm_f", "D"):
        v = 1.0 + 0.1 * z
    elif name == "conv_bias":
        v = 0.1 * z
    elif path == "router/bias":
        v = 0.05 * z
    elif name == "embedding":
        v = z
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
    embedding, the head, the last norm, or the stack ``path`` of all layers
    of its kind, in layer order, held in ``param_dtype`` (which holds every
    value exactly; a float32 stack of the routed experts would be 12.8
    GB)."""
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

def conv_silu(p: dict, u):
    """``silu(b + sum_j w_j * u_{t - (K - 1) + j})`` of one row ``[L,
    channels]``, an explicit sum over shifted copies."""
    taps = p["mamba2/conv_taps"]
    last = taps.shape[0] - 1
    mixed = jnp.zeros_like(u) + p["mamba2/conv_bias"]
    for j in range(last + 1):
        back = last - j                 # tap j meets the value `back` before
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[:u.shape[0] - back]],
            axis=0)
        mixed = mixed + taps[j] * shifted
    return jax.nn.silu(mixed)


def recurrence(delta, x, a, b_t, c_t, fault: str | None = None):
    """``y [L, H, P]`` of one row: ``delta [L, H]``, ``x [L, H, P]``, ``a
    [H]`` (negative), ``b_t`` / ``c_t [L, H, N]`` (the group's, a copy a
    head); the state ``[H, P, N]`` a position at a time; ``state_dropped``
    zeroes it before every ``DROP_EVERY``-th position."""
    def step(s, at):
        t, d, xx, bb, cc = at
        if fault == "state_dropped":
            s = jnp.where(t % DROP_EVERY == 0, 0.0, s)
        s = jnp.exp(d * a)[:, None, None] * s \
            + (d[:, None] * xx)[:, :, None] * bb[:, None, :]
        return s, jnp.sum(s * cc[:, None, :], axis=-1)

    heads, p, n = x.shape[1], x.shape[2], b_t.shape[2]
    _, y = jax.lax.scan(step, jnp.zeros((heads, p, n), jnp.float32),
                        (jnp.arange(delta.shape[0]), delta, x, b_t, c_t))
    return y


def mamba2_mixer(p: dict, x, cfg: dict, quant: str | None = None,
                 fault: str | None = None):
    """The Mamba-2 mixer of one row: ``x`` ``[L, d]`` (already normed)."""
    q = rounder(quant)
    d_i, cd = d_inner(cfg), conv_dim(cfg)
    h, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    length = x.shape[0]
    zxd = _dot(x, p["mamba2/in_proj"], q)
    z, dt = zxd[:, :d_i], zxd[:, d_i + cd:]
    xbc = conv_silu(p, zxd[:, d_i:d_i + cd])
    xs = xbc[:, :d_i].reshape(length, h, hp)
    # every head gets its own copy of the B and C of its group
    b_t = jnp.repeat(xbc[:, d_i:d_i + g * n].reshape(length, g, n), h // g,
                     axis=1)
    c_t = jnp.repeat(xbc[:, d_i + g * n:].reshape(length, g, n), h // g,
                     axis=1)
    delta = jax.nn.softplus(dt + p["mamba2/dt_bias"])
    y = recurrence(delta, xs, -jnp.exp(p["mamba2/A_log"]), b_t, c_t, fault)
    y = y + p["mamba2/D"][:, None] * xs
    u = (y.reshape(length, d_i) * jax.nn.silu(z)).reshape(length, g, -1)
    u = u * jax.lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return _dot(u.reshape(length, d_i) * p["mamba2/norm"],
                p["mamba2/out_proj"], q)


def attention(p: dict, x, cfg: dict, quant: str | None = None):
    """Grouped-query attention of one row, no positional term: ``x`` ``[L,
    d]`` (already normed); a query head at a time (its ``[L, L]`` scores
    are 1 GB at the cell's window)."""
    q = rounder(quant)
    hd = cfg["head_dim"]
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


def relu2(x, up, down, q):
    return _dot(jnp.square(jax.nn.relu(_dot(x, up, q))), down, q)


def route(p: dict, x, cfg: dict, q):
    """``(picks [L, k], weights [L, k], margin [L])``: the top-k of the
    sigmoid scores plus the selection bias over the router's whole width,
    the weights the unbiased scores normalised over the picks and scaled,
    and the gap between the last pick's biased score and the next's."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_dot(x, p["router/kernel"], q))
    top, picks = jax.lax.top_k(scores + p["router/bias"], k + 1)
    picks = picks[:, :k]
    weights = jnp.take_along_axis(scores, picks, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + NORM_TOPK_EPS)
    return (picks, weights * cfg["routed_scaling_factor"],
            top[:, k - 1] - top[:, k])


def moe(p: dict, x, cfg: dict, quant: str | None = None,
        fault: str | None = None, parts: bool = False):
    """The expert layer on ``x`` ``[L, d]`` (already normed): ``(y,
    margin)``, or ``(routed, shared, margin)`` with ``parts``."""
    q = rounder(quant)
    picks, weights, margin = route(p, x, cfg, q)
    first, held = cfg.get("first_expert", 0), cfg["n_routed_experts"]

    def one_expert(e, routed):
        # every token through held expert e, weighted by its pick of it (0
        # for most); a loop the compiler sees once, not ``held`` copies
        src = jnp.where(e < 2, e ^ 1, e) if fault == "expert_swapped" else e
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        return routed + w_e[:, None] * relu2(
            x, p["routed/up"][src], p["routed/down"][src], q)

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(x))
    shared = (relu2(x, p["shared/up"], p["shared/down"], q)
              if cfg.get("n_shared_experts", 1) else jnp.zeros_like(x))
    return (routed, shared, margin) if parts else (routed + shared, margin)


def layer(p: dict, x, cfg: dict, kind: str, quant: str | None = None,
          fault: str | None = None):
    """One block whose mixer is ``kind`` on one row: ``(y [L, d], routing
    margin [L])``, the margin infinite for a layer without a router."""
    # a program in a wide ``quant`` holds the residual stream in it too
    stream = rounder(quant) if _wide(quant) else (lambda a: a)
    normed = rms_norm(x, p["norms/norm"], cfg["layer_norm_epsilon"])
    margin = jnp.full((x.shape[0],), jnp.inf, jnp.float32)
    if kind == "mamba2":
        mixed = mamba2_mixer(p, normed, cfg, quant, fault)
    elif kind == "moe":
        mixed, margin = moe(p, normed, cfg, quant, fault)
    else:
        mixed = attention(p, normed, cfg, quant)
    return stream(x + mixed), margin


def head_logits(outer: dict, x, cfg: dict, quant: str | None = None):
    x = rms_norm(x, outer["norm_f"], cfg["layer_norm_epsilon"])
    return _dot(x, outer["head/kernel"], rounder(quant))


def forward(params: dict, tokens, cfg: dict, quant: str | None = None,
            fault: str | None = None) -> dict:
    """One row of token ids ``[L]`` through a whole tree of
    :func:`make_params`: ``features``, ``logits``, ``token_logprob`` and the
    routing ``margin`` (the least over expert layers, per token)."""
    x = params["outer"]["embed/embedding"][tokens]
    margin = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
    for i, p in enumerate(params["layers"]):
        x, m = layer(p, x, cfg, layer_kind(cfg, i), quant, fault)
        margin = jnp.minimum(margin, m)
    logits = head_logits(params["outer"], x, cfg, quant)
    feats = jnp.mean(rms_norm(x, params["outer"]["norm_f"],
                              cfg["layer_norm_epsilon"]), axis=0)
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
        kinds = {layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])}
        make = {kind: jax.jit(lambda k, i, kind=kind: make_layer_params(
            cfg, k, i, kind)) for kind in kinds}
        step = {kind: jax.jit(lambda p, x, kind=kind: layer(
            p, x, cfg, kind, quant, fault)) for kind in kinds}
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
