"""Plain reference for ``model_type: qwen3_next`` (Qwen3-Next-80B-A3B):
layers whose mixer is Gated DeltaNet (a gated delta-rule linear attention)
three times in four and gated softmax attention the fourth, each followed
by a top-k expert layer with a gated shared expert, in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision. No kernels, no
scan over layers, no cache; the recurrence is the literal ``lax.scan`` over
positions (no chunked form, no triangular solve, so no carry between chunks
to get wrong); the experts are a loop over the held ones; a query head at a
time meets the key/value head it shares.

The equations, from the configuration's own keys (``x`` a token's state;
``d = hidden_size``, eps ``rms_norm_eps``; ``rms0(x) = x / rms(x) * (1 +
w)``, the family's zero-centred norm):

* layer ``i``: ``h = h + Mixer_i(rms0(h))``, ``h = h + MoE_i(rms0(h))``;
  ``Mixer_i`` is attention where ``(i + 1) % full_attention_interval == 0``
  and Gated DeltaNet elsewhere; after the last layer ``rms0`` (``norm_f``)
  and logits ``h W_head`` (untied); no biases anywhere;
* Gated DeltaNet, ``Hk = linear_num_key_heads`` key heads of ``dk`` and
  ``Hv`` value heads of ``dv``, ``r = Hv / Hk``: ``x W_qkvz`` holds for each
  key head ``j`` in turn ``[q_j (dk) | k_j (dk) | v_j (r dv) | z_j (r
  dv)]``, ``x W_ba`` ``[b_j (r) | a_j (r)]`` (the public implementation's
  layout); ``[q | k | v]`` (every head's q, then k, then v) <- ``silu`` of a
  depthwise causal convolution of ``linear_conv_kernel_dim`` taps, no bias,
  zero before the row's start; ``q <- q / sqrt(|q|^2 + 1e-6) dk^-1/2`` and
  ``k <- k / sqrt(|k|^2 + 1e-6)`` a head; value head ``h`` reads key head ``h
  // r``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
  with ``S_{-1} = 0``, per position ``S <- exp(g) S``, ``delta = beta (v -
  S^T k)``, ``S <- S + k delta^T``, ``o = S^T q``; ``o <- rms(o) * w *
  silu(z)`` a value head (the norm BEFORE the gate, a plain ``[dv]`` scale);
  ``Mixer = o W_out``;
* attention, ``num_attention_heads`` query heads of ``head_dim`` on
  ``num_key_value_heads``: ``x W_q`` holds for each head ``[q_h | gate_h]``;
  ``q``, ``k`` <- ``rms0`` over the head; rotary on the first
  ``partial_rotary_factor x head_dim`` dims, pairs ``(i, i + half)``, angle
  ``t rope_theta^(-i / half)``; causal softmax of ``head_dim^-1/2 q.k``;
  ``o_h <- o_h sigmoid(gate_h)``; ``W_o``;
* experts: ``p = softmax(x W_r)`` over the router's whole width; the picks
  are the ``num_experts_per_tok`` largest, their weights ``p / (sum of the
  picks' p)``; expert ``e(x) = (silu(x G_e) * x U_e) D_e``; a share holds
  experts ``[first_expert, first_expert + num_experts)`` and sums only the
  held picks' terms; plus ``sigmoid(x w_sg)`` times a shared expert of the
  same form, for every token.

Weights come from :func:`layer_leaf` / :func:`outer_leaf` (the program holds
its layers by kind: ``gdn/*``, ``attn/*``, ``router/*``, ``routed/*``,
``shared/*``, ``norms/*``), one leaf at a time from the seed's key. The
forward runs a layer at a time so that neither side ever holds the tree in
float32. ``quant`` rounds both operands of every matrix product to that type
(the control; ``"bfloat16"`` rounds the residual stream too); ``fault``
plants ``expert_swapped`` (the first two held experts of every layer trade
places) or ``state_dropped`` (the state is zeroed every ``DROP_EVERY``
positions, which is what a chunked kernel that loses its carry computes).
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
L2_EPS = 1e-6
# the stand-in weights' decay rates and step sizes (``assumed``)
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 1e-1)
DT_FLOOR = 1e-4
ZERO_CENTRED = ("input_norm", "post_norm", "q_norm", "k_norm", "norm_f")


# ---- the layers' kinds ----

def layer_kind(cfg: dict, i: int) -> str:
    return ("attention" if (i + 1) % cfg["full_attention_interval"] == 0
            else "gdn")


def router_width(cfg: dict) -> int:
    return cfg.get("router_width") or cfg["num_experts"]


def _gdn_sizes(cfg: dict) -> tuple:
    """``(Hk, dk, Hv, dv, r)``."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return (hk, cfg["linear_key_head_dim"], hv, cfg["linear_value_head_dim"],
            hv // hk)


# ---- the seed's weights ----

def kind_shapes(cfg: dict) -> dict:
    """``kind -> {leaf: shape}`` of one layer's weights of each kind; the
    names are the program's stacks'."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hk, dk, hv, dv, r = _gdn_sizes(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    fs = cfg["shared_expert_intermediate_size"]
    return {
        "every": {"norms/input_norm": (d,), "norms/post_norm": (d,),
                  "router/kernel": (d, router_width(cfg)),
                  "routed/gate": (held, d, f), "routed/up": (held, d, f),
                  "routed/down": (held, f, d), "shared/gate": (d, fs),
                  "shared/up": (d, fs), "shared/down": (fs, d),
                  "shared/expert_gate": (d, 1)},
        "gdn": {"gdn/in_proj_qkvz": (d, hk * (2 * dk + 2 * r * dv)),
                "gdn/in_proj_ba": (d, 2 * hv),
                "gdn/conv_taps": (cfg["linear_conv_kernel_dim"],
                                  hk * (2 * dk + r * dv)),
                "gdn/dt_bias": (hv,), "gdn/A_log": (hv,), "gdn/norm": (dv,),
                "gdn/out_proj": (hv * dv, d)},
        "attention": {"attn/q": (d, 2 * h * hd), "attn/k": (d, hkv * hd),
                      "attn/v": (d, hkv * hd), "attn/o": (h * hd, d),
                      "attn/q_norm": (hd,), "attn/k_norm": (hd,)},
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
    """One leaf from its own key: matrices (the conv's taps and the shared
    expert's gate among them) normal(0, 1 / fan_in); the embedding normal(0,
    1); zero-centred norm scales normal(0, 0.1^2); the gated norm's plain
    scale 1 + normal(0, 0.1^2); ``A_log`` the log of rates uniform over
    ``A_RANGE``, one a value head; ``dt_bias`` the inverse softplus of step
    sizes log-uniform over ``DT_RANGE`` floored at ``DT_FLOOR``; all values
    are what ``param_dtype`` holds exactly."""
    name = path.rsplit("/", 1)[-1]
    if name == "dt_bias":
        dt = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, *map(math.log, DT_RANGE))), DT_FLOOR)
        return round_to(dt + jnp.log(-jnp.expm1(-dt)), cfg["param_dtype"])
    if name == "A_log":
        return round_to(jnp.log(jax.random.uniform(
            key, shape, jnp.float32, *A_RANGE)), cfg["param_dtype"])
    z = jax.random.normal(key, shape, jnp.float32)
    if name in ZERO_CENTRED:
        v = 0.1 * z
    elif name == "norm":
        v = 1.0 + 0.1 * z
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
    value exactly; a float32 stack of the routed experts would be 12.9
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

def rms0(x, w, eps):
    return rms_norm(x, 1.0 + w, eps)


def conv_silu(taps, u):
    """``silu(sum_j w_j * u_{t - (K - 1) + j})`` of one row ``[L,
    channels]``, an explicit sum over shifted copies; no bias."""
    last = taps.shape[0] - 1
    mixed = jnp.zeros_like(u)
    for j in range(last + 1):
        back = last - j                 # tap j meets the value `back` before
        shifted = jnp.concatenate(
            [jnp.zeros((back, u.shape[1]), u.dtype), u[:u.shape[0] - back]],
            axis=0)
        mixed = mixed + taps[j] * shifted
    return jax.nn.silu(mixed)


def l2_normed(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def recurrence(q, k, v, g, beta, fault: str | None = None):
    """``o [L, Hv, dv]`` of one row: ``q``, ``k`` ``[L, Hv, dk]`` (the key
    head's, a copy a value head), ``v [L, Hv, dv]``, ``g`` and ``beta [L,
    Hv]``; the state ``[Hv, dk, dv]`` a position at a time, its reads and
    writes as elementwise sums; ``state_dropped`` zeroes it before every
    ``DROP_EVERY``-th position."""
    def step(s, at):
        t, qq, kk, vv, gg, bb = at
        if fault == "state_dropped":
            s = jnp.where(t % DROP_EVERY == 0, 0.0, s)
        s = jnp.exp(gg)[:, None, None] * s
        read = jnp.sum(s * kk[:, :, None], axis=1)          # S^T k
        delta = bb[:, None] * (vv - read)
        s = s + kk[:, :, None] * delta[:, None, :]
        return s, jnp.sum(s * qq[:, :, None], axis=1)       # S^T q

    heads, dk, dv = k.shape[1], k.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), jnp.float32),
                        (jnp.arange(q.shape[0]), q, k, v, g, beta))
    return o


def gated_norm(o, z, w, eps):
    """``rms(o) * w`` over a value head's channels, THEN ``* silu(z)``."""
    return rms_norm(o, w, eps) * jax.nn.silu(z)


def gdn_mixer(p: dict, x, cfg: dict, quant: str | None = None,
              fault: str | None = None):
    """The Gated DeltaNet mixer of one row: ``x`` ``[L, d]`` (already
    normed)."""
    q = rounder(quant)
    hk, dk, hv, dv, r = _gdn_sizes(cfg)
    n = x.shape[0]
    qkvz = _dot(x, p["gdn/in_proj_qkvz"], q).reshape(n, hk, -1)
    ba = _dot(x, p["gdn/in_proj_ba"], q).reshape(n, hk, 2 * r)
    parts = (qkvz[..., :dk], qkvz[..., dk:2 * dk],
             qkvz[..., 2 * dk:2 * dk + r * dv])
    z = qkvz[..., 2 * dk + r * dv:].reshape(n, hv, dv)
    b, a = ba[..., :r].reshape(n, hv), ba[..., r:].reshape(n, hv)
    mixed = conv_silu(p["gdn/conv_taps"], jnp.concatenate(
        [part.reshape(n, -1) for part in parts], axis=-1))
    qh = l2_normed(mixed[:, :hk * dk].reshape(n, hk, dk)) * dk ** -0.5
    kh = l2_normed(mixed[:, hk * dk:2 * hk * dk].reshape(n, hk, dk))
    vh = mixed[:, 2 * hk * dk:].reshape(n, hv, dv)
    # value head h reads key head h // r
    qh, kh = jnp.repeat(qh, r, axis=1), jnp.repeat(kh, r, axis=1)
    g = -jnp.exp(p["gdn/A_log"]) * jax.nn.softplus(a + p["gdn/dt_bias"])
    o = recurrence(qh, kh, vh, g, jax.nn.sigmoid(b), fault)
    o = gated_norm(o, z, p["gdn/norm"], cfg["rms_norm_eps"])
    return _dot(o.reshape(n, hv * dv), p["gdn/out_proj"], q)


def rope_partial(x, cfg: dict):
    """Rotate the pairs ``(x[i], x[i + half])`` of the first ``rot =
    partial_rotary_factor x head_dim`` dims by ``t rope_theta^(-i / half)``,
    ``half = rot / 2``; the rest of the head as it is. ``x`` ``[L, heads,
    head_dim]``."""
    rot = int(cfg["head_dim"] * cfg["partial_rotary_factor"])
    half = rot // 2
    inv = cfg["rope_theta"] ** (-np.arange(half, dtype=np.float64) / half)
    angle = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
             * jnp.asarray(inv, jnp.float32)[None, :])
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:rot]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rot:]], axis=-1)


def output_gate(out, gate):
    """Each attention head's output times ``sigmoid`` of its gate."""
    return out * jax.nn.sigmoid(gate)


def attention(p: dict, x, cfg: dict, quant: str | None = None):
    """Gated grouped-query attention of one row: ``x`` ``[L, d]`` (already
    normed); a query head at a time (its ``[L, L]`` scores are 256 MB at
    the cell's window)."""
    q = rounder(quant)
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n = x.shape[0]
    qg = _dot(x, p["attn/q"], q).reshape(n, h, 2 * hd)
    gate = qg[..., hd:]
    qh = q(rope_partial(rms0(qg[..., :hd], p["attn/q_norm"], eps), cfg))
    kh = q(rope_partial(rms0(_dot(x, p["attn/k"], q).reshape(n, hkv, hd),
                             p["attn/k_norm"], eps), cfg))
    vh = q(_dot(x, p["attn/v"], q).reshape(n, hkv, hd))
    causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]

    def one_head(i):
        kv = i // (h // hkv)
        scores = jnp.dot(qh[:, i], kh[:, kv].T, precision=HIGHEST) \
            * hd ** -0.5
        w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.dot(q(w), vh[:, kv], precision=HIGHEST)

    out = output_gate(jax.lax.map(one_head, jnp.arange(h)).transpose(1, 0, 2),
                      gate)                              # [L, h, hd]
    return _dot(out.reshape(n, h * hd), p["attn/o"], q)


def gated(x, gate, up, down, q):
    return _dot(jax.nn.silu(_dot(x, gate, q)) * _dot(x, up, q), down, q)


def shared_expert(p: dict, x, q):
    """The shared expert, for every token, times ``sigmoid(x w_sg)``."""
    return jax.nn.sigmoid(_dot(x, p["shared/expert_gate"], q)) * gated(
        x, p["shared/gate"], p["shared/up"], p["shared/down"], q)


def route(p: dict, x, cfg: dict, q):
    """``(picks [L, k], weights [L, k], margin [L])``: the top-k of the
    softmax over the router's whole width, the weights renormalised over the
    picks, and the gap between the last pick's probability and the next's
    where one of the two experts is held here (infinite elsewhere: a swap of
    two absent experts changes no term this share computes, only the
    renormalisation, by the order of the gap)."""
    k = cfg["num_experts_per_tok"]
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]
    probs = jax.nn.softmax(_dot(x, p["router/kernel"], q), axis=-1)
    top, picks = jax.lax.top_k(probs, k + 1)
    weights = top[:, :k]
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    here = (picks[:, k - 1:] >= first) & (picks[:, k - 1:] < first + held)
    margin = jnp.where(here.any(axis=-1), top[:, k - 1] - top[:, k],
                       jnp.inf)
    return picks[:, :k], weights, margin


def moe(p: dict, x, cfg: dict, quant: str | None = None,
        fault: str | None = None, parts: bool = False):
    """The expert layer on ``x`` ``[L, d]`` (already normed): ``(y,
    margin)``, or ``(routed, shared, margin)`` with ``parts``."""
    q = rounder(quant)
    picks, weights, margin = route(p, x, cfg, q)
    first, held = cfg.get("first_expert", 0), cfg["num_experts"]

    def one_expert(e, routed):
        # every token through held expert e, weighted by its pick of it (0
        # for most); a loop the compiler sees once, not ``held`` copies
        src = jnp.where(e < 2, e ^ 1, e) if fault == "expert_swapped" else e
        w_e = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=-1)
        return routed + w_e[:, None] * gated(
            x, p["routed/gate"][src], p["routed/up"][src],
            p["routed/down"][src], q)

    routed = jax.lax.fori_loop(0, held, one_expert, jnp.zeros_like(x))
    shared = shared_expert(p, x, q)
    return (routed, shared, margin) if parts else (routed + shared, margin)


def layer(p: dict, x, cfg: dict, kind: str, quant: str | None = None,
          fault: str | None = None):
    """One layer whose mixer is ``kind`` on one row, its expert layer after:
    ``(y [L, d], routing margin [L])``."""
    # a program in a wide ``quant`` holds the residual stream in it too
    stream = rounder(quant) if _wide(quant) else (lambda a: a)
    eps = cfg["rms_norm_eps"]
    normed = rms0(x, p["norms/input_norm"], eps)
    if kind == "gdn":
        mixed = gdn_mixer(p, normed, cfg, quant, fault)
    else:
        mixed = attention(p, normed, cfg, quant)
    h = stream(x + mixed)
    y, margin = moe(p, rms0(h, p["norms/post_norm"], eps), cfg, quant, fault)
    return stream(h + y), margin


def head_logits(outer: dict, x, cfg: dict, quant: str | None = None):
    x = rms0(x, outer["norm_f"], cfg["rms_norm_eps"])
    return _dot(x, outer["head/kernel"], rounder(quant))


def forward(params: dict, tokens, cfg: dict, quant: str | None = None,
            fault: str | None = None) -> dict:
    """One row of token ids ``[L]`` through a whole tree of
    :func:`make_params`: ``features``, ``logits``, ``token_logprob`` and the
    routing ``margin`` (the least over layers, per token)."""
    x = params["outer"]["embed/embedding"][tokens]
    margin = jnp.full((tokens.shape[0],), jnp.inf, jnp.float32)
    for i, p in enumerate(params["layers"]):
        x, m = layer(p, x, cfg, layer_kind(cfg, i), quant, fault)
        margin = jnp.minimum(margin, m)
    logits = head_logits(params["outer"], x, cfg, quant)
    feats = jnp.mean(rms0(x, params["outer"]["norm_f"], cfg["rms_norm_eps"]),
                     axis=0)
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
