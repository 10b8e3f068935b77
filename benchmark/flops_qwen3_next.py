"""Operations and bytes of a Gated DeltaNet / gated-attention / expert
language model's forward pass (family ``qwen3_next``), from the
configuration's sizes alone.

As in ``flops.py``, ``flops_lm.py`` and ``flops_nemotron_h.py``: one
multiply-accumulate is two operations, only the matrix products are counted
towards the step's share of the peak (the convolution's four taps a
channel, the decays' exponentials, the norms and the gates are left out),
nothing comes from the compiler. Attention is counted causally and moves the
keys and values once a KEY/VALUE head. The routed experts' work is counted
at the picks the rows REALLY sent to held experts, which the driver reads
from the program's own load counts (``window["moe"]``). The delta-rule
recurrence is counted in its chunked form at ``CHUNK`` positions (the public
kernel's), whatever chunk a kernel takes, so the count is the same work
whatever implements it or fuses around it (:func:`gated_delta_flops`,
:func:`gated_delta_bytes`).
"""

from __future__ import annotations

# the chunk of the public kernel's chunked form, which the count follows
CHUNK = 64


def kinds(cfg: dict) -> list:
    """The mixer of every layer, in order (each is followed by an expert
    layer)."""
    return ["attention" if (i + 1) % cfg["full_attention_interval"] == 0
            else "gdn" for i in range(cfg["num_hidden_layers"])]


def count(cfg: dict, kind: str) -> int:
    """Layers of ``kind``: ``gdn``, ``attention`` or ``moe`` (every one)."""
    return (cfg["num_hidden_layers"] if kind == "moe"
            else kinds(cfg).count(kind))


def _gdn_sizes(cfg: dict) -> tuple:
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return (hk, cfg["linear_key_head_dim"], hv, cfg["linear_value_head_dim"])


def gated_delta_flops(cfg: dict, window: int) -> int:
    """The recurrence of one row in one Gated DeltaNet layer, as the matrix
    products of its chunked form at chunks of ``CHUNK``, every value head
    with its key head's copy: a chunk's ``q k^T``, ``k k^T``, ``W = T (beta
    k e)`` (``[C, C] x [C, dk]`` each), ``U = T (beta v)`` and the masked
    ``[C, C] x [C, dv]`` read-out, and the state's three (``W S``, ``q S``,
    ``k^T N``: ``dk x dv`` a position each); the triangular solve (``C^3 /
    3`` a chunk) is left out."""
    _, dk, hv, dv = _gdn_sizes(cfg)
    return 2 * window * hv * (CHUNK * (3 * dk + 2 * dv) + 3 * dk * dv)


def gated_delta_bytes(cfg: dict, window: int) -> int:
    """The least one row's recurrence moves in one Gated DeltaNet layer:
    ``q`` and ``k`` read once a KEY head and ``v`` once, and ``o`` written
    once, in the compute type (2 bytes); ``g`` and ``beta`` read in float32;
    the state never leaves the chip's fast memory."""
    hk, dk, hv, dv = _gdn_sizes(cfg)
    return window * (2 * (2 * hk * dk + 2 * hv * dv) + 2 * 4 * hv)


def token_flops(cfg: dict) -> dict:
    """Matrix-product operations of ONE token in ONE layer of each kind;
    ``routed`` is one (token, held expert) pair's, ``head`` the whole
    head's, ``gdn`` the projections and the recurrence's products."""
    d = cfg["hidden_size"]
    hk, dk, hv, dv = _gdn_sizes(cfg)
    hd, h = cfg["head_dim"], cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    return {
        "gdn": (2 * d * (2 * hk * dk + 2 * hv * dv + 2 * hv) + 2 * hv * dv * d
                + gated_delta_flops(cfg, 1)),
        "attention_projections": 2 * (d * 2 * h * hd + 2 * d * kv + h * hd * d),
        "router": 2 * d * cfg["router_width"],
        "routed": 6 * d * f,
        "shared": 6 * d * fs + 2 * d,
        "head": 2 * d * cfg["vocab_size"],
    }


def attention_core_flops(cfg: dict, window: int) -> int:
    """``q.k`` and ``p.v`` of one row of ``window`` tokens in one attention
    layer, every query head against the keys up to itself."""
    return (2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * window * (window + 1) // 2)


def attention_core_bytes(cfg: dict, window: int) -> int:
    """The least one row's attention core moves in one layer: ``q`` read
    and ``k``, ``v`` read ONCE A KEY/VALUE HEAD in the compute type (2
    bytes), the output written in float32."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return window * cfg["head_dim"] * (2 * hq + 2 * 2 * hkv + 4 * hq)


def forward_flops(cfg: dict, window: int, held_pairs_per_token: float
                  ) -> dict:
    """Forward matrix-product operations of one row (a window of tokens),
    by part; ``held_pairs_per_token`` is the measured held picks a token an
    expert layer."""
    part = token_flops(cfg)
    n_attn, n_moe = count(cfg, "attention"), count(cfg, "moe")
    parts = {
        "gdn": count(cfg, "gdn") * window * part["gdn"],
        "attention_projections":
            n_attn * window * part["attention_projections"],
        "attention": n_attn * attention_core_flops(cfg, window),
        "router": n_moe * window * part["router"],
        "routed": n_moe * window * part["routed"] * held_pairs_per_token,
        "shared": n_moe * window * part["shared"],
        "head": window * part["head"],
    }
    parts["total"] = sum(parts.values())
    return parts


def grouped_product_work(cfg: dict, pairs: float, steps: float) -> dict:
    """``name -> (operations, bytes)`` of the three grouped expert products
    over ``pairs`` (token, held expert) pairs met in ``steps``
    expert-layer-steps (a step reads every held expert's matrix once: at
    hundreds of pairs an expert a step none goes unvisited). Operands 2
    bytes; gate and up come out in float32, down in the compute type."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = steps * cfg["num_experts"] * d * f * 2
    ops = 2 * pairs * d * f
    return {"gate": (ops, weights + pairs * (2 * d + 4 * f)),
            "up": (ops, weights + pairs * (2 * d + 4 * f)),
            "down": (ops, weights + pairs * (2 * f + 2 * d))}
